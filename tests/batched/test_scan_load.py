"""A load plane (ISSUE 47): ``run_rounds(load=(update_thr, read_thr,
seed))``, the third form of a scan's input: every group is offered its
own updates and reads, drawn on the device round by round from (seed,
round, group, stream) against the group's thresholds, where the other
two forms offer every group the same thing in the same round. Groups
share nothing, so thresholds no draw can miss are today's controlled
scan with P proposals and a read a round, thresholds none can meet a
scan with nothing offered; two and four tiles equal one scan; two calls
of 32 rounds equal one of 64 (``load_round`` carried); the device's
draws equal a plain numpy copy of the rule bit for bit; and the oracle
(``batched/shadow.py``) stepped group by group on each group's own
draws agrees history by history. With no `load` nothing of this reaches
a program (the digest tests in ``test_scan_replace.py`` and
``test_scopes.py`` stay as they are).

Round-step programs (``conftest.py``, ISSUE 47 audit): none new. Every
engine here is ``test_scan_reconf.RC3`` (the benchmark's ``engine1m-r3``
values at the CPU tests' 8 groups, a key since ISSUE 32): the load
plane, like the schedules, is an input of the closed-loop program and
no key of the round step.
"""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import MultiRaftEngine
from etcd_tpu.batched import engine as engine_mod
from etcd_tpu.batched.engine import (CTL_COLS, CTL_READS, LOAD_ALWAYS,
                                     LOAD_COUNT_NAMES)
from .test_scan_phased import assert_same_run, observed, run_spans
from .test_scan_reconf import (RC3, assert_equal_to_the_oracle,
                               make_shadows)
from .test_scopes import SCOPES, scoped

G, R, P = RC3.num_groups, RC3.num_replicas, RC3.max_props_per_round
CALL = 32
ROUNDS = 64
SEED = 2**31 + 4711
M = 0xFFFFFFFF
# A skewed table: a group always offered P updates and a read, one
# never offered anything, and shares between them.
UPDATE_THR = np.asarray([LOAD_ALWAYS, 0, 1 << 31, 1 << 30, 1 << 24, 1 << 29,
                         3 << 30, 0], np.uint32)
READ_THR = np.asarray([LOAD_ALWAYS, 0, 1 << 30, 1 << 31, 0, 1 << 26,
                       LOAD_ALWAYS, 1 << 28], np.uint32)


# -- the rule, in plain numpy (the LOAD_* comment in engine.py) ----------------------


def np_fmix32(x):
    x = np.asarray(x, np.uint64)
    x = ((x ^ (x >> 16)) * 0x85EBCA6B) & M
    x = ((x ^ (x >> 13)) * 0xC2B2AE35) & M
    return x ^ (x >> 16)


def np_word(seed, t, groups, k):
    """Stream k's draw of each of `groups` in round t: uint64 holding
    32 bits."""
    base = np_fmix32((seed + t * 0x9E3779B1) & M)
    key = (np.asarray(groups, np.uint64) * 0x85EBCA77 + k * 0xC2B2AE3D) & M
    return np_fmix32(base ^ key)


def np_offers(update_thr, read_thr, seed, t):
    """(updates offered [G], read asked [G]) in round t."""
    groups = np.arange(len(update_thr))
    upd, rd = update_thr.astype(np.uint64), read_thr.astype(np.uint64)
    n = sum(((np_word(seed, t, groups, k) < upd) | (upd == M)).astype(int)
            for k in range(P))
    return n, (np_word(seed, t, groups, P) < rd) | (rd == M)


# -- engines -------------------------------------------------------------------------


def settled(tiles: int = 0, monkeypatch=None) -> MultiRaftEngine:
    """RC3 with its leaders from a seed, settled by eager rounds."""
    if monkeypatch is not None:
        monkeypatch.setattr(engine_mod, "TILE_ALIGN", 1)
        monkeypatch.setattr(engine_mod, "TILE_ROWS",
                            RC3.num_instances // tiles if tiles else 1 << 40)
    eng = MultiRaftEngine(RC3)
    lead = np.random.default_rng(47).integers(0, R, G)
    eng.campaign(np.arange(G) * R + lead)
    for _ in range(16):
        eng.step_round()
    assert (eng.leaders() == lead).all()
    eng.first_leaders = lead
    return eng


def run_load(eng, update_thr=UPDATE_THR, read_thr=READ_THR, rounds=ROUNDS,
             call=CALL, seed=SEED):
    for _ in range(0, rounds, call):
        eng.run_rounds(call, load=(update_thr, read_thr, seed))
    return eng


def run_control(eng, offer: int, reads: bool, rounds=ROUNDS):
    ctl = np.zeros((CALL, CTL_COLS), np.int32)
    ctl[:, CTL_READS] = int(reads)
    props = jnp.full((RC3.num_instances,), offer, jnp.int32)
    for _ in range(0, rounds, CALL):
        eng.run_rounds(CALL, propose_n=props, control=ctl)
    return eng


@functools.cache
def skewed() -> dict:
    eng = run_load(settled())
    return dict(observed(eng), counts=eng.load_counts())


# -- (a) the two ends are today's scans -----------------------------------------------


@pytest.mark.parametrize("thr, offer, reads", [
    (LOAD_ALWAYS, P, True), (0, 0, False)], ids=["always", "never"])
def test_a_threshold_at_either_end_is_todays_controlled_scan(thr, offer,
                                                             reads):
    """Thresholds no draw can miss: P proposals and a read a round for
    every group; thresholds none can meet: nothing offered. State,
    inbox, history, read floor, lane counts, ScanWatch and telemetry."""
    flat = np.full(G, thr, np.uint32)
    eng = run_load(settled(), flat, flat)
    assert_same_run(observed(eng), observed(run_control(settled(), offer,
                                                        reads)))
    assert eng.load_counts() == {
        "offered": offer * G * ROUNDS, "reads_asked": reads * G * ROUNDS,
        "active": reads * G * ROUNDS}


def test_a_group_offered_nothing_appends_nothing_and_keeps_its_leader():
    got = skewed()
    quiet = np.flatnonzero((UPDATE_THR == 0) & (READ_THR == 0))
    assert quiet.tolist() == [1]
    before = settled()
    fields = type(before.state)._fields
    last = got["state"][fields.index("last")].reshape(G, R)
    role = got["state"][fields.index("role")].reshape(G, R)
    assert (last[quiet] == np.asarray(before.state.last).reshape(
        G, R)[quiet]).all()
    assert (role.argmax(axis=1) == before.first_leaders).all()
    assert not got["telemetry"][1].any(), "an invariant bit is set"
    assert got["watch"]["reads_below_commit"] == 0


# -- (b) tiles, and calls ---------------------------------------------------------------


@pytest.mark.parametrize("n_tiles", [2, 4])
def test_the_tiled_load_scan_equals_the_one_scan(n_tiles, monkeypatch):
    eng = run_load(settled(tiles=n_tiles, monkeypatch=monkeypatch))
    assert eng._tiles == n_tiles
    assert_same_run(observed(eng), skewed())
    assert eng.load_counts() == skewed()["counts"]


def test_two_calls_of_32_rounds_equal_one_of_64():
    eng = run_load(settled(), call=ROUNDS)
    assert eng.load_round == ROUNDS
    assert_same_run(observed(eng), skewed())
    assert eng.load_counts() == skewed()["counts"]


# -- (c) the draws ----------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 47, 2**31 + 42, LOAD_ALWAYS])
def test_the_devices_draws_are_the_numpy_copys_bit_for_bit(seed):
    groups = np.concatenate([np.arange(64), [65535, 65536, 1048575],
                             np.random.default_rng(seed).integers(
                                 0, 1 << 20, 32)])
    key = jnp.asarray(groups, jnp.uint32) * jnp.uint32(
        engine_mod.LOAD_GROUP_MUL)
    for t in (0, 1, 63, 64, 770, 100_003, 2**31 - 1):
        base = engine_mod.load_base(jnp.uint32(seed), jnp.int32(t))
        for k in range(P + 1):
            got = np.asarray(engine_mod.load_word(base, key, k))
            assert got.dtype == np.uint32
            assert (got == np_word(seed, t, groups, k)).all(), (t, k)


def test_the_draws_are_spread_and_the_streams_apart():
    """No statistics of the mix: a million draws fill every 256th of
    the range within a few percent, and two streams of one group and
    round are not one word."""
    groups = np.arange(1 << 16)
    words = [np_word(SEED, t, groups, k) for t in range(8) for k in range(2)]
    hist = np.bincount((np.concatenate(words) >> 24).astype(np.int64),
                       minlength=256)
    assert abs(hist / hist.mean() - 1).max() < 0.06
    assert (words[0] != words[1]).mean() > 0.999


def test_the_counts_are_the_replay_of_the_draws():
    want = dict.fromkeys(LOAD_COUNT_NAMES, 0)
    for t in range(ROUNDS):
        n, read = np_offers(UPDATE_THR, READ_THR, SEED, t)
        want["offered"] += int(n.sum())
        want["reads_asked"] += int(read.sum())
        want["active"] += int((read | (n > 0)).sum())
    assert skewed()["counts"] == want
    assert 0 < want["active"] < G * ROUNDS
    assert settled().load_counts() == dict.fromkeys(LOAD_COUNT_NAMES, 0)


# -- (d) against the program's oracle, history by history ------------------------------


def test_the_skewed_table_against_the_oracle_history_by_history():
    eng = settled()
    shadows = make_shadows(RC3)
    for g, sh in enumerate(shadows):
        sh.round(campaigns=[int(eng.first_leaders[g])])
        for _ in range(16):
            sh.round()
    run_load(eng)
    history = [0] * RC3.num_instances
    bits = lambda ids: sum(1 << i for i in ids)  # noqa: E731
    for t in range(ROUNDS):
        n, read = np_offers(UPDATE_THR, READ_THR, SEED, t)
        for g, sh in enumerate(shadows):
            sh.round(tick=True, offer=int(n[g]), reads=bool(read[g]))
            for s, (st, mem, rd) in enumerate(zip(
                    sh.snapshot_state(), sh.membership(), sh.read_state())):
                term, role, lead, commit, last = st
                history[g * R + s] = engine_mod.history_fold(
                    history[g * R + s],
                    (term, role, lead, commit, last, *rd, bool(mem[1]),
                     bits(mem[0]), bits(mem[1]), bits(mem[2])))
    assert eng.scan_history().tolist() == history
    assert_equal_to_the_oracle(eng, shadows, "after the run")


# -- the refusals, the span --------------------------------------------------------------


@pytest.mark.parametrize("kw, word", [
    (dict(propose_n=jnp.zeros((G * R,), jnp.int32)), "propose_n"),
    (dict(control=np.zeros((4, CTL_COLS), np.int32)), "control"),
    (dict(control=np.zeros((4, CTL_COLS), np.int32),
          starts=np.zeros(G, np.int32)), "control"),
])
def test_a_load_plane_beside_another_offer_is_refused(kw, word):
    eng = MultiRaftEngine(RC3)
    with pytest.raises(ValueError, match=f"not with {word}"):
        eng.run_rounds(4, load=(UPDATE_THR, READ_THR, 1), **kw)
    assert eng.load_round == 0


@pytest.mark.parametrize("load", [
    (UPDATE_THR, READ_THR),
    (UPDATE_THR[:-1], READ_THR, 1),
    (UPDATE_THR, READ_THR[None], 1),
    (UPDATE_THR.astype(np.int32), READ_THR, 1),
    (UPDATE_THR, READ_THR.astype(np.float32), 1),
    (UPDATE_THR, READ_THR.astype(np.uint64), 1),
    (UPDATE_THR, READ_THR, -1),
    (UPDATE_THR, READ_THR, 2**32),
    (UPDATE_THR, READ_THR, 0.5),
], ids=["no-seed", "short", "2d", "int32", "float", "uint64", "negative",
        "wide", "fraction"])
def test_a_load_plane_of_another_shape_or_kind_is_refused(load):
    eng = MultiRaftEngine(RC3)
    with pytest.raises(ValueError, match="load|thr|seed"):
        eng.run_rounds(4, load=load)
    assert eng.load_round == 0 and eng._watch is None


def test_a_load_plane_over_nodes_is_refused():
    eng = MultiRaftEngine(RC3, nodes=jax.devices()[:R])
    with pytest.raises(ValueError, match="not with nodes"):
        eng.run_rounds(4, load=(UPDATE_THR, READ_THR, 1))


def test_the_span_says_where_the_load_rounds_began():
    eng = run_load(settled(), rounds=3 * CALL)
    got = run_spans(eng)[-3:]
    assert [s["load_from"] for s in got] == [0, CALL, 2 * CALL]
    for s in got:
        assert (s["rounds"], s["tiles"], s["isolated"]) == (CALL, 1, 0)
        assert (s["reads"], s["conf_ops"], s["transfers"]) == (0, 0, 0)
    controlled = run_control(settled(), 2, True, rounds=CALL)
    assert "load_from" not in run_spans(controlled)[-1]


# -- with no load plane the programs are the parent's ----------------------------------


def test_with_no_load_plane_the_scan_is_the_parents_text(tmp_path):
    """An engine that has run load scans still lowers its round and its
    controlled 64-round closed loop to the texts ``test_scan_replace.py``
    pins for ``engine1m-r3``: the third form is told by `load` being
    given and reaches no other program."""
    from . import lowered_text
    from .test_scan_replace import PARENT_TEXT

    eng = run_load(settled(), rounds=CALL)
    zb, zi = eng._zeros_b, eng._zeros_i
    one = jax.jit(eng._step).lower(
        eng.state, eng.inbox, zb, zb, zi, zb).as_text()
    loop = eng._closed_loop.lower(
        eng.state, eng.inbox, zb, zi, eng._tel(), eng._flt(), eng._lanes,
        jnp.zeros((64, R), bool), 64,
        jnp.zeros((64, CTL_COLS), jnp.int32), eng._watch).as_text()
    lowered_text.held_to_the_pin(
        (one, loop), PARENT_TEXT["engine1m-r3"], tmp_path,
        ("tests.batched.test_scan_replace", "_lowered", "engine1m-r3"),
        "after load scans the round or the controlled closed loop is not "
        "the pinned text")


# -- every equation under a registered scope, and ``raft_load`` with teeth -------------


def load_loop_args(eng) -> tuple:
    """(args, kwargs) of the closed loop as a load scan of 4 rounds
    hands them."""
    ctl, plane, _ = eng._load_schedule((UPDATE_THR, READ_THR, SEED), 4)
    return ((eng.state, eng.inbox, eng._zeros_b, eng._zeros_i, eng._tel(),
             eng._flt(), eng._lanes + (eng._tally,), None, 4, ctl,
             eng._watch), dict(load=plane))


def traced(eng):
    args, kwargs = load_loop_args(eng)
    return scoped(jax.make_jaxpr(eng._closed_loop, static_argnums=(8,))(
        *args, **kwargs).jaxpr)


@pytest.mark.parametrize("n_tiles", [1, 2])
def test_every_equation_of_the_load_scan_has_a_registered_scope(
        n_tiles, monkeypatch):
    eng = settled(tiles=n_tiles if n_tiles > 1 else 0,
                  monkeypatch=monkeypatch)
    by_scope, bare = traced(eng)
    assert not bare, bare[:10]
    assert "raft_load" in SCOPES and by_scope["raft_load"] > 0
    assert "raft_watch" in by_scope and "raft_phase" not in by_scope
    # P + 1 draws of a dozen ops, their compares, three counts and the
    # limbs; the thresholds widened once a call and sliced once a tile.
    assert by_scope["raft_load"] < 120
    # A controlled scan holds none of it.
    ctl, _ = eng._control_schedule(np.zeros((4, CTL_COLS), np.int32), 4)
    args, _kw = load_loop_args(eng)
    jaxpr = jax.make_jaxpr(eng._closed_loop, static_argnums=(8,))(
        *args[:6], eng._lanes, None, 4, ctl, eng._watch).jaxpr
    assert "raft_load" not in scoped(jaxpr)[0]


def test_without_its_scope_the_draws_are_filed_with_the_carry(monkeypatch):
    """``raft_load`` taken away (its ``with`` a no-op): the thresholds'
    widening, once a call, stands under no name, and the round's draws
    under the scope round them: a trace would show the carry's share
    grown by the load plane's."""
    named, _ = traced(settled())
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda s: contextlib.nullcontext() if s == "raft_load" else real(s))
    bare_of_it, bare = traced(settled())
    assert "raft_load" not in bare_of_it and bare
    assert bare_of_it["raft_carry"] + len(bare) == (
        named["raft_carry"] + named["raft_load"])
