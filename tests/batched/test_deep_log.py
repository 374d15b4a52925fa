"""The log as term runs (ISSUE 50, ``BatchedConfig.log_runs``,
``batched/termlog.py``): the representation against a plain Python list
on seeded random logs, a full run table included; the device round
against the shadow oracle through a cut, a heal with a stale leader's
suffix, the reject hint, PROBE -> REPLICATE and catch-up by appends at a
small deep window; the refusals; and, beside the pins of
``lowered_text.py``'s family, the field off as the parent's text.

Round-step programs: ``DEEP`` is this file's one (window 512, 256 kept,
K=8, E=16 at 8 groups); ``tests/benchmark/test_catchup.py`` builds the
cell's own at its tiny root (``conftest.py``, ISSUE 50 audit).
"""

import json
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine, termlog
from etcd_tpu.batched.shadow import ShadowCluster
from etcd_tpu.batched.state import LEADER, REPLICATE
from etcd_tpu.batched.telemetry import INV_NAMES, TM_INDEX
from etcd_tpu.batched.engine import CATCHUP_NAMES

from . import lowered_text
from .test_differential import device_state
from .test_scan_replace import PARENT_TEXT

DEEP = BatchedConfig(
    num_groups=8, num_replicas=3, window=512, max_ents_per_msg=16,
    max_props_per_round=2, election_timeout=10, heartbeat_timeout=1,
    max_inflight=512, pre_vote=True, check_quorum=True, auto_compact=True,
    lanes_minor=True, telemetry=True, log_runs=8)
KEPT = DEEP.window // 2


def runs_log(runs, snap_index: int, last: int):
    """[(index, term)] above the floor, as the run table says it."""
    start, term = np.asarray(runs[0]), np.asarray(runs[1])
    return [(i, int(term[(term > 0) & (start <= i)].max(initial=0)))
            for i in range(snap_index + 1, last + 1)]


def device_log(eng, inst: int):
    st = eng.state
    return runs_log(np.asarray(st.log_term[inst]), int(st.snap_index[inst]),
                    int(st.last[inst]))


# -- (a) the representation against a plain list ----------------------------------


class Log(NamedTuple):
    """What termlog's interface reads and writes of a state."""

    log_term: jnp.ndarray
    snap_index: jnp.ndarray
    snap_term: jnp.ndarray
    last: jnp.ndarray
    term: jnp.ndarray


class Plain:
    """The same log as a plain dict index -> term above a floor."""

    def __init__(self) -> None:
        self.snap, self.snap_term, self.last, self.terms = 0, 0, 0, {}

    def term_at(self, i: int) -> int:
        if i == self.snap:
            return self.snap_term
        return self.terms[i] if self.snap < i <= self.last else 0

    def find_conflict(self, index: int, term: int) -> int:
        i = min(index, self.last)
        while i > self.snap and self.terms[i] > term:
            i -= 1
        if i > self.snap:
            return i
        return self.snap if self.snap_term <= term else self.snap - 1

    def truncate_and_append(self, at: int, terms) -> None:
        for i in range(at, self.last + 1):
            del self.terms[i]
        for j, t in enumerate(terms):
            self.terms[at + j] = t
        self.last = at + len(terms) - 1

    def compact(self, to: int) -> None:
        self.snap_term = self.term_at(to)
        for i in range(self.snap + 1, to + 1):
            del self.terms[i]
        self.snap = to


def _scalar(x):
    return jnp.asarray(x, jnp.int32)


@pytest.mark.parametrize("k,seed", [(4, 1), (4, 2), (8, 3), (32, 4)])
def test_the_run_table_answers_as_a_plain_list_does(k, seed):
    """Seeded random logs: own-term appends across term changes,
    appends of a message's entries over a conflict (truncation), the
    floor chasing `applied`; after every step ``term_at`` of every index
    round the window and seeded reject hints equal the list's. Where a
    new term's slot holds a run the window needs (K=4 fills in a few
    term changes) the floor moves up early: to the last entry of that
    run, never past the log's end, and every answer above it stays the
    list's; the bit (floor above `applied`) is set only where that
    move passed `applied`. Without such a run in the slot the floor
    does not move."""
    e = 8
    cfg = DEEP._replace(log_runs=k, max_ents_per_msg=e)
    rng = np.random.default_rng([50, seed])
    own = jax.jit(lambda st, n: termlog.append_own(cfg, st, n, 2))
    ents = jax.jit(lambda st, prev, terms, write, ci, any_c:
                   termlog.append_entries(cfg, st, prev, terms, write, ci,
                                          any_c))
    t_at = jax.jit(lambda st, i: termlog.term_at(cfg, st, i))
    sweep = lambda st, i: termlog.term_at(cfg, st, i)  # noqa: E731 (any length)
    hint = jax.jit(lambda st, i, t: termlog.find_conflict(cfg, st, i, t))

    st = Log(jnp.zeros((2, k), jnp.int32), _scalar(0), _scalar(0),
             _scalar(0), _scalar(1))
    plain, applied, term = Plain(), 0, 1
    early = passed = 0

    def wanted(new_terms, from_index):
        """Terms of the window's kept entries whose slot a new term
        wants: the table is full for that term."""
        new = set(new_terms)
        return [t for i, t in plain.terms.items() if i < from_index and any(
            t != n and t % k == n % k for n in new)]

    def settle(st, want_moved: bool):
        """The device's floor after a write: the list follows an early
        move, which has to be a legal one."""
        nonlocal early, passed
        snap = int(st.snap_index)
        if snap != plain.snap:
            assert want_moved and plain.snap < snap <= plain.last
            assert int(st.snap_term) == plain.term_at(snap)
            plain.compact(snap)
            early += 1
            passed += snap > applied
        else:
            assert not want_moved
        assert int(st.snap_term) == plain.snap_term
        return st

    for step in range(400):
        op = rng.integers(0, 4)
        if op == 0:  # the leader's own entries, the term sometimes new
            term += int(rng.integers(0, 3) == 0) * int(rng.integers(1, 4))
            n = int(rng.integers(0, 3))
            full = bool(n) and bool(wanted([term], plain.last + 1))
            st = own(st._replace(term=_scalar(term)), _scalar(n))
            plain.truncate_and_append(plain.last + 1, [term] * n)
            st = settle(st._replace(last=_scalar(plain.last)), full)
        elif op == 1 and plain.last > plain.snap:  # a message's entries
            prev = int(rng.integers(max(plain.snap, applied),
                                    plain.last + 1))
            keep = int(rng.integers(0, min(e, plain.last - prev) + 1))
            msg = [plain.term_at(prev + 1 + j) for j in range(keep)]
            t = max([plain.term_at(prev + keep)] + msg)
            fresh = int(rng.integers(0, e - keep + 1))
            for j in range(fresh):
                # A conflicting entry is of a newer term than the one
                # it replaces (a later leader's).
                old = plain.term_at(prev + keep + 1 + j)
                t = max(t, old + 1) if j == 0 and old else t + int(
                    rng.integers(0, 4) == 0)
                # An append is cut from its leader's window, whose table
                # holds one run a slot: no two of its terms share one.
                if any(t != m and t % k == m % k
                       for m in msg + [plain.term_at(prev)]):
                    break
                msg.append(t)
            term = max(term, t)
            n = len(msg)
            idx = [prev + 1 + j for j in range(n)]
            conflict = [i > plain.last or plain.term_at(i) != m
                        for i, m in zip(idx, msg)]
            any_c = any(conflict)
            ci = conflict.index(True) if any_c else 0
            # The device's own conflict scan, as _handle_append makes it.
            padded = np.zeros(e, np.int32)
            padded[:n] = msg
            have = np.arange(e) < n
            existing = np.asarray(t_at(st, jnp.asarray(
                prev + 1 + np.arange(e), jnp.int32)))
            dev_conf = have & ((prev + 1 + np.arange(e) > plain.last)
                               | (existing != padded))
            assert dev_conf[:n].tolist() == conflict
            write = have & (np.arange(e) >= ci) & any_c
            full = any_c and bool(wanted(msg[ci:], prev + 1 + ci))
            st = ents(st, _scalar(prev), jnp.asarray(padded),
                      jnp.asarray(write), _scalar(ci), jnp.asarray(any_c))
            if any_c:
                plain.truncate_and_append(prev + 1 + ci, msg[ci:])
            st = settle(st._replace(last=_scalar(plain.last)), full)
        elif op == 2:  # apply, and the floor follows it
            applied = int(rng.integers(applied, plain.last + 1))
            to = int(rng.integers(plain.snap, max(applied, plain.snap) + 1))
            st = st._replace(snap_term=t_at(st, _scalar(to)),
                             snap_index=_scalar(to))
            plain.compact(to)
            assert int(st.snap_term) == plain.snap_term
        lo, hi = plain.snap - 2, plain.last + 3
        got = np.asarray(sweep(st, jnp.arange(lo, hi, dtype=jnp.int32)))
        assert got.tolist() == [plain.term_at(i) for i in range(lo, hi)], step
        assert runs_log(st.log_term, plain.snap, plain.last) == sorted(
            plain.terms.items())
        for _ in range(4):
            i = int(rng.integers(lo, hi))
            t = int(rng.integers(0, term + 2))
            assert int(hint(st, _scalar(i), _scalar(t))) == (
                plain.find_conflict(i, t)), (step, i, t)
    if k == 4:
        assert early > 0, "the table never filled"
    # The bit is the end-of-round state's `snap_index > applied`
    # (kernels.invariant_bits): never set by a move that stayed at or
    # below `applied`, which `passed` counts the others of.
    assert passed <= early


def test_a_full_table_sets_the_bit_only_where_applied_is_passed():
    """Five terms inside the entries a replica has yet to apply, K=4:
    the fifth term's slot holds the first's run, the floor moves up
    past it and stands above `applied`; the end-of-round bitmap says
    so. The same log with everything applied moves the floor as far
    and sets no bit."""
    from etcd_tpu.batched.kernels import invariant_bits
    from etcd_tpu.batched.state import init_state

    cfg = DEEP._replace(log_runs=4, num_groups=1)
    bit = 1 << INV_NAMES.index("runs_passed_applied")
    for applied, want in ((0, bit), (12, 0)):
        st = jax.tree.map(lambda x: x[0], init_state(cfg))
        for term in range(1, 6):
            st = st._replace(term=_scalar(term))
            st = termlog.append_own(cfg, st, _scalar(3), 3)
            st = st._replace(last=st.last + 3, commit=st.last + 3)
        assert (int(st.snap_index), int(st.snap_term)) == (3, 1)
        assert runs_log(st.log_term, 3, 15) == [
            (i, (i - 1) // 3 + 1) for i in range(4, 16)]
        st = st._replace(applied=_scalar(applied))
        bits = int(invariant_bits(st, _scalar(0), cfg.window))
        assert bits & bit == want
        assert not bits & (1 << INV_NAMES.index("ring_over_window"))


# -- (b) the device round against the oracle ------------------------------------


PERIOD, CUT_FROM, CUT_ROUNDS = 160, 16, 96


def _cut_node(rnd: int, k0: int, r: int):
    period, t = divmod(rnd, PERIOD)
    return (k0 + period) % r if CUT_FROM <= t < CUT_FROM + CUT_ROUNDS else None


def test_catch_up_by_appends_matches_the_oracle_every_round():
    """Three periods of the cell's schedule in small: a node in turn is
    away for 96 rounds under 2 proposals a round (192 entries behind of
    the 256 kept, twelve appends of E=16 deep), the groups it led elect
    another leader and it returns with its uncommitted suffix to
    truncate. State and log of every replica equal the oracle's after
    every round; no snapshot is ever sent; rejects and hints moved; the
    node that was away stands in REPLICATE and level within 48 rounds
    of its heal; the catch-up counts say what the rounds did."""
    eng = MultiRaftEngine(DEEP)
    cfg = eng.cfg
    g_n, r, n = cfg.num_groups, cfg.num_replicas, cfg.num_instances
    slots = np.random.default_rng(5000).integers(0, r, g_n)
    shadows = [
        ShadowCluster(
            r, election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, pre_vote=True,
            check_quorum=True, group=g, deterministic_timeouts=True,
            auto_compact_window=cfg.window, max_ents=cfg.max_ents_per_msg,
            max_props=cfg.max_props_per_round)
        for g in range(g_n)]
    eng.campaign(np.arange(g_n) * r + slots)
    for g, sh in enumerate(shadows):
        sh.round(campaigns=[int(slots[g])])
    for _ in range(16):
        eng.step_round()
        for sh in shadows:
            sh.round()
    props = jnp.full((n,), 2, jnp.int32)
    node_of = np.arange(n) % r
    deepest = truncated = 0
    rounds = 3 * PERIOD
    sched = np.zeros((rounds, r), bool)
    for rnd in range(rounds):
        k = _cut_node(rnd, 1, r)
        if k is not None:
            sched[rnd, k] = True
        before = np.asarray(eng.state.last)
        # One round a scan: the scan's own program, so the carry counts.
        eng.run_rounds(1, tick=True, propose_n=props,
                       isolate=sched[rnd:rnd + 1])
        for sh in shadows:
            sh.round(tick=True, offer=2, isolate=() if k is None else (k,))
        got = device_state(eng, cfg)
        want = [s for sh in shadows for s in sh.snapshot_state()]
        assert got == want, f"round {rnd}"
        for g, sh in enumerate(shadows):
            for s in range(r):
                assert device_log(eng, g * r + s) == sh.log_terms(s), (
                    f"round {rnd} group {g} replica {s}")
        commit = eng.commits()
        deepest = max(deepest, int((commit.max(axis=1)[:, None]
                                    - commit).max()))
        truncated += int((np.asarray(eng.state.last) < before).sum())
        t = rnd % PERIOD
        if t == CUT_FROM + CUT_ROUNDS + 48:
            k = _cut_node(rnd - 49, 1, r)
            st = eng.state
            role = np.asarray(st.role).reshape(g_n, r)
            at = (role == LEADER).argmax(axis=1)
            assert ((role == LEADER).sum(axis=1) == 1).all()
            progress = np.asarray(st.pr_state).reshape(g_n, r, r)[
                np.arange(g_n), at, k]
            assert ((progress == REPLICATE) | (at == k)).all()
            assert (commit.max(axis=1) - commit[:, k]
                    <= cfg.max_ents_per_msg).all()
    counters, invariants = eng.telemetry()
    assert not invariants.any()
    total = counters.sum(axis=0)
    assert total[TM_INDEX["sent_snapshot"]] == 0
    assert total[TM_INDEX["to_snapshot"]] == 0
    for name in ("elections_won", "append_rejected", "probe_to_replicate"):
        assert total[TM_INDEX[name]] > 0, name
    assert deepest > 150, "no replica fell deep behind"
    assert truncated > 0, "no stale suffix was truncated"
    floor = np.asarray(eng.state.snap_index)
    # The depth: every replica holds the entries kept above its floor
    # (the floor is min(applied, last - kept): step._apply_and_compact).
    assert (np.asarray(eng.state.last) - floor == KEPT).all()
    assert (np.asarray(eng.state.applied) >= floor).all()
    counts = eng.catchup_counts()
    assert tuple(counts) == CATCHUP_NAMES
    # Three heals of 8 replicas, each some 12 rounds and 12 appends of
    # 16 entries deep.
    assert 3 * g_n * 8 < counts["behind_rounds"] < 3 * g_n * 24
    assert counts["catchup_entries"] > 0.8 * 16 * counts["catchup_appends"]
    from etcd_tpu.obs import spans

    mine = [s for s in spans.snapshot() if s.name == "engine.run_rounds"
            and s.stats.get("engine") == eng._serial]
    assert sum(s.stats["healed"] for s in mine) == 3


def test_the_deep_round_holds_nothing_of_the_windows_size():
    """No value of the lowered round or closed loop has a dimension of
    the window (512) or of the entries kept: every question of the log
    is a pass over K."""
    eng = MultiRaftEngine(DEEP)
    zb, zi = eng._zeros_b, eng._zeros_i
    one = jax.jit(eng._step).lower(
        eng.state, eng.inbox, zb, zb, zi, zb).as_text()
    loop = eng._closed_loop.lower(
        eng.state, eng.inbox, zb, zi, eng._tel(), eng._flt(),
        eng._lanes + (eng._catchup,), jnp.zeros((64, 3), bool),
        64).as_text()
    for text in (one, loop):
        assert "x512x" not in text and "<512x" not in text
        assert "x256x" not in text and "<256x" not in text
        assert "2x8x24x" in text or "24x2x8x" in text


# -- (c) the refusals, and the field off ------------------------------------------


def test_validate_refuses_what_the_run_table_does_not_serve():
    # The cell's depth as a ring: 307,200 rows of 10,240 terms, 12.6 GB.
    deep = DEEP._replace(num_groups=102_400, window=10_240)
    with pytest.raises(ValueError, match="without log_runs"):
        deep._replace(log_runs=0).validate()
    assert deep._replace(log_runs=32).validate()
    # A hosted single-group member's ring of 32,768 is three rows of it,
    # and the largest live ring (3.1 M rows of 32) a third of the bound.
    assert DEEP._replace(num_groups=1, window=32_768, log_runs=0).validate()
    assert DEEP._replace(num_groups=1_048_576, window=32,
                         log_runs=0).validate()
    with pytest.raises(ValueError, match="fleet_summary"):
        DEEP._replace(fleet_summary=True).validate()
    with pytest.raises(ValueError, match="conf_entries"):
        DEEP._replace(conf_entries=True).validate()
    with pytest.raises(ValueError, match="must be >= 0"):
        DEEP._replace(log_runs=-1).validate()


def test_the_engine_refuses_nodes_and_a_load_plane():
    with pytest.raises(ValueError, match="not with nodes"):
        MultiRaftEngine(DEEP, nodes=jax.devices()[:3])
    eng = MultiRaftEngine(DEEP)
    thr = np.zeros(DEEP.num_groups, np.uint32)
    with pytest.raises(ValueError, match="not with log_runs"):
        eng.run_rounds(4, load=(thr, thr, 1))


def test_the_hosting_path_refuses_the_field():
    from etcd_tpu.batched.rawnode import BatchedRawNode

    with pytest.raises(ValueError, match="hosting path"):
        BatchedRawNode(DEEP)


def test_with_the_field_off_the_cell_is_the_election_cells_text(tmp_path):
    """The deployment's file with the log's fields set back (no runs,
    the ring of 32, appends of 4, 256 in flight) is `engine100k-r3`,
    and lowers to the digests pinned for it: the run table is the one
    thing the new configuration adds to the program."""
    root = os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                        "configs")
    with open(os.path.join(root, "engine100k-r3-deeplog.json")) as f:
        deep = json.load(f)["sizes"]
    with open(os.path.join(root, "engine100k-r3.json")) as f:
        ring = json.load(f)["sizes"]
    back = dict(deep, log_runs=0, window=32, max_ents_per_msg=4,
                max_inflight=256)
    assert BatchedConfig(**back) == BatchedConfig(**ring)
    assert BatchedConfig(**deep).log_runs == 32

    eng = MultiRaftEngine(BatchedConfig(**dict(back, num_groups=8)))
    zb, zi = eng._zeros_b, eng._zeros_i
    one = jax.jit(eng._step).lower(
        eng.state, eng.inbox, zb, zb, zi, zb).as_text()
    loop = eng._closed_loop.lower(
        eng.state, eng.inbox, zb, zi, eng._tel(), eng._flt(),
        eng._lanes, jnp.zeros((64, 3), bool), 64).as_text()
    lowered_text.held_to_the_pin(
        (one, loop), PARENT_TEXT["engine100k-r3"], tmp_path,
        ("tests.batched.test_scan_replace", "_lowered", "engine100k-r3"),
        "the deep-log deployment with its field off is not the election "
        "cell's pinned text")


def test_in_tiles_the_deep_log_runs_as_in_one_scan(monkeypatch):
    """The scan in tiles (``engine.scan_tiles``: the cell's 307,200 rows
    are one tile, a larger deployment's are not): the run tables, the
    state and the catch-up counts of two tiles of four groups equal one
    scan's over all eight."""
    from etcd_tpu.batched import engine as engine_mod

    def run():
        eng = MultiRaftEngine(DEEP)
        eng.campaign(np.arange(8) * 3 + np.arange(8) % 3)
        for _ in range(8):
            eng.step_round()
        sched = np.zeros((96, 3), bool)
        sched[8:72, 2] = True
        props = jnp.full((24,), 2, jnp.int32)
        for lo in (0, 32, 64):
            eng.run_rounds(32, tick=True, propose_n=props,
                           isolate=sched[lo:lo + 32])
        return eng

    whole = run()
    monkeypatch.setattr(engine_mod, "TILE_ALIGN", 1)
    monkeypatch.setattr(engine_mod, "TILE_ROWS", 12)
    tiled = run()
    assert (whole._tiles, tiled._tiles) == (1, 2)
    for f in type(whole.state)._fields:
        assert (np.asarray(getattr(whole.state, f))
                == np.asarray(getattr(tiled.state, f))).all(), f
    assert whole.catchup_counts() == tiled.catchup_counts()
    assert whole.catchup_counts()["catchup_appends"] > 0
