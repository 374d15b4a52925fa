"""Faults inside the closed-loop scan (ISSUE 28): the per-round node
schedule of ``run_rounds(isolate=...)``, the timeout hash exact at any
instance id on its one path, and the differential the repo lacked — the batched round
under ``pre_vote`` + ``check_quorum`` with a node cut off and healed,
every round against the shadow oracle.

Round-step programs: ``CELL`` holds the values of the benchmark's
``engine100k-r3`` at the CPU tests' 8 groups (``tests/benchmark`` builds
the same program), ``R5`` those of ``engine10k-r5`` at 8 groups and
``R3_MAJOR`` those of ``test_pipelined.make_engine(4)``; only ``CELL``
is this file's own (``conftest.py``, ISSUE 28 and ISSUE 30 audits).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.analysis import sentinels
from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.shadow import ShadowCluster
from etcd_tpu.batched.state import LEADER, init_state
from etcd_tpu.batched.step import MsgSlots, _rand_timeout

from .test_differential import device_log, device_state

COMMON = dict(window=32, max_ents_per_msg=4, max_props_per_round=2,
              auto_compact=True)
CELL = BatchedConfig(num_groups=8, num_replicas=3, election_timeout=10,
                     heartbeat_timeout=1, pre_vote=True, check_quorum=True,
                     lanes_minor=True, telemetry=True, **COMMON)
R5 = BatchedConfig(num_groups=8, num_replicas=5, election_timeout=1 << 20,
                   heartbeat_timeout=4, lanes_minor=True, **COMMON)
R3_MAJOR = BatchedConfig(num_groups=4, num_replicas=3,
                         election_timeout=1 << 20, heartbeat_timeout=4,
                         **COMMON)


# -- (0) the timeout hash ------------------------------------------------------


def _iids():
    rng = np.random.default_rng(27)
    return np.concatenate([
        [0, 271_180, 271_181, 307_199, 3 * 2**20 - 1],
        rng.integers(0, 3 * 2**20, size=1000)]).astype(np.int64)


@pytest.mark.parametrize("election_timeout", [10, 1 << 20])
def test_rand_timeout_equals_the_python_integer_formula(election_timeout):
    """``DeviceHashRand`` computes the hash in Python integers. The
    parent's int32 ``(iid + 1) * 7919`` wrapped from iid 271,181 on, so
    at 10 ticks a ninth of ``engine100k-r3``'s groups drew another
    timeout than their oracle; at 1<<20 the wrap is a multiple of the
    timeout and both agree. One expression serves both now."""
    cfg = CELL._replace(election_timeout=election_timeout).validate()
    iids = _iids()
    et = election_timeout
    for resets in list(range(0, 1001, 37)) + [1, 2, 999, 1000]:
        got = np.asarray(_rand_timeout(
            cfg, jnp.asarray(iids, jnp.int32), jnp.int32(resets)))
        want = et + ((iids + 1) * 7919 + resets * 104729) % et
        assert (got == want).all(), (
            resets, iids[got != want][:5], got[got != want][:5])
        assert got.dtype == np.int32
    # The same rule under vmap, as `_reset` calls it: one lane at a time.
    per_lane = jax.vmap(lambda i, n: _rand_timeout(cfg, i, n))(
        jnp.asarray(iids, jnp.int32), jnp.full(iids.shape, 1000, jnp.int32))
    assert (np.asarray(per_lane)
            == et + ((iids + 1) * 7919 + 1000 * 104729) % et).all()


@pytest.mark.parametrize("election_timeout", [10, 1 << 20])
def test_init_state_draws_the_same_timeouts(election_timeout):
    """``init_state`` is reset count 0 of the same hash (it had its own
    copy of the wrapping product)."""
    cfg = CELL._replace(election_timeout=election_timeout)
    iids = _iids()
    st = init_state(cfg, iids=iids)
    got = np.asarray(st.randomized_timeout)
    et = election_timeout
    assert got.dtype == np.int32
    assert (got == et + ((iids + 1) * 7919) % et).all()
    # The dense layout of the whole deployment, every instance id.
    whole = init_state(CELL._replace(
        num_groups=102_400, election_timeout=election_timeout))
    n = np.arange(307_200, dtype=np.int64)
    assert (np.asarray(whole.randomized_timeout)
            == et + ((n + 1) * 7919) % et).all()


def test_validate_admits_only_timeouts_the_hash_is_exact_for():
    """Up to 46,340 no product of two residues, summed with another,
    passes 2^32; a power of two divides whatever wraps."""
    for et in (1, 10, 1000, 46_340, 1 << 16, 1 << 20, 1 << 30):
        assert CELL._replace(election_timeout=et).validate()
    for et in (0, -8, 46_341, 100_000, (1 << 20) + 1, 3 << 19):
        with pytest.raises(ValueError, match="power of two"):
            CELL._replace(election_timeout=et).validate()
    # At the largest admitted odd size, against Python integers.
    cfg = CELL._replace(election_timeout=46_340)
    iids = _iids()
    got = np.asarray(_rand_timeout(cfg, jnp.asarray(iids, jnp.int32),
                                   jnp.int32(1000)))
    assert (got == 46_340
            + ((iids + 1) * 7919 + 1000 * 104729) % 46_340).all()


# -- (a) a schedule in the scan equals the same rounds one by one ------------------


def _schedule(rounds: int, r: int) -> np.ndarray:
    """Node 1 away from round 3 to 9, then node 0 from 12 to 40: both
    edges of each outage inside a scan of 16."""
    sched = np.zeros((rounds, r), bool)
    sched[3:9, 1] = True
    sched[12:40, 0] = True
    return sched


def _fields_equal(a, b, what: str) -> None:
    for name in type(a)._fields:
        av, bv = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        assert av.dtype == bv.dtype and (av == bv).all(), (what, name)


def inbox_equal(by_lane: MsgSlots, whole: MsgSlots) -> None:
    """Two inboxes hold the same messages: `valid` equal, every field
    equal wherever a slot is valid; and in `by_lane`, the one route()
    wrote lane by lane, a lane with no valid slot is all zero."""
    valid = np.asarray(by_lane.valid)
    assert (valid == np.asarray(whole.valid)).all()
    empty = ~valid.any(axis=(0, 1))
    for f in MsgSlots._fields:
        va, vb = np.asarray(getattr(by_lane, f)), np.asarray(getattr(whole, f))
        assert va.dtype == vb.dtype, f
        at = valid.reshape(valid.shape + (1,) * (va.ndim - 3))
        assert (np.where(at, va, 0) == np.where(at, vb, 0)).all(), f
        assert not va[:, :, empty].any(), f


@pytest.mark.parametrize("cfg", [CELL, R5, R3_MAJOR],
                         ids=["r3-minor-telemetry", "r5-minor", "r3-major"])
def test_scheduled_scan_equals_single_rounds(cfg):
    a, b = MultiRaftEngine(cfg), MultiRaftEngine(cfg)
    r, n = cfg.num_replicas, cfg.num_instances
    lead = np.arange(cfg.num_groups) * r + np.arange(cfg.num_groups) % r
    props = jnp.full((n,), 2, jnp.int32)
    for eng in (a, b):
        eng.campaign(lead)
        for _ in range(8):
            eng.step_round()
    sched = _schedule(48, r)
    a.run_rounds(16, tick=True, propose_n=props, isolate=sched[:16])
    a.run_rounds_pipelined(32, chunk=16, tick=True, propose_n=props,
                           isolate=sched[16:])
    slots = np.arange(n) % r
    for t in range(48):
        b.step_round(tick=True, propose_n=props,
                     isolate=jnp.asarray(sched[t][slots]))
    _fields_equal(a.state, b.state, "state")
    # The scan's route() exchanges only the lanes somebody wrote
    # (ISSUE 31); the single rounds' exchanges them all.
    inbox_equal(a.inbox, b.inbox)
    assert isinstance(a.inbox, MsgSlots)
    if cfg.telemetry:
        for x, y in zip(a.telemetry(), b.telemetry()):
            assert (x == y).all()
        assert not a.telemetry()[1].any(), "an invariant bit is set"
    # The outage did something: a replica of the cut node fell behind.
    commit = a.commits()
    assert (commit.max(axis=1) > 0).all()


def test_a_schedule_of_another_shape_is_refused():
    eng = MultiRaftEngine(CELL)
    with pytest.raises(ValueError, match=r"\[rounds, R\]"):
        eng.run_rounds(16, isolate=np.zeros((16, 2), bool))
    with pytest.raises(ValueError, match=r"\[rounds, R\]"):
        eng.run_rounds(16, isolate=np.zeros((8, 3), bool))


def test_without_a_schedule_the_scan_gains_no_input_and_no_key():
    """The cells that run no fault trace the scan they always did: the
    schedule is one more input only where one is given, and no new
    compile key either way."""
    eng = MultiRaftEngine(CELL)
    keys = set(sentinels.compile_keys("closed_loop"))
    args = (eng.state, eng.inbox, eng._zeros_b, eng._zeros_i, eng._tel(),
            eng._flt(), eng._lanes)
    plain = eng._closed_loop.lower(*args, None, 16)
    faulty = eng._closed_loop.lower(
        *args, jnp.zeros((16, 3), bool), 16)
    n_in = len(jax.tree.leaves(args))
    assert len(jax.tree.leaves(plain.args_info)) == n_in
    assert len(jax.tree.leaves(faulty.args_info)) == n_in + 1
    assert "tensor<16x3xi1>" not in plain.as_text()
    assert "tensor<16x3xi1>" in faulty.as_text()
    assert set(sentinels.compile_keys("closed_loop")) == keys


def test_the_span_says_which_calls_carried_a_fault():
    from etcd_tpu.obs import spans

    eng = MultiRaftEngine(CELL)
    sched = _schedule(16, 3)
    eng.run_rounds(16, isolate=sched)
    eng.run_rounds(16)
    mine = [s for s in spans.snapshot()
            if s.name == "engine.run_rounds"
            and s.stats.get("engine") == eng._serial]
    assert [s.stats["isolated"] for s in mine] == [int(sched.sum()), 0]
    assert [s.stats["rounds"] for s in mine] == [16, 16]


# -- (b) pre_vote + check_quorum, a node cut off and healed, against the oracle ----


def _cut_node(rnd: int, k0: int, r: int):
    """``benchmark/traffic/elections.json``'s schedule: of every 128
    rounds, node (k0 + period) mod R is away from round 32 to 95."""
    period, t = divmod(rnd, 128)
    return (k0 + period) % r if 32 <= t < 96 else None


@pytest.mark.parametrize("k0", [0, 1, 2], ids=["node0", "node1", "node2"])
def test_elections_under_etcd_defaults_match_the_oracle_every_round(k0):
    """Timer elections, PreVote, the CheckQuorum step-down of a leader
    cut off, snapshot catch-up of the healed node, proposals offered to
    every replica throughout: three periods of the cell's schedule,
    state and log of every replica compared after every round. ``k0``
    is the node cut first: node 0 leads four of the eight groups at
    that point, node 1 two, node 2 two, so each case cuts leaders in
    some groups and followers in the others, in another order."""
    eng = MultiRaftEngine(CELL)
    cfg = eng.cfg
    g_n, r, n = cfg.num_groups, cfg.num_replicas, cfg.num_instances
    slots = np.random.default_rng(2700).integers(0, r, g_n)
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
    leaderless = 0
    for rnd in range(3 * 128):
        k = _cut_node(rnd, k0, r)
        eng.step_round(tick=True, propose_n=props,
                       isolate=jnp.asarray(node_of == k))
        for sh in shadows:
            sh.round(tick=True, offer=2, isolate=() if k is None else (k,))
        got = device_state(eng, cfg)
        want = [s for sh in shadows for s in sh.snapshot_state()]
        assert got == want, f"round {rnd}"
        for g, sh in enumerate(shadows):
            for s in range(r):
                assert device_log(eng, cfg, g * r + s) == sh.log_terms(s), (
                    f"round {rnd} group {g} replica {s}")
        leaderless += int((eng.leaders() < 0).sum())
    counters, invariants = eng.telemetry()
    assert not invariants.any()
    from etcd_tpu.batched.telemetry import TM_INDEX

    total = counters.sum(axis=0)
    for name in ("elections_started", "elections_won", "sent_snapshot",
                 "to_snapshot", "sent_vote_req"):
        assert total[TM_INDEX[name]] > 0, name
    assert leaderless > 0, "no group ever lost its leader"
    role = np.asarray(eng.state.role).reshape(g_n, r)
    assert ((role == LEADER).sum(axis=1) == 1).all()
