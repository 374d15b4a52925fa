"""Replicas born and retired inside the closed-loop scan (ISSUE 34): a
rolling node replacement on four nodes. A group has four slots, three
voters and one empty (``init_state``'s ``spare``); in each period of 128
rounds the spare joins as a learner (``CONF_ADD_LEARNER``, a simple
change with upstream's initProgress), is carried by a snapshot that
states the configuration, catches up by appends, is swapped for the
voter next to it in one joint change of two ops (``CONF_SWAP``) and
that voter's machine is switched off
(``CTL_RETIRE``) and its slot reset (``CTL_WIPE``): next period's spare.
Every round of every schedule here is held against the shadow oracle
(plain ``RawNode``s over ``MemoryStorage``: ``propose_conf_change`` with
the one-op and the two-op ``ConfChangeV2``, ``apply_conf_change``,
snapshots whose metadata carries the ``ConfState``, ``restore``, a fresh
``RawNode`` at the wipe) in state, membership, read state and log.

Round-step programs (``conftest.py``, ISSUE 34 audit): ``RP4`` holds the
values of the benchmark's ``engine512k-r3of4`` at the CPU tests' groups
(R=4, n-minor, telemetry on; ``tests/benchmark`` builds the same
program at 8 groups) and ``RP4_MAJOR`` is this file's own (n-major,
telemetry off).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched import engine as engine_mod
from etcd_tpu.batched.engine import (CTL_CONF, CTL_FROM, CTL_READS,
                                     CTL_RETIRE, CTL_STALL, CTL_TO, CTL_WIPE,
                                     REPLACE_WATCH_NAMES, WATCH_NAMES,
                                     control_cols, watch_names)
from etcd_tpu.batched.shadow import ShadowCluster
from etcd_tpu.batched.state import (CONF_ADD_LEARNER, CONF_DEMOTE, CONF_LEAVE,
                                    CONF_PROMOTE, CONF_SWAP, LEADER, REPLICATE,
                                    SNAPSHOT, BatchedState,
                                    conf_code, conf_decode, empty_replica,
                                    init_state)
from etcd_tpu.batched.telemetry import TM_INDEX

from . import lowered_text
from .test_differential import device_log, device_state
from .test_scan_faults import COMMON, inbox_equal
from .test_scan_reconf import (_fields_equal, _first, device_membership,
                               device_reads)

ETCD = dict(election_timeout=10, heartbeat_timeout=1, pre_vote=True,
            check_quorum=True, conf_entries=True, replace_replicas=True,
            **COMMON)
RP4 = BatchedConfig(num_groups=8, num_replicas=4, lanes_minor=True,
                    telemetry=True, **ETCD)
RP4_MAJOR = BatchedConfig(num_groups=4, num_replicas=4, **ETCD)
R = 4

# -- the schedule: benchmark/traffic/replace-readindex.json's cycle ----------------

PERIOD = 128
ADD, TRANSFER, SWAP, RETIRE, CUT, CUT_ROUNDS, STALL, LEAVE, WIPE = (
    8, 24, 40, 72, 82, 6, 2, 96, 120)


def replace_row(t: int, e0: int) -> dict:
    """Round t (from the first after settle) of the replacement cycle:
    in period k the spare is node e = (e0 + k) mod 4, the node retired
    d = e + 1, the transfer's target n = e + 2, and m = e + 3."""
    period, t = divmod(t, PERIOD)
    e = (e0 + period) % R
    d, n = (e + 1) % R, (e + 2) % R
    row = dict(drained=None, transfer_to=None, conf=0, cut=None,
               retired=None, wipe=None, stall=False)
    if t < ADD:
        row["conf"] = conf_code(CONF_LEAVE) if period else 0
    elif t < SWAP:
        row["conf"] = conf_code(CONF_ADD_LEARNER, e)
    elif t < LEAVE:
        row["conf"] = conf_code(CONF_SWAP, e, d)
    else:
        row["conf"] = conf_code(CONF_LEAVE)
    if TRANSFER <= t < LEAVE:
        row.update(drained=d, transfer_to=n)
    if t >= RETIRE:
        row["retired"] = d
    if CUT <= t < CUT + CUT_ROUNDS:
        row["cut"] = n
        row["stall"] = t >= CUT + STALL
    if t == WIPE:
        row["wipe"] = d
    return row


def control_rows(rows) -> np.ndarray:
    ctl = np.zeros((len(rows), control_cols(RP4)), np.int32)
    for i, row in enumerate(rows):
        if row["drained"] is not None:
            ctl[i, CTL_FROM] = row["drained"] + 1
            ctl[i, CTL_TO] = row["transfer_to"] + 1
        ctl[i, CTL_CONF] = row["conf"]
        ctl[i, CTL_READS] = 1
        ctl[i, CTL_STALL] = int(row["stall"])
        if row["retired"] is not None:
            ctl[i, CTL_RETIRE] = row["retired"] + 1
        if row["wipe"] is not None:
            ctl[i, CTL_WIPE] = row["wipe"] + 1
    return ctl


def isolate_rows(rows) -> np.ndarray:
    iso = np.zeros((len(rows), R), bool)
    for i, row in enumerate(rows):
        if row["cut"] is not None:
            iso[i, row["cut"]] = True
    return iso


def widen(cfg, row):
    """The per-instance inputs of one eager round, as the scan widens
    its control row: (isolate, transfer_to, conf_req, wipe)."""
    node = np.arange(cfg.num_instances) % cfg.num_replicas
    on = lambda s: node == (-1 if s is None else s)  # noqa: E731
    to = 0 if row["transfer_to"] is None else row["transfer_to"] + 1
    drained = on(row["drained"])
    return (jnp.asarray(on(row["cut"]) | on(row["retired"])),
            jnp.asarray(np.where(drained, to, 0).astype(np.int32)),
            jnp.asarray(np.where(drained, 0, row["conf"]).astype(np.int32)),
            jnp.asarray(on(row["wipe"])))


# -- the pair: engine and oracle, settled ------------------------------------------


def make_shadows(cfg, spare):
    return [
        ShadowCluster(
            cfg.num_replicas, election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, pre_vote=cfg.pre_vote,
            check_quorum=cfg.check_quorum, group=g,
            deterministic_timeouts=True, auto_compact_window=cfg.window,
            max_ents=cfg.max_ents_per_msg, max_props=cfg.max_props_per_round,
            spare=spare, replace=cfg.replace_replicas)
        for g in range(cfg.num_groups)]


def settled_pair(cfg, spare, seed=3400, slots=None):
    eng = MultiRaftEngine(cfg, spare=spare)
    cfg = eng.cfg
    g_n, r = cfg.num_groups, cfg.num_replicas
    seated = np.asarray([s for s in range(r) if s != spare])
    if slots is None:
        slots = seated[np.random.default_rng(seed).integers(0, r - 1, g_n)]
    shadows = make_shadows(cfg, spare)
    eng.campaign(np.arange(g_n) * r + slots)
    for g, sh in enumerate(shadows):
        sh.round(campaigns=[int(slots[g])])
    for _ in range(16):
        eng.step_round()
        for sh in shadows:
            sh.round()
    assert (eng.leaders() == slots).all()
    return eng, shadows, slots


def assert_equal_to_the_oracle(eng, shadows, what):
    cfg = eng.cfg
    r = cfg.num_replicas
    got = device_state(eng, cfg)
    want = [s for sh in shadows for s in sh.snapshot_state()]
    assert got == want, (what, "state", _first(got, want))
    got, want = device_membership(eng), [
        m for sh in shadows for m in sh.membership()]
    assert got == want, (what, "membership", _first(got, want))
    got, want = device_reads(eng), [
        x for sh in shadows for x in sh.read_state()]
    assert got == want, (what, "reads", _first(got, want))
    for g, sh in enumerate(shadows):
        for s in range(r):
            assert device_log(eng, cfg, g * r + s) == sh.log_terms(s), (
                what, "log", g, s)


def assert_empty(eng, empty, rows, ticks, what):
    """Instances `rows` are the empty replica on every field, `ticks`
    rounds old."""
    want = empty._replace(
        election_elapsed=jnp.full_like(empty.election_elapsed, ticks),
        read_req_latch=jnp.ones_like(empty.read_req_latch))
    for f, got, exp in zip(
            BatchedState._fields + tuple(
                "conf." + f for f in empty.conf._fields),
            jax.tree.leaves(eng.state), jax.tree.leaves(want)):
        assert (np.asarray(got)[rows] == np.asarray(exp)[rows]).all(), (
            what, "not the empty replica in", f)


def step_both(eng, shadows, row, offer=2):
    cfg = eng.cfg
    n = cfg.num_instances
    iso, transfer, conf, wipe = widen(cfg, row)
    eng.step_round(tick=True, propose_n=jnp.full((n,), offer, jnp.int32),
                   isolate=iso, transfer_to=transfer,
                   read_req=jnp.ones((n,), bool), conf_req=conf, wipe=wipe)
    away = [s for s in (row["cut"], row["retired"]) if s is not None]
    for sh in shadows:
        sh.round(tick=True, offer=offer, isolate=away, reads=True,
                 conf=row["conf"], drained=row["drained"],
                 transfer_to=row["transfer_to"], wipe=row["wipe"])


def progress_rows(eng):
    """(pr_state, match, next, pending_snapshot) of every leader's row
    for every slot: [G, R] each, -1 where a group has no leader."""
    cfg = eng.cfg
    g_n, r = cfg.num_groups, cfg.num_replicas
    lead = eng.leaders()
    rows = np.arange(g_n) * r + np.maximum(lead, 0)
    st = eng.state
    out = [np.asarray(getattr(st, f))[rows] for f in (
        "pr_state", "match", "next", "pending_snapshot")]
    return [np.where((lead >= 0)[:, None], x, -1) for x in out]


# -- (a) the replacement cycle against the oracle, every round ---------------------


@pytest.mark.parametrize("cfg,e0", [(RP4, 3), (RP4_MAJOR, 1)],
                         ids=["r4-minor-telemetry", "r4-major"])
def test_replacement_cycle_matches_the_oracle_every_round(cfg, e0):
    periods = 2
    eng, shadows, slots = settled_pair(cfg, e0)
    cfg = eng.cfg
    g_n, r = cfg.num_groups, cfg.num_replicas
    empty = empty_replica(cfg, eng.state, jnp.arange(cfg.num_instances))
    snaps_to = np.zeros((periods, g_n), int)
    replicate_at = np.full((periods, g_n), -1)
    swap_at = np.full((periods, g_n), -1)
    commits = []
    for t in range(periods * PERIOD):
        row = replace_row(t, e0)
        k, tt = divmod(t, PERIOD)
        e, d = (e0 + k) % r, (e0 + k + 1) % r
        before = np.asarray(eng.state.pr_state).copy()
        step_both(eng, shadows, row)
        assert_equal_to_the_oracle(eng, shadows, f"round {t}")
        state, match, nxt, pending = progress_rows(eng)
        lead = eng.leaders()
        rows = np.arange(g_n) * r + np.maximum(lead, 0)
        snaps_to[k] += ((before[rows, e] != SNAPSHOT)
                        & (state[:, e] == SNAPSHOT))
        for g in range(g_n):
            if state[g, e] == REPLICATE and replicate_at[k, g] < 0 and (
                    tt >= ADD):
                replicate_at[k, g] = tt
            if swap_at[k, g] < 0 and bool(
                    np.asarray(eng.state.in_joint)[rows[g]]):
                swap_at[k, g] = tt
        commits.append(eng.commits().copy())
        # The spare, and after the wipe the slot wiped, is the empty
        # replica on every field until a leader's message reaches it.
        if tt < ADD or tt >= WIPE:
            who = e if tt < ADD else d
            mine = np.arange(g_n) * r + who
            # (It has no timer to fire and like upstream's counts its
            # ticks all the same, and it is asked for reads like any.)
            since = tt - WIPE if tt >= WIPE else (
                tt + PERIOD - WIPE if k else t + 1)
            assert_empty(eng, empty, mine, since, f"round {t}")
    # One snapshot carried each new replica, it stood in REPLICATE long
    # before the swap was on offer, and the swap was taken at once.
    assert (snaps_to == 1).all(), snaps_to
    assert (replicate_at >= ADD).all() and (replicate_at <= ADD + 12).all(), (
        replicate_at)
    assert (swap_at >= SWAP).all() and (swap_at <= SWAP + 8).all(), swap_at
    # n and m applied three changes a period, e and d two.
    want = np.zeros(r, int)
    for k in range(periods):
        e = (e0 + k) % r
        for s, c in ((e, 2), ((e + 1) % r, 2), ((e + 2) % r, 3),
                     ((e + 3) % r, 3)):
            want[s] += c
    assert [sh.conf_applied for sh in shadows] == [want.tolist()] * g_n
    if cfg.telemetry:
        counters, invariants = eng.telemetry()
        assert not invariants.any()
        assert (counters[:, TM_INDEX["conf_changes_applied"]].reshape(g_n, r)
                == want).all()
        assert counters[:, TM_INDEX["sent_snapshot"]].sum() == periods * g_n
    # At the end: the three nodes that are not d vote, no learner, no
    # joint configuration; slot d is empty.
    st = eng.state
    d = (e0 + periods) % r
    voter = np.asarray(st.voter).reshape(g_n, r, r)
    live = [s for s in range(r) if s != d]
    assert voter[:, live][:, :, live].all() and not voter[:, :, d].any()
    assert not voter[:, d].any()
    assert not np.asarray(st.learner).any()
    assert not np.asarray(st.in_joint).any()
    assert not np.asarray(st.voter_out).any()
    # With d off and n away the outgoing half {d, n, m} has no majority
    # and the incoming {n, m, e} has: nothing commits in rounds 84-87.
    c = np.stack(commits)
    assert (c[CUT + CUT_ROUNDS - 1] == c[CUT + STALL - 1]).all()
    assert (c[LEAVE].max(axis=1) > c[CUT + CUT_ROUNDS].max(axis=1)).all()


# -- (b) the controlled scan equals the same rounds one by one ---------------------


def test_controlled_scan_equals_single_rounds():
    cfg, e0 = RP4, 2
    a, b = MultiRaftEngine(cfg, spare=e0), MultiRaftEngine(cfg, spare=e0)
    r, n, g_n = cfg.num_replicas, cfg.num_instances, cfg.num_groups
    slots = np.asarray([s for s in range(r) if s != e0])[
        np.random.default_rng(5).integers(0, r - 1, g_n)]
    props = jnp.full((n,), 2, jnp.int32)
    for eng in (a, b):
        eng.campaign(np.arange(g_n) * r + slots)
        for _ in range(8):
            eng.step_round()
    rows = [replace_row(t, e0) for t in range(2 * PERIOD)]
    ctl, iso = control_rows(rows), isolate_rows(rows)
    for lo in range(0, 2 * PERIOD - 64, 64):
        a.run_rounds(64, tick=True, propose_n=props, isolate=iso[lo:lo + 64],
                     control=ctl[lo:lo + 64])
    a.run_rounds_pipelined(64, chunk=32, tick=True, propose_n=props,
                           isolate=iso[-64:], control=ctl[-64:])
    history = [0] * n
    short = restores = 0
    for row in rows:
        cut, transfer, conf, wipe = widen(cfg, row)
        floor0 = np.asarray(b.state.snap_index).copy()
        b.step_round(tick=True, propose_n=props, isolate=cut,
                     transfer_to=transfer, read_req=jnp.ones((n,), bool),
                     conf_req=conf, wipe=wipe)
        st = b.state
        fields = [np.asarray(getattr(st, f))
                  for f in engine_mod.HISTORY_FIELDS]
        fields = [f @ (1 << np.arange(r)) if f.ndim == 2 else f
                  for f in fields]
        history = [engine_mod.history_fold(h, [f[i] for f in fields])
                   for i, h in enumerate(history)]
        leads = np.asarray(st.role) == LEADER
        short += int((leads[:, None] & np.asarray(st.learner)
                      & (np.asarray(st.pr_state) != REPLICATE)).sum())
        snap, last = np.asarray(st.snap_index), np.asarray(st.last)
        restores += int(((snap > floor0) & (snap == last)).sum())
    _fields_equal(a.state, b.state, "state")
    inbox_equal(a.inbox, b.inbox)
    for x, y in zip(a.telemetry(), b.telemetry()):
        assert (x == y).all()
    assert not a.telemetry()[1].any()
    watch = a.scan_watch()
    assert list(watch) == list(WATCH_NAMES + REPLACE_WATCH_NAMES)
    assert list(watch) == list(watch_names(cfg))
    for name in ("reads_below_commit", "joint_commits_in_stall",
                 "conf_marks_lost", "outsider_votes_or_campaigns",
                 "swaps_before_ready"):
        assert watch[name] == 0, name
    assert watch["joint_instance_rounds"] > 0
    assert watch["swaps_taken"] == 2 * g_n
    assert watch["replicas_reset"] == 2 * g_n
    assert watch["conf_restores"] == restores == 2 * g_n
    assert watch["learner_rounds_short_of_replicate"] == short > 0
    assert b.scan_watch() == dict.fromkeys(watch_names(cfg), 0)
    assert a.scan_history().tolist() == history
    counters, _ = a.telemetry()
    assert counters[:, TM_INDEX["sent_snapshot"]].sum() == 2 * g_n


def test_the_span_says_what_was_retired_and_wiped():
    from etcd_tpu.obs import spans

    eng = MultiRaftEngine(RP4, spare=0)
    rows = [replace_row(t, 0) for t in range(64, 128)]
    eng.run_rounds(64, isolate=isolate_rows(rows), control=control_rows(rows))
    eng.run_rounds(16)
    mine = [s for s in spans.snapshot()
            if s.name == "engine.run_rounds"
            and s.stats.get("engine") == eng._serial]
    assert [s.stats["retired"] for s in mine] == [128 - RETIRE, 0]
    assert [s.stats["wipes"] for s in mine] == [1, 0]
    assert [s.stats["isolated"] for s in mine] == [CUT_ROUNDS, 0]
    assert [s.stats["conf_ops"] for s in mine] == [64, 0]
    assert [s.stats["transfers"] for s in mine] == [LEAVE - 64, 0]


def test_a_schedule_or_a_wipe_of_the_wrong_kind_is_refused():
    from .test_scan_reconf import RC3

    eng = MultiRaftEngine(RP4, spare=1)
    with pytest.raises(ValueError, match="CTL_COLS"):
        eng.run_rounds(16, control=np.zeros((16, 5), np.int32))
    plain = MultiRaftEngine(RC3)
    with pytest.raises(ValueError, match="CTL_COLS"):
        plain.run_rounds(16, control=np.zeros((16, 7), np.int32))
    with pytest.raises(ValueError, match="replace_replicas"):
        plain.step_round(wipe=jnp.zeros((RC3.num_instances,), bool))
    with pytest.raises(ValueError, match="replace_replicas"):
        MultiRaftEngine(RC3, spare=0)
    with pytest.raises(ValueError, match="conf_entries"):
        RC3._replace(conf_entries=False, replace_replicas=True).validate()
    with pytest.raises(ValueError, match="16-bit"):
        RP4._replace(num_replicas=16).validate()


# -- (c) a snapshot that restores the masks, a silent learner, the spare -----------


def test_a_snapshot_restores_the_masks_in_the_middle_of_a_joint_configuration():
    """Node m is cut off from round 38 to 63 of the cycle: the swap is
    taken and applied without it (rounds 40-43), it falls off the ring,
    and the snapshot that carries it back states a joint configuration:
    incoming {n, m, e}, outgoing {d, n, m}. It takes the four masks and
    `in_joint` from the message, at a round in which it applies nothing,
    and goes on to leave the joint configuration like the others."""
    e0 = 0
    e, d, n, m = 0, 1, 2, 3
    # Led from n: the cut node leads nothing.
    eng, shadows, _ = settled_pair(
        RP4, e0, slots=np.full(RP4.num_groups, n))
    cfg = eng.cfg
    g_n, r = cfg.num_groups, cfg.num_replicas
    mine = np.arange(g_n) * r + m
    restored_at = np.full(g_n, -1)
    for t in range(PERIOD):
        row = replace_row(t, e0)
        if 38 <= t < 64:
            assert row["cut"] is None
            row["cut"] = m
        before = (np.asarray(eng.state.in_joint)[mine].copy(),
                  np.asarray(eng.state.snap_index)[mine].copy())
        applied = eng.telemetry()[0][mine, TM_INDEX["conf_changes_applied"]]
        step_both(eng, shadows, row)
        assert_equal_to_the_oracle(eng, shadows, f"round {t}")
        st = eng.state
        jumped = (np.asarray(st.snap_index)[mine] > before[1]) & (
            np.asarray(st.snap_index)[mine] == np.asarray(st.last)[mine])
        turned = np.asarray(st.in_joint)[mine] & ~before[0]
        by_apply = eng.telemetry()[0][
            mine, TM_INDEX["conf_changes_applied"]] > applied
        if turned.any():
            assert (turned == jumped).all() and not by_apply[turned].any()
            restored_at[turned] = t
            want = np.zeros(r, bool)
            want[[n, m, e]] = True
            assert (np.asarray(st.voter)[mine][turned] == want).all()
            want = np.zeros(r, bool)
            want[[d, n, m]] = True
            assert (np.asarray(st.voter_out)[mine][turned] == want).all()
            assert not np.asarray(st.learner)[mine][turned].any()
    assert (restored_at >= 64).all() and (restored_at <= 70).all(), (
        restored_at)
    # m applied the learner (before the cut) and LeaveJoint; the swap
    # it has from the snapshot.
    counters, invariants = eng.telemetry()
    assert not invariants.any()
    assert (counters[mine, TM_INDEX["conf_changes_applied"]] == 2).all()
    assert not np.asarray(eng.state.in_joint).any()


def test_a_snapshot_that_does_not_name_the_replica_is_refused():
    """``raft.restore`` refuses a snapshot whose ConfState does not hold
    the node; so does the round: the state stays and the answer is the
    commit index."""
    from etcd_tpu.batched import step

    cfg = RP4_MAJOR
    st = init_state(cfg, spare=1)
    one = jax.tree.map(lambda x: x[1], st)  # the spare of group 0
    m = step.empty_msgs((), 0)._replace(
        valid=True, type=jnp.asarray(step.T_SNAP), term=jnp.asarray(3),
        index=jnp.asarray(40), log_term=jnp.asarray(2),
        reject_hint=jnp.asarray(0b1101), ctx=jnp.asarray(0))
    got, resp = step._handle_snapshot(cfg, one, m, jnp.asarray(1))
    _fields_equal(got, one, "refused")
    assert int(resp.index) == 0 and bool(resp.valid)
    named = m._replace(ctx=jnp.asarray(0b0010 | (0b0100 << 16)))
    got, resp = step._handle_snapshot(cfg, one, named, jnp.asarray(1))
    assert int(got.snap_index) == int(got.last) == int(got.commit) == 40
    assert np.asarray(got.voter).tolist() == [True, False, True, True]
    assert np.asarray(got.learner).tolist() == [False, True, False, False]
    assert np.asarray(got.conf.learner_next).tolist() == [
        False, False, True, False]
    assert not bool(got.in_joint) and int(resp.index) == 40
    # And what emit packs is what restore unpacks.
    words = step._snapshot_conf_words(got)
    assert [int(w) for w in words] == [0b1101, 0b0010 | (0b0100 << 16)]


def test_a_learner_that_never_answers_holds_no_floor_and_one_snapshot_carries_it():
    """A learner is added whose machine never answers (cut off from the
    start). The leader's compaction floor is auto_compact's, half a
    ring behind `last`, whoever lags: two proposals go on being
    appended and committed every round and the ring never fills.
    Healed 30 rounds and 60 entries later, the learner is carried by
    ONE snapshot: it is taken at the applied index, half a ring ahead
    of the floor, so the appends after it are still in the ring and
    the learner reaches REPLICATE (ROADMAP D12's loop, a snapshot at
    the floor answered below it, does not start)."""
    e0 = 3
    eng, shadows, slots = settled_pair(RP4, e0, slots=np.zeros(
        RP4.num_groups, int))
    cfg = eng.cfg
    g_n, r, w, p = (cfg.num_groups, cfg.num_replicas, cfg.window,
                    cfg.max_props_per_round)
    lead = np.arange(g_n) * r
    quiet = dict(drained=None, transfer_to=None, conf=0, cut=None,
                 retired=None, wipe=None, stall=False)
    held, lasts, commits = [], [], []
    for t in range(40):
        row = dict(quiet, cut=e0,
                   conf=conf_code(CONF_ADD_LEARNER, e0) if t >= 10 else 0)
        step_both(eng, shadows, row)
        assert_equal_to_the_oracle(eng, shadows, f"round {t}")
        st = eng.state
        held.append((np.asarray(st.last) - np.asarray(st.snap_index))[lead])
        lasts.append(np.asarray(st.last)[lead].copy())
        commits.append(np.asarray(st.commit)[lead].copy())
    held, lasts, commits = (np.stack(x) for x in (held, lasts, commits))
    assert np.asarray(eng.state.learner)[lead, e0].all()
    assert (held <= w // 2 + p).all(), held.max()
    # Two entries appended in every round but the change's (three), and
    # two committed.
    assert set(np.unique(np.diff(lasts, axis=0))) == {p, p + 1}
    assert (np.diff(commits[3:], axis=0) >= p).all()
    sent0 = eng.telemetry()[0][:, TM_INDEX["sent_snapshot"]].sum()
    assert sent0 == 0
    for t in range(20):
        step_both(eng, shadows, quiet)
        assert_equal_to_the_oracle(eng, shadows, f"healed, round {t}")
    counters, invariants = eng.telemetry()
    assert counters[:, TM_INDEX["sent_snapshot"]].sum() == g_n
    assert not invariants.any()
    state, match, _, _ = progress_rows(eng)
    assert (state[:, e0] == REPLICATE).all()
    assert (match[:, e0] >= np.asarray(eng.state.commit)[lead] - 2 * p).all()


def test_a_spare_slot_never_campaigns():
    """Nothing is offered: the spare hears nobody, counts its ticks
    past every timeout and stays what it is; the others run three
    voters' elections without it (their leader cut off for good)."""
    e0 = 2
    eng, shadows, slots = settled_pair(RP4, e0, slots=np.zeros(
        RP4.num_groups, int))
    cfg = eng.cfg
    g_n, r = cfg.num_groups, cfg.num_replicas
    empty = empty_replica(cfg, eng.state, jnp.arange(cfg.num_instances))
    spare = np.arange(g_n) * r + e0
    quiet = dict(drained=None, transfer_to=None, conf=0, cut=0,
                 retired=None, wipe=None, stall=False)
    for t in range(45):
        step_both(eng, shadows, quiet)
        assert_equal_to_the_oracle(eng, shadows, f"round {t}")
        assert_empty(eng, empty, spare, t + 1, f"round {t}")
    assert (eng.leaders() > 0).all() and (eng.leaders() != e0).all()
    assert (eng.terms()[:, e0] == 0).all() and (eng.terms()[:, 1] > 1).all()


def test_the_oracle_raises_where_a_reused_slot_votes_twice_in_a_term():
    sh = make_shadows(RP4_MAJOR, 1)[0]
    sh.round(campaigns=[0])
    while not sh.nodes[2].raft.vote:
        sh.round()
    assert sh.votes_cast[2] == {1: 1}
    sh.votes_cast[2][1] = 4  # as if its predecessor had voted for slot 3
    with pytest.raises(AssertionError, match="voted for 1 in term 1"):
        sh.round()


# -- (d) what the new fields cost a configuration that does not ask for them ------

# sha256 of the lowered text (one round, the 64-round closed loop) of the
# four live configurations at 8 groups, taken with _lowered() below. A
# round that changes on purpose re-pins these
# (ETCD_TPU_PRINT_ROUND_DIGESTS=1 prints them); a field that leaks into
# a configuration that does not ask for it shows here before it shows as
# a cache miss on the chip. Pinned by PR 34 on the text of 916f7bd (PR
# 33), which stood through 2cee456 (PR 38); re-pinned by PR 39 on its own
# text, because it rewrote `kernels.ring_write_masked` (one reduce whose
# output is the ring, for a sum, an any and a select), which every
# append site of the round calls; re-pinned by PR 41 on its own text,
# because it rewrote `kernels.quorum_committed` (the q-th largest of the
# R acked indexes by compares and selects, for a sort and a one-hot
# pick), which every `_maybe_commit` of the round calls; re-pinned by PR
# 43 on its own text, because it split the HB and HB_RESP lane conds of
# `step._deliver_vectorized` in two wherever the occupancy is a batch's
# (two more `lax.cond`s a round, two more bits in `lane_occupancy`; a
# round built with `lane_skip=False` kept the parent's text:
# `test_rare_lanes.py` pins it); re-pinned by PR 45 on its own text,
# because the state gained a field (`own_from`), `_maybe_commit` and
# `_control`'s committed-in-term read it and no ring, and the round runs
# in two vmaps with emit's `lax.cond` between them (a round built with
# `lane_skip=False` moved too, by the first two: `test_rare_lanes.py`
# re-pins it); re-pinned by PR 48 on its own text, because a kind lane
# carries the fields of `step.LANE_FIELDS` alone (35 planes and the
# entries exchanged, wiped and carried for 60 and the entries; the
# handlers fill the rest with zero constants inside their branches);
# re-pinned by PR 49 on its own text, because `_tick`'s campaign writes
# the one entry it appends through one ring column (`cols=1` down
# `_campaign` -> `_become_leader` -> `_append_own`: a `[N, W, 1]`
# compare under `raft_tick` for the `[N, W, P]` one, every other write
# as it was): each time the
# text of every configuration moved on purpose, and the chip compiles
# each scan anew once.
PARENT_TEXT = {
    "engine64k-r3": (
        "b4d904094e0d6935fb5a0503d4fc19ee6aeb843a717f8f9fed9548b663266ae9",
        "3e0dbd6e84afd24c7af721d26e594e5f42fd5af670967ea579bac04cf8cb6bf7"),
    "engine10k-r5": (
        "c01927a81bd5948eb577b80aef1502d45eaf150f21d26257d9d17c3ed39e16f4",
        "bc075eac2928707473d3f08c1fbc0e3602bf5e4eda7e985c7ef983b5ed895a35"),
    "engine100k-r3": (
        "37584efb09fe3309c24e06f46a064de0948e2e6b4c57aee23f2f516a7ef001e7",
        "c216b3e5e9c1413367554ffae7baf1ca9c11f15a1ab1d0c9f1fb6119c743cfb7"),
    "engine1m-r3": (
        "cbbdd8e981c8415a201b76ef8a35e9e3bcbd201500124da5eab759eeaaa5000b",
        "453163890a4580c8cd77371d9053a9d9e2f96ff48496eb7a18fefaf8b97de951"),
}


def _lowered(name):
    """(config, one round, the 64-round closed loop as its cell calls
    it) lowered: nothing compiles."""
    with open(os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                           "configs", name + ".json")) as f:
        sizes = dict(json.load(f)["sizes"], num_groups=8)
    eng = MultiRaftEngine(BatchedConfig(**sizes))
    cfg = eng.cfg
    zb, zi = eng._zeros_b, eng._zeros_i
    one = jax.jit(eng._step).lower(
        eng.state, eng.inbox, zb, zb, zi, zb).as_text()
    args = (eng.state, eng.inbox, zb, zi, eng._tel(), eng._flt(), eng._lanes)
    if not cfg.telemetry:
        loop = eng._closed_loop.lower(*args, None, 64)
    else:
        iso = jnp.zeros((64, cfg.num_replicas), bool)
        if not cfg.conf_entries:
            loop = eng._closed_loop.lower(*args, iso, 64)
        else:
            n = cfg.num_instances
            watch = engine_mod.ScanWatch(
                jnp.zeros((len(watch_names(cfg)), 2), jnp.int32),
                jnp.zeros((n,), jnp.int32), jnp.zeros((n,), jnp.uint32))
            loop = eng._closed_loop.lower(
                *args, iso, 64,
                jnp.zeros((64, control_cols(cfg)), jnp.int32), watch)
    return cfg, one, loop.as_text()


@pytest.mark.parametrize("name", sorted(PARENT_TEXT))
def test_with_the_new_fields_off_the_round_is_the_parents_text(name,
                                                               tmp_path):
    """(Where a digest differs, `lowered_text.held_to_the_pin` lowers
    the program again in a fresh process, writes both texts under
    `tmp_path` and says what differed: PERF.md section 7, "A digest
    that moved".)"""
    cfg, one, loop = _lowered(name)
    assert not cfg.replace_replicas
    if os.environ.get("ETCD_TPU_PRINT_ROUND_DIGESTS"):
        print(name, tuple(lowered_text.digest(t) for t in (one, loop)))
    lowered_text.held_to_the_pin(
        (one, loop), PARENT_TEXT[name], tmp_path,
        ("tests.batched.test_scan_replace", "_lowered", name),
        "the lowered round or closed loop of a live configuration is not "
        "the text it was at the commit that pinned it (PR 49)")
    assert control_cols(cfg) == 5 and watch_names(cfg) == WATCH_NAMES


def test_with_the_new_fields_on_the_state_says_so():
    assert control_cols(RP4) == 7 and CTL_RETIRE == 5 and CTL_WIPE == 6
    st = init_state(RP4_MAJOR, spare=jnp.asarray([0, 1, 2, 3]))
    voter = np.asarray(st.voter).reshape(4, 4, 4)
    for g in range(4):
        want = np.ones(4, bool)
        want[g] = False
        assert (voter[g][want] == want).all() and not voter[g][g].any()
    # The wide code reads the narrow ones as they were.
    for kind in (CONF_DEMOTE, CONF_LEAVE, CONF_PROMOTE):
        for slot in (0, 3, 100):
            code = conf_code(kind, slot)
            assert code == kind | slot << 2
            assert conf_decode(code) == (kind, slot, 0)
    assert conf_decode(conf_code(CONF_SWAP, 3, 2)) == (CONF_SWAP, 3, 2)
    assert conf_decode(conf_code(CONF_ADD_LEARNER, 126)) == (
        CONF_ADD_LEARNER, 126, 0)
