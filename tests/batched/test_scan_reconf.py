"""The control plane inside the closed-loop scan (ISSUE 32): a rolling
node drain through joint configurations under writes and ReadIndex
reads. Configuration changes are entries of the device's log
(``BatchedConfig.conf_entries``) that each replica applies at its own
apply point; transfers, reads and the change on offer ride the scan as
a per-round control schedule (``run_rounds(control=...)``). Every round
of every schedule here is held against the shadow oracle (plain
``RawNode``s: ``propose_conf_change``, ``apply_conf_change``,
``read_index``, ``transfer_leader``) in state, membership, read state
and log.

Round-step programs (``conftest.py``, ISSUE 32 audit): ``RC3`` holds the
values of the benchmark's ``engine1m-r3`` at the CPU tests' 8 groups
(R=3, n-minor, telemetry on; ``tests/benchmark`` builds the same
program) and ``RC5`` is this file's own (R=5, n-major, telemetry off).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.analysis import sentinels
from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched import engine as engine_mod
from etcd_tpu.batched.engine import (CTL_COLS, CTL_CONF, CTL_FROM, CTL_READS,
                                     CTL_STALL, CTL_TO, WATCH_NAMES)
from etcd_tpu.batched.shadow import ShadowCluster
from etcd_tpu.batched.state import (CONF_DEMOTE, CONF_LEAVE, CONF_PROMOTE,
                                    FOLLOWER, LEADER, BatchedState,
                                    ConfBatchedState, conf_code, init_state)
from etcd_tpu.batched.telemetry import TM_INDEX

from .test_differential import device_log, device_state
from .test_scan_faults import CELL, COMMON, inbox_equal

ETCD = dict(election_timeout=10, heartbeat_timeout=1, pre_vote=True,
            check_quorum=True, conf_entries=True, **COMMON)
RC3 = BatchedConfig(num_groups=8, num_replicas=3, lanes_minor=True,
                    telemetry=True, **ETCD)
RC5 = BatchedConfig(num_groups=8, num_replicas=5, **ETCD)


# -- the schedule: benchmark/traffic/joint-readindex.json's drain cycle ------------


def drain_row(t: int, d0: int, r: int, cut_rounds: int = 6) -> dict:
    """Round t (from the first after settle) of the 128-round cycle:
    node d = (d0 + period) mod R hands its leaderships to d+1 from
    round 8, is demoted through a joint configuration (24, left at 56),
    promoted back (72, left at 104); node d+1 is cut off from round 80
    for `cut_rounds`."""
    period, t = divmod(t, 128)
    d = (d0 + period) % r
    to = (d + 1) % r
    row = dict(drained=None, transfer_to=None, conf=0, cut=None, stall=False)
    if 8 <= t < 56:
        row.update(drained=d, transfer_to=to)
    if 24 <= t < 56:
        row["conf"] = conf_code(CONF_DEMOTE, d)
    elif 56 <= t < 72 or t >= 104:
        row["conf"] = conf_code(CONF_LEAVE)
    elif 72 <= t < 104:
        row["conf"] = conf_code(CONF_PROMOTE, d)
    if 80 <= t < 80 + cut_rounds:
        row["cut"] = to
        # Only at R=3 does the node cut leave a joint half short.
        row["stall"] = t >= 82 and r == 3
    return row


def control_rows(rows) -> np.ndarray:
    """The engine's control schedule, int32 [T, CTL_COLS], of `rows`."""
    ctl = np.zeros((len(rows), CTL_COLS), np.int32)
    for i, row in enumerate(rows):
        if row["drained"] is not None:
            ctl[i, CTL_FROM] = row["drained"] + 1
            ctl[i, CTL_TO] = row["transfer_to"] + 1
        ctl[i, CTL_CONF] = row["conf"]
        ctl[i, CTL_READS] = 1
        ctl[i, CTL_STALL] = int(row["stall"])
    return ctl


def isolate_rows(rows, r: int) -> np.ndarray:
    iso = np.zeros((len(rows), r), bool)
    for i, row in enumerate(rows):
        if row["cut"] is not None:
            iso[i, row["cut"]] = True
    return iso


def widen(cfg, row):
    """The per-instance inputs of one eager round, as the scan widens
    its control row: (isolate, transfer_to, conf_req)."""
    node = np.arange(cfg.num_instances) % cfg.num_replicas
    drained = node == (-1 if row["drained"] is None else row["drained"])
    to = 0 if row["transfer_to"] is None else row["transfer_to"] + 1
    return (jnp.asarray(node == (-1 if row["cut"] is None else row["cut"])),
            jnp.asarray(np.where(drained, to, 0).astype(np.int32)),
            jnp.asarray(np.where(drained, 0, row["conf"]).astype(np.int32)))


# -- the pair: engine and oracle, settled ------------------------------------------


def make_shadows(cfg, learners=()):
    return [
        ShadowCluster(
            cfg.num_replicas, election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, pre_vote=cfg.pre_vote,
            check_quorum=cfg.check_quorum, group=g,
            deterministic_timeouts=True, auto_compact_window=cfg.window,
            max_ents=cfg.max_ents_per_msg, max_props=cfg.max_props_per_round,
            learners=learners)
        for g in range(cfg.num_groups)]


def settled_pair(cfg, seed=3200, learners=()):
    eng = MultiRaftEngine(cfg)
    cfg = eng.cfg
    g_n, r = cfg.num_groups, cfg.num_replicas
    voters = [s for s in range(r) if s not in learners]
    slots = np.asarray(voters)[
        np.random.default_rng(seed).integers(0, len(voters), g_n)]
    for g in range(g_n if learners else 0):
        eng.set_membership(g, voters, learners=learners)
    shadows = make_shadows(cfg, learners)
    eng.campaign(np.arange(g_n) * r + slots)
    for g, sh in enumerate(shadows):
        sh.round(campaigns=[int(slots[g])])
    for _ in range(16):
        eng.step_round()
        for sh in shadows:
            sh.round()
    assert (eng.leaders() == slots).all()
    return eng, shadows, slots


def device_membership(eng):
    """Per instance (voters, outgoing, learners, learners next) as
    sorted tuples of slots: each replica's own view, as the oracle's."""
    st = eng.state
    pick = lambda a, i: tuple(np.nonzero(a[i])[0].tolist())  # noqa: E731
    v, vo, ln, nx, joint = (np.asarray(x) for x in (
        st.voter, st.voter_out, st.learner, st.conf.learner_next,
        st.in_joint))
    return [(pick(v, i), pick(vo, i) if joint[i] else (), pick(ln, i),
             pick(nx, i)) for i in range(v.shape[0])]


def device_reads(eng):
    seq, idx, ready = eng.read_states()
    return list(zip(seq.tolist(), idx.tolist(), ready.tolist()))


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


def _first(got, want):
    return [(i, a, b) for i, (a, b) in enumerate(zip(got, want))
            if a != b][:3]


def step_both(eng, shadows, row, offer=2):
    cfg = eng.cfg
    n = cfg.num_instances
    iso, transfer, conf = widen(cfg, row)
    eng.step_round(tick=True, propose_n=jnp.full((n,), offer, jnp.int32),
                   isolate=iso, transfer_to=transfer,
                   read_req=jnp.ones((n,), bool), conf_req=conf)
    for sh in shadows:
        sh.round(tick=True, offer=offer,
                 isolate=() if row["cut"] is None else (row["cut"],),
                 reads=True, conf=row["conf"], drained=row["drained"],
                 transfer_to=row["transfer_to"])


# -- (a) the drain cycle against the oracle, every round ---------------------------


@pytest.mark.parametrize("cfg,periods,cut_rounds", [
    (RC3, 2, 6), (RC5, 1, 4)], ids=["r3-minor-telemetry", "r5-major"])
def test_drain_cycle_matches_the_oracle_every_round(cfg, periods, cut_rounds):
    """At R=5 the cut lasts 4 rounds: its joint halves keep a majority
    without the node cut, commits go on, and a node away for 6 rounds
    of them falls off the ring's 16 entries and is sent a snapshot,
    which does not carry a ConfState on the device yet (ROADMAP)."""
    eng, shadows, slots = settled_pair(cfg)
    cfg = eng.cfg
    g_n, r = cfg.num_groups, cfg.num_replicas
    d0 = 1
    flipped = {}     # group -> round its leader entered the first joint
    split = 0        # rounds x groups whose replicas held different masks
    commits, seqs = [], []
    for t in range(periods * 128):
        step_both(eng, shadows, drain_row(t, d0, r, cut_rounds))
        assert_equal_to_the_oracle(eng, shadows, f"round {t}")
        joint = np.asarray(eng.state.in_joint).reshape(g_n, r)
        lead = eng.leaders()
        for g in range(g_n):
            if lead[g] >= 0 and joint[g, lead[g]] and g not in flipped:
                flipped[g] = t
        split += int((joint != joint[:, :1]).any(axis=1).sum())
        commits.append(eng.commits().copy())
        seqs.append(np.asarray(eng.state.read_seq).reshape(g_n, r).copy())
    # Every replica applied all four changes of every period, itself.
    assert [sum(sh.conf_applied) for sh in shadows] == (
        [4 * r * periods] * g_n)
    if cfg.telemetry:
        counters, invariants = eng.telemetry()
        total = counters.sum(axis=0)
        assert not invariants.any()
        assert (counters[:, TM_INDEX["conf_changes_applied"]]
                == 4 * periods).all()
        assert total[TM_INDEX["sent_snapshot"]] == 0
        assert total[TM_INDEX["sent_timeout_now"]] > 0
        assert total[TM_INDEX["reads_confirmed"]] > g_n * periods * 20
    # A replica flips at its own apply point, not its leader's: there
    # were rounds in which a group's replicas held different masks
    # (and the program equalled the oracle in each: asserted above).
    assert split >= 4 * g_n * periods
    # The offer latches through the transfer: a group led from the
    # drained node is offered the hand-over and not the demotion, and
    # takes the demotion late and not never.
    assert set(flipped) == set(range(g_n))
    on_d = [flipped[g] for g in range(g_n) if slots[g] == d0]
    rest = [flipped[g] for g in range(g_n) if slots[g] != d0]
    assert on_d and rest and min(on_d) >= max(rest) and max(rest) <= 27
    assert (eng.leaders() != (d0 + periods - 1) % r).all()
    # At a period's end: all voters, no learner, not in joint.
    st = eng.state
    assert np.asarray(st.voter).all() and not np.asarray(st.learner).any()
    assert not np.asarray(st.in_joint).any()
    assert not np.asarray(st.voter_out).any()
    if r == 3:
        # With node d+1 away {a,b,d}'s majority is there and {a,b}'s
        # is not: from the third cut round to the last nothing commits
        # and no read is confirmed, anywhere; both resume after.
        c, s = np.stack(commits), np.stack(seqs)
        assert (c[85] == c[81]).all() and (s[85] == s[82]).all()
        assert (c[95].max(axis=1) > c[85].max(axis=1)).all()
        assert (s[95].max(axis=1) > s[85].max(axis=1)).all()
        # Nobody stepped down: 6 rounds are shorter than the timeout.
        assert (np.asarray(st.term).reshape(g_n, r)[:, 0]
                <= 1 + periods).all()


def test_a_learner_neither_votes_nor_campaigns_nor_counts():
    """After the demotion is left (round 56 of the cycle) node d is a
    learner. Cut off with writes going on, the others commit without
    it; cut off for longer than any election timeout with nothing
    offered (so that no snapshot is needed after), it never campaigns
    and the leader, which hears a quorum of voters, stays."""
    eng, shadows, _ = settled_pair(RC3)
    r, g_n = 3, RC3.num_groups
    d0 = 1
    for t in range(64):
        step_both(eng, shadows, drain_row(t, d0, r))
    assert (np.asarray(eng.state.learner).reshape(g_n, r, r)[:, :, d0]).all()
    quiet = dict(drained=None, transfer_to=None, conf=0, cut=d0, stall=False)
    before = eng.commits().max(axis=1)
    for t in range(4):  # 8 entries: the ring's 16 still hold its next
        step_both(eng, shadows, quiet)
        assert_equal_to_the_oracle(eng, shadows, f"writes, round {t}")
    assert (eng.commits().max(axis=1) >= before + 4).all()
    terms = eng.terms().copy()
    for t in range(25):
        step_both(eng, shadows, quiet, offer=0)
        assert_equal_to_the_oracle(eng, shadows, f"quiet, round {t}")
        role = np.asarray(eng.state.role).reshape(g_n, r)
        assert (role[:, d0] == FOLLOWER).all(), "a learner campaigned"
    assert (eng.terms() == terms).all()
    assert ((np.asarray(eng.state.role).reshape(g_n, r) == LEADER)
            .sum(axis=1) == 1).all()
    # Healed, it is promoted back as the cycle has it.
    for t in range(64, 128):
        step_both(eng, shadows, drain_row(t, d0, r))
        assert_equal_to_the_oracle(eng, shadows, f"round {t}")
    assert np.asarray(eng.state.voter).all()


def test_a_second_change_is_refused_while_one_is_unapplied():
    """R=5 with slot 4 a learner from the start. Demote slot 1 in round
    0; in round 1 the promotion of slot 4 is on offer, which fits the
    configuration (not joint yet, 4 is a learner): only the unapplied
    change stands in its way. Once the first is applied the joint
    configuration does."""
    eng, shadows, slots = settled_pair(RC5, seed=7, learners=(4,))
    cfg = eng.cfg
    g_n, r = cfg.num_groups, cfg.num_replicas
    lead = np.arange(g_n) * r + slots
    ask = lambda code: dict(drained=None, transfer_to=None, conf=code,  # noqa: E731
                            cut=None, stall=False)
    # Slot 1 leads some groups: those demote slot 0 instead.
    step_both(eng, shadows, ask(0))
    last0 = np.asarray(eng.state.last)[lead].copy()
    n = cfg.num_instances
    first = np.where((slots == 1)[np.arange(n) // r],
                     conf_code(CONF_DEMOTE, 0), conf_code(CONF_DEMOTE, 1))

    def offer(codes):
        eng.step_round(tick=True, propose_n=jnp.full((n,), 2, jnp.int32),
                       read_req=jnp.ones((n,), bool),
                       conf_req=jnp.asarray(codes.astype(np.int32)))
        for g, sh in enumerate(shadows):
            # One code a group here (the oracle's rows offer one).
            sh.round(tick=True, offer=2, reads=True, conf=int(codes[g * r]))

    offer(first)
    mark = np.asarray(eng.state.conf.index)[lead].copy()
    assert (mark == last0 + 1).all()
    assert (np.asarray(eng.state.last)[lead] == last0 + 3).all()
    second = np.full(n, conf_code(CONF_PROMOTE, 4))
    for t in range(4):
        offer(second)
        assert_equal_to_the_oracle(eng, shadows, f"round {t}")
        # Two proposals a round and no third entry; the mark stands.
        assert (np.asarray(eng.state.last)[lead] == last0 + 3 + 2 * (t + 1)
                ).all()
        assert (np.asarray(eng.state.conf.index)[lead] == mark).all()
    assert np.asarray(eng.state.in_joint).all()
    assert np.asarray(eng.state.learner)[:, 4].all()


@pytest.mark.parametrize("load", [0, 2], ids=["by-append", "by-snapshot"])
def test_a_truncated_suffix_forgets_the_change(load):
    """The leader of every group is cut off and, alone, takes a change:
    an entry nobody else holds, marked. The others elect and append,
    and when the old leader is healed what they send replaces its
    suffix: a conflicting append with nothing offered meanwhile, a
    snapshot under `load` proposals a round (the ring has passed it).
    The mark goes with the entry and nobody's masks ever move."""
    eng, shadows, slots = settled_pair(RC3, seed=11)
    cfg = eng.cfg
    g_n, r, n = cfg.num_groups, cfg.num_replicas, cfg.num_instances
    lead = np.arange(g_n) * r + slots
    cut = np.zeros(n, bool)
    cut[lead] = True
    # Demote slot 0, or slot 1 where slot 0 is the one that leads.
    code = conf_code(CONF_DEMOTE, 0) + 4 * (slots == 0).astype(np.int32)
    codes = np.zeros(n, np.int32)
    codes[lead] = code
    marks = []
    for t in range(48):
        isolated = cut if t < 30 else np.zeros(n, bool)
        offer = 2 if t == 0 else load
        eng.step_round(
            tick=True, propose_n=jnp.full((n,), offer, jnp.int32),
            isolate=jnp.asarray(isolated),
            conf_req=jnp.asarray(codes if t == 0 else np.zeros_like(codes)))
        for g, sh in enumerate(shadows):
            s = int(slots[g])
            sh.round(tick=True, offer=offer,
                     isolate=(s,) if isolated[lead[g]] else (),
                     conf={s: int(code[g])} if t == 0 else 0)
        assert_equal_to_the_oracle(eng, shadows, f"round {t}")
        marks.append(np.asarray(eng.state.conf.index)[lead].copy())
    marks = np.stack(marks)
    assert (marks[0] > 0).all() and (marks[29] == marks[0]).all()
    assert (marks[-1] == 0).all(), "the mark outlived its entry"
    assert np.asarray(eng.state.voter).all()
    assert not np.asarray(eng.state.in_joint).any()
    assert (eng.leaders() != slots).all() and (eng.leaders() >= 0).all()
    counters, invariants = eng.telemetry()
    assert not invariants.any()
    assert not counters[:, TM_INDEX["conf_changes_applied"]].any()
    assert (counters[:, TM_INDEX["sent_snapshot"]].sum() > 0) == bool(load)


# -- (b) the controlled scan equals the same rounds one by one ---------------------


def _fields_equal(a, b, what):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and (x == y).all(), (what, i)


@pytest.mark.parametrize("cfg,cut_rounds", [(RC3, 6), (RC5, 4)],
                         ids=["r3-minor-telemetry", "r5-major"])
def test_controlled_scan_equals_single_rounds(cfg, cut_rounds):
    a, b = MultiRaftEngine(cfg), MultiRaftEngine(cfg)
    r, n, g_n = cfg.num_replicas, cfg.num_instances, cfg.num_groups
    slots = np.random.default_rng(5).integers(0, r, g_n)
    props = jnp.full((n,), 2, jnp.int32)
    for eng in (a, b):
        eng.campaign(np.arange(g_n) * r + slots)
        for _ in range(8):
            eng.step_round()
    rows = [drain_row(t, 2, r, cut_rounds) for t in range(128)]
    ctl, iso = control_rows(rows), isolate_rows(rows, r)
    a.run_rounds(64, tick=True, propose_n=props, isolate=iso[:64],
                 control=ctl[:64])
    a.run_rounds_pipelined(64, chunk=32, tick=True, propose_n=props,
                           isolate=iso[64:], control=ctl[64:])
    joint = opened = 0
    history = [0] * n
    for row in rows:
        cut, transfer, conf = widen(cfg, row)
        b.step_round(tick=True, propose_n=props, isolate=cut,
                     transfer_to=transfer, read_req=jnp.ones((n,), bool),
                     conf_req=conf)
        fields = [np.asarray(getattr(b.state, f))
                  for f in engine_mod.HISTORY_FIELDS]
        fields = [f @ (1 << np.arange(r)) if f.ndim == 2 else f
                  for f in fields]
        history = [engine_mod.history_fold(h, [f[i] for f in fields])
                   for i, h in enumerate(history)]
        joint += int(np.asarray(b.state.in_joint).sum())
        opened += int(((np.asarray(b.state.read_index) >= 0)
                       & ~np.asarray(b.state.read_ready)).sum())
    assert isinstance(a.state, ConfBatchedState)
    _fields_equal(a.state, b.state, "state")
    inbox_equal(a.inbox, b.inbox)
    if cfg.telemetry:
        for x, y in zip(a.telemetry(), b.telemetry()):
            assert (x == y).all()
        assert not a.telemetry()[1].any()
    watch = a.scan_watch()
    assert list(watch) == list(WATCH_NAMES)
    assert watch["joint_instance_rounds"] == joint > 0
    assert watch["read_open_instance_rounds"] == opened > 0
    assert watch["reads_below_commit"] == 0
    assert watch["joint_commits_in_stall"] == 0
    assert watch["conf_marks_lost"] == 0
    assert b.scan_watch() == dict.fromkeys(WATCH_NAMES, 0)
    # The history is every round's state, folded by the rule a
    # reference follows in Python integers.
    assert a.scan_history().dtype == np.uint32
    assert a.scan_history().tolist() == history
    assert not b.scan_history().any()
    assert not np.asarray(a.state.in_joint).any()


def test_the_watch_counts_what_it_is_handed(monkeypatch):
    """A round marked CTL_STALL in which joint groups do commit (the
    cut is not there), and a read floor above every batch's index: both
    counts move. The limbs carry past 2^24."""
    eng = MultiRaftEngine(RC3)
    r, n, g_n = 3, RC3.num_instances, RC3.num_groups
    eng.campaign(np.arange(g_n) * r)
    for _ in range(8):
        eng.step_round()
    props = jnp.full((n,), 2, jnp.int32)
    ctl = np.zeros((16, CTL_COLS), np.int32)
    ctl[:, CTL_READS] = 1
    ctl[:, CTL_CONF] = conf_code(CONF_DEMOTE, 1)
    ctl[8:, CTL_STALL] = 1
    eng.run_rounds(16, propose_n=props, control=ctl)
    watch = eng.scan_watch()
    assert watch["joint_commits_in_stall"] > 0
    assert watch["reads_below_commit"] == 0
    eng._watch = eng._watch._replace(
        read_floor=jnp.full((n,), 1 << 20, jnp.int32),
        counts=eng._watch.counts.at[0, 1].set((1 << 24) - 2))
    before = watch["joint_instance_rounds"]
    eng.run_rounds(16, propose_n=props, control=ctl)
    watch = eng.scan_watch()
    assert watch["reads_below_commit"] > 0
    assert watch["joint_instance_rounds"] > (1 << 24) + 16 * n - before - 64


# -- (c) what the schedule costs a configuration that does not ask for it ----------


def test_without_a_schedule_the_scan_is_the_parents():
    """A configuration without ``conf_entries`` holds a plain
    ``BatchedState``, and its closed loop, lowered with no control
    schedule, has the inputs it had and names nothing of this PR's:
    the cells that run no control plane trace the scan they always did
    (compared text for text with the parent's archive when this was
    written: CHANGES.md, PR 32)."""
    eng = MultiRaftEngine(CELL)
    assert type(eng.state) is BatchedState
    assert type(init_state(CELL)) is BatchedState
    keys = set(sentinels.compile_keys("closed_loop"))
    args = (eng.state, eng.inbox, eng._zeros_b, eng._zeros_i, eng._tel(),
            eng._flt(), eng._lanes)
    plain = eng._closed_loop.lower(*args, None, 16)
    named = eng._closed_loop.lower(*args, None, 16, control=None, watch=None)
    assert plain.as_text() == named.as_text()
    n_in = len(jax.tree.leaves(args))
    assert len(jax.tree.leaves(plain.args_info)) == n_in
    text = plain.as_text()
    assert f"tensor<16x{CTL_COLS}xi32>" not in text
    # With one, the scan gains the rows and the watch, and no key.
    watch = engine_mod.ScanWatch(
        jnp.zeros((len(WATCH_NAMES), 2), jnp.int32),
        jnp.zeros((CELL.num_instances,), jnp.int32),
        jnp.zeros((CELL.num_instances,), jnp.uint32))
    asked = eng._closed_loop.lower(
        *args, None, 16, jnp.zeros((16, CTL_COLS), jnp.int32), watch)
    assert len(jax.tree.leaves(asked.args_info)) == n_in + 4
    assert f"tensor<16x{CTL_COLS}xi32>" in asked.as_text()
    assert set(sentinels.compile_keys("closed_loop")) == keys


def test_a_control_schedule_of_another_shape_or_kind_is_refused():
    eng = MultiRaftEngine(CELL)
    with pytest.raises(ValueError, match="CTL_COLS"):
        eng.run_rounds(16, control=np.zeros((16, 3), np.int32))
    with pytest.raises(ValueError, match="CTL_COLS"):
        eng.run_rounds(16, control=np.zeros((8, CTL_COLS), np.int32))
    with pytest.raises(ValueError, match="CTL_COLS"):
        eng.run_rounds(16, control=np.zeros((16, CTL_COLS), np.float32))
    ctl = np.zeros((16, CTL_COLS), np.int32)
    ctl[3, CTL_CONF] = conf_code(CONF_LEAVE)
    with pytest.raises(ValueError, match="conf_entries"):
        eng.run_rounds(16, control=ctl)
    with pytest.raises(ValueError, match="conf_entries"):
        eng.step_round(conf_req=jnp.zeros((CELL.num_instances,), jnp.int32))


def test_reads_and_transfers_ride_the_scan_without_conf_entries():
    """The control schedule is not the configuration changes': a
    configuration without them takes transfers and reads in a scan."""
    eng = MultiRaftEngine(CELL)
    r, g_n, n = 3, CELL.num_groups, CELL.num_instances
    eng.campaign(np.arange(g_n) * r)
    for _ in range(8):
        eng.step_round()
    ctl = np.zeros((32, CTL_COLS), np.int32)
    ctl[:, CTL_READS] = 1
    ctl[4:, CTL_FROM], ctl[4:, CTL_TO] = 1, 3
    eng.run_rounds(32, propose_n=jnp.full((n,), 2, jnp.int32), control=ctl)
    assert (eng.leaders() == 2).all()
    counters, invariants = eng.telemetry()
    assert not invariants.any()
    assert counters[:, TM_INDEX["sent_timeout_now"]].sum() == g_n
    seq, index, _ = eng.read_states()
    assert (seq.reshape(g_n, r).max(axis=1) > 4).all()


def test_the_span_says_what_the_control_plane_asked():
    from etcd_tpu.obs import spans

    eng = MultiRaftEngine(RC3)
    rows = [drain_row(t, 0, 3) for t in range(64)]
    ctl, iso = control_rows(rows), isolate_rows(rows, 3)
    eng.run_rounds(64, isolate=iso, control=ctl)
    eng.run_rounds(16)
    mine = [s for s in spans.snapshot()
            if s.name == "engine.run_rounds"
            and s.stats.get("engine") == eng._serial]
    assert [s.stats["reads"] for s in mine] == [64 * RC3.num_instances, 0]
    assert [s.stats["conf_ops"] for s in mine] == [64 - 24, 0]
    assert [s.stats["transfers"] for s in mine] == [56 - 8, 0]
    assert [s.stats["isolated"] for s in mine] == [0, 0]
    assert [s.stats["rounds"] for s in mine] == [64, 16]
