"""The reconfiguration cell's own pieces on the CPU: the generator's
schedule from the seed and its whole-period windows, each new comparison
shown to fail on a fault handed to it, the classes' derivation, the
sample, the reference wrapper against the program's oracle (and its
history against the engine's rule), the readers, the cell's seven
per-layer entries (live since PR 36) with the cells each lists and each
read on a tiny run, the rule that holds the cell's entries after the
three that were there and lets a later PR append (shown open and shown
tight on a temporary copy of ``BENCHMARK.json``), and the cell driven
tiny with its timed path broken and under both controls. (That the cell
runs tiny and is correct, and the contract's rules for its live
entries, are ``test_harness.py``'s, ``test_contract.py``'s and
``test_layers.py``'s, from the data.)"""

import json
import os
import tempfile
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.compare import engine_checks, verdict
from benchmark.drivers import engine_reconf
from benchmark.fault_checks import schedule_classes
from benchmark.generators import engine_reconf_rounds as gen
from benchmark.readers import reconf as reader
from benchmark.readers import telemetry as telemetry_reader
from benchmark.reconf_checks import (LEADER, membership_checks, run_checks,
                                     sample_checks, window_checks)

from .util import (CELLS_AT_36, REPO, bench, edited_copy, in_workloads_order,
                   own_entries, swap, tiny_root)

CELL = "engine1m-r3.joint-readindex"
SIZES = {"num_groups": 16, "num_replicas": 3}


def traffic():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "joint-readindex.json")) as f:
        return json.load(f)


def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "engine1m-r3.json")) as f:
        return json.load(f)


# -- the generator ------------------------------------------------------------------


def test_generator_is_deterministic_in_the_seed():
    a = gen.make(traffic(), SIZES, 2**31 + 5)
    b = gen.make(traffic(), SIZES, 2**31 + 5)
    assert (a["leader_slots"] == b["leader_slots"]).all()
    assert a["first_drained_node"] == b["first_drained_node"]
    assert gen.rows(a, 0, 512) == gen.rows(b, 0, 512)
    others = [gen.make(traffic(), SIZES, s) for s in range(6, 14)]
    assert any((o["leader_slots"] != a["leader_slots"]).any()
               for o in others)
    assert {o["first_drained_node"] for o in others} == {0, 1, 2}
    assert a["leader_slots"].shape == (16,)
    assert set(np.unique(a["leader_slots"])) <= {0, 1, 2}


def test_schedule_is_the_issues():
    """The drain cycle of ISSUE 32's table, period 128, node d rotating:
    hand-over asked from 8, demotion on offer from 24, LeaveJoint from
    56, promotion from 72, node d+1 cut for rounds 80-85 (the last four
    marked as stalled), LeaveJoint from 104 into the next period; reads
    in every round; never two nodes away; every edge inside a call of
    64 rounds and none on a call's first."""
    load = gen.make(traffic(), SIZES, 7)
    d0 = load["first_drained_node"]
    rows = gen.rows(load, 0, 3 * 128)
    assert all(r["reads"] for r in rows)
    for period in range(3):
        d, n = (d0 + period) % 3, (d0 + period + 1) % 3
        p = rows[period * 128:(period + 1) * 128]
        assert all(r["drained"] is None for r in p[:8] + p[56:])
        assert all((r["drained"], r["transfer_to"]) == (d, n)
                   for r in p[8:56])
        first = None if period == 0 else (gen.LEAVE, None)
        assert all(r["conf"] == first for r in p[:24])
        assert all(r["conf"] == (gen.DEMOTE, d) for r in p[24:56])
        assert all(r["conf"] == (gen.LEAVE, None) for r in p[56:72])
        assert all(r["conf"] == (gen.PROMOTE, d) for r in p[72:104])
        assert all(r["conf"] == (gen.LEAVE, None) for r in p[104:])
        assert [r["cut"] for r in p[80:86]] == [n] * 6
        assert all(r["cut"] is None for r in p[:80] + p[86:])
        assert [r["stall"] for r in p[80:86]] == [False, False] + [True] * 4
        assert not any(r["stall"] for r in p[:80] + p[86:])
    # Every call of 64 holds an edge and begins with none.
    for call in range(6):
        c = rows[call * 64:(call + 1) * 64]
        key = lambda r: (r["drained"], r["conf"], r["cut"])  # noqa: E731
        assert len({key(r) for r in c}) > 1
        if call:
            assert key(c[0]) == key(rows[call * 64 - 1])
    assert gen.rows(load, 40, 8) == rows[40:48]
    assert gen.row(load, 85)["cut"] == (d0 + 1) % 3
    assert gen.row(load, 86)["cut"] is None


@pytest.mark.parametrize("edit", [
    {"period_rounds": 100}, {"demote_round": 64}, {"cut_from_round": 58},
    {"promote_round": 50}, {"cut_rounds": 30}, {"leave_promotion_round": 128},
], ids=lambda e: next(iter(e)))
def test_a_schedule_with_an_edge_out_of_place_is_refused(edit):
    with pytest.raises(ValueError):
        gen.make(dict(traffic(), **edit), SIZES, 1)


class FakeTarget:
    groups = 16

    def __init__(self, call_s):
        self.call_s, self.calls = call_s, 0

    def call(self):
        self.calls += 1
        time.sleep(self.call_s)

    def window_opens(self):
        pass

    window_closes = window_opens

    def window_counters(self):
        return {}


@pytest.mark.parametrize("call_s,seconds,want", [
    (0.05, 0.02, 2), (0.05, 0.12, 4), (0.001, 0.0, 2), (0.04, 0.19, 6)])
def test_the_window_is_whole_periods(call_s, seconds, want):
    """Calls go on until the seconds have passed and the calls make
    whole periods (two calls each): every run measures the same mix."""
    load = gen.make(traffic(), SIZES, 3)
    target = FakeTarget(call_s)
    raw = gen.run(target, load, traffic(), seconds,
                  harness.Probe(False, 0.0, tempfile.gettempdir()))
    assert raw["calls"] == target.calls == want
    assert raw["periods"] * 2 == want and raw["rounds"] == 64 * want
    assert raw["attempted"] == raw["rounds"] and raw["failed"] == 0
    assert raw["group_rounds_per_s"] == pytest.approx(
        16 * raw["rounds"] / raw["window_s"])
    assert raw["window_s"] >= seconds and raw["replicas"] == 3


# -- the comparisons, each handed a fault -------------------------------------------

G, R = 6, 3


def home_state():
    """Six groups as a period ends: one leader, all voters, nobody a
    learner, no joint configuration."""
    n = G * R
    st = {f: np.zeros(n, np.int32)
          for f in ("role", "read_seq", "read_index")}
    st["read_ready"] = np.zeros(n, bool)
    st["in_joint"] = np.zeros(n, bool)
    st["voter"] = np.ones((n, R), bool)
    for f in ("voter_out", "learner", "learner_next"):
        st[f] = np.zeros((n, R), bool)
    for g in range(G):
        st["role"][g * R + g % R] = LEADER
        st["read_seq"][g * R + g % R] = 40 + g
        st["read_index"][g * R + g % R] = 500 + g
    st["read_index"][st["role"] != LEADER] = -1
    return st


def test_home_state_passes_the_membership_checks():
    checks = membership_checks(home_state(), G, R)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert len(checks) == 2 and all(c.limit == 0 for c in checks)


def a_follower_still_in_the_joint_configuration(st):
    i = 2 * R + 0  # group 2 is led from slot 2
    st["in_joint"][i] = True
    st["voter_out"][i] = [True, True, False]
    return {"replicas_whose_masks_differ_from_their_leaders": 1,
            "replicas_not_all_voters_at_the_periods_end": 1}


def a_group_left_with_a_learner(st):
    for s in range(R):
        st["voter"][4 * R + s, 1] = False
        st["learner"][4 * R + s, 1] = True
    return {"replicas_whose_masks_differ_from_their_leaders": 0,
            "replicas_not_all_voters_at_the_periods_end": 3}


def a_follower_that_missed_the_promotion(st):
    i = 5 * R + 1  # group 5 is led from slot 2
    st["voter"][i, 0] = False
    st["learner"][i, 0] = True
    return {"replicas_whose_masks_differ_from_their_leaders": 1,
            "replicas_not_all_voters_at_the_periods_end": 1}


def a_learner_next_left_behind(st):
    st["learner_next"][1, 2] = True
    return {"replicas_whose_masks_differ_from_their_leaders": 1,
            "replicas_not_all_voters_at_the_periods_end": 1}


@pytest.mark.parametrize("fault", [
    a_follower_still_in_the_joint_configuration, a_group_left_with_a_learner,
    a_follower_that_missed_the_promotion, a_learner_next_left_behind],
    ids=lambda f: f.__name__)
def test_membership_fault_is_not_correct(fault):
    st = home_state()
    want = fault(st)
    checks = membership_checks(st, G, R)
    assert not verdict(checks)
    assert {c.name: c.value for c in checks} == want


def window_args(periods=3):
    commit = np.arange(G) * 10 + 100
    reads = np.arange(G) + 50
    applied = np.full(G * R, 8)
    return [commit, commit + 700, reads, reads + 190, applied,
            applied + 4 * periods, periods]


def test_sound_window_passes():
    checks = window_checks(*window_args())
    assert verdict(checks), [c for c in checks if not c.ok]
    assert len(checks) == 4 and all(c.limit == 0 for c in checks)


def a_group_that_committed_nothing(a):
    a[1] = a[1].copy()
    a[1][3] = a[0][3]
    return "groups_that_committed_nothing_in_the_window"


def a_group_that_confirmed_no_read(a):
    a[3] = a[3].copy()
    a[3][0] = a[2][0]
    return "groups_that_confirmed_no_read_in_the_window"


def a_replica_that_skipped_a_change(a):
    a[5] = a[5].copy()
    a[5][7] -= 1
    return "replicas_that_did_not_apply_four_changes_a_period"


def a_replica_that_applied_one_twice(a):
    a[5] = a[5].copy()
    a[5][2] += 1
    return "replicas_that_did_not_apply_four_changes_a_period"


def a_window_that_is_no_whole_period(a):
    a[6] = 0
    a[5] = a[4].copy()
    return "window_of_no_whole_period"


@pytest.mark.parametrize("fault", [
    a_group_that_committed_nothing, a_group_that_confirmed_no_read,
    a_replica_that_skipped_a_change, a_replica_that_applied_one_twice,
    a_window_that_is_no_whole_period], ids=lambda f: f.__name__)
def test_window_fault_is_not_correct(fault):
    args = window_args()
    name = fault(args)
    checks = window_checks(*args)
    assert [c.name for c in checks if not c.ok] == [name]


def run_args():
    counters = {"sent_snapshot": 0, "sent_timeout_now": 12,
                "elections_won": 12}
    watch = {"joint_instance_rounds": 900, "read_open_instance_rounds": 400,
             "reads_below_commit": 0, "joint_commits_in_stall": 0,
             "conf_marks_lost": 0}
    return [np.zeros(G * R, np.int32), counters, watch]


def test_sound_run_passes():
    checks = run_checks(*run_args())
    assert verdict(checks), [c for c in checks if not c.ok]
    assert len(checks) == 7 and all(c.limit == 0 for c in checks)


@pytest.mark.parametrize("where,key,value,name", [
    (0, 4, 1 << 8, "instances_with_an_invariant_bit_set"),
    (0, 9, 1 << 6, "instances_with_an_invariant_bit_set"),
    (1, "sent_snapshot", 3, "snapshots_sent_in_the_run"),
    (2, "reads_below_commit", 1,
     "reads_confirmed_below_an_earlier_commit_of_the_group"),
    (2, "joint_commits_in_stall", 5,
     "commits_in_a_joint_configuration_through_the_cut"),
    (2, "conf_marks_lost", 2, "configuration_marks_overwritten_unapplied"),
    (2, "joint_instance_rounds", 0,
     "run_without_a_round_in_a_joint_configuration"),
    (1, "sent_timeout_now", 0, "run_without_a_transfer_won"),
    (1, "elections_won", 0, "run_without_a_transfer_won"),
])
def test_run_fault_is_not_correct(where, key, value, name):
    args = run_args()
    args[where][key] = value
    checks = run_checks(*args)
    assert [c.name for c in checks if not c.ok] == [name]


def sample_refs(st, history):
    slots = lambda row: tuple(np.nonzero(row)[0].tolist())  # noqa: E731
    members = lambda g: [  # noqa: E731
        (slots(st["voter"][g * R + s]), (), (), ()) for s in range(R)]
    reads = lambda g: [  # noqa: E731
        (int(st["read_seq"][i]), int(st["read_index"][i]),
         bool(st["read_ready"][i])) for i in range(g * R, g * R + R)]
    hist = lambda g: [int(h) for h in history[g * R:g * R + R]]  # noqa: E731
    return members, reads, hist


def test_sound_sample_passes_and_each_difference_is_counted():
    st = home_state()
    history = np.arange(G * R, dtype=np.uint32) * 2654435761
    members, reads, hist = sample_refs(st, history)
    sample = [1, 4]
    checks = sample_checks(st, history, R, sample, members, reads, hist)
    assert verdict(checks) and len(checks) == 3
    # A replica the program left a learner, one whose read batch stands
    # at another index, one whose history took another path: each its
    # own count, and only in sampled groups.
    bad = {k: v.copy() for k, v in st.items()}
    bad["learner"][4 * R + 1, 0] = True
    bad["read_index"][1 * R + 1] += 2
    other = history.copy()
    other[4 * R] ^= 1
    other[0] ^= 1  # group 0 is not sampled
    checks = sample_checks(bad, other, R, sample, members, reads, hist)
    assert {c.name: c.value for c in checks} == {
        "sampled_replicas_membership_differs_from_reference": 1,
        "sampled_replicas_read_state_differs_from_reference": 1,
        "sampled_replicas_history_differs_from_reference": 1}
    # An outgoing half counts only inside a joint configuration.
    bad = {k: v.copy() for k, v in st.items()}
    bad["voter_out"][1 * R, 0] = True
    assert verdict(sample_checks(bad, history, R, sample, members, reads,
                                 hist))


# -- the classes and the sample -----------------------------------------------------------


def make_driver(groups, seed, shadow_groups=30):
    cfg = config()
    cfg["sizes"]["num_groups"] = groups
    cfg["shadow_groups"] = shadow_groups
    load = gen.make(traffic(), cfg["sizes"], seed)
    return engine_reconf.Driver(cfg, traffic(), seed, ""), load


def test_classes_are_first_leader_and_g_mod_10():
    """What a group's run depends on, the schedule apart: where its
    leadership sits as each drain begins (the seed's first leader) and
    when its transfers' elections end (its replicas' timeouts, fixed at
    10 ticks and R=3 by g mod 10). At most 30."""
    driver, load = make_driver(3000, 5)
    classes = driver.classes(load)
    g = np.arange(3000)
    assert (classes == schedule_classes(load["leader_slots"], 3, 10)).all()
    assert len(np.unique(classes)) == 30
    by_key = {}
    for k, c in zip(zip(load["leader_slots"].tolist(), (g % 10).tolist()),
                    classes.tolist()):
        assert by_key.setdefault(k, c) == c
    assert len(set(by_key.values())) == 30


def test_a_class_member_that_differs_is_not_correct():
    """Class equality is ``compare.engine_checks``'; here it sees the
    configuration lanes and the history too."""
    driver, load = make_driver(60, 9)
    classes = driver.classes(load)
    n = 60 * 3
    state = {"commit": np.ones(n, np.int32), "snap_index": np.zeros(n, np.int32),
             "last": np.ones(n, np.int32), "log_term": np.ones((n, 32), np.int32),
             "conf_index": np.zeros(n, np.int32),
             "history": np.repeat(classes.astype(np.uint32), 3)}
    ok = engine_checks(state, 60, 3, 32, classes, [], None, None,
                       skip_fields=())
    assert {c.name: c.value for c in ok}[
        "groups_unequal_within_leader_class"] == 0
    for field in ("conf_index", "history"):
        bad = {k: v.copy() for k, v in state.items()}
        bad[field][17 * 3 + 1] += 1
        got = engine_checks(bad, 60, 3, 32, classes, [], None, None,
                            skip_fields=())
        assert {c.name: c.value for c in got}[
            "groups_unequal_within_leader_class"] == 1, field


@pytest.mark.parametrize("seed", [7, 2**31 + 13, 2_700_000_011])
def test_the_sample_holds_one_group_of_each_class(seed):
    driver, load = make_driver(3000, seed)
    sample = driver.sample(load)
    classes = driver.classes(load)
    assert sample == sorted(sample) == driver.sample(load)
    assert len(sample) == 30 == len({classes[g] for g in sample})
    other, load2 = make_driver(3000, seed + 1)
    assert other.sample(load2) != sample


def test_the_sample_of_a_tiny_cell_is_whole():
    driver, load = make_driver(8, 3, shadow_groups=12)
    assert driver.sample(load) == list(range(8))
    driver, load = make_driver(8, 3, shadow_groups=4)
    assert len(driver.sample(load)) == 4


# -- the reference wrapper against the program's own oracle ------------------------------


def test_the_history_rule_is_the_engines():
    from benchmark.reference import shadow_reconf
    from etcd_tpu.batched import engine

    assert shadow_reconf.HISTORY_FIELDS == engine.HISTORY_FIELDS
    rng = np.random.default_rng(32)
    h = 0
    for _ in range(50):
        values = rng.integers(-2, 2**31, size=12).tolist()
        assert (shadow_reconf.history_fold(h, values)
                == engine.history_fold(h, values))
        h = engine.history_fold(h, values)
    assert 0 < h < 2**32


@pytest.mark.parametrize("g", [0, 7, 13])
def test_reference_wrapper_steps_like_the_programs_oracle(g):
    """``reference.shadow_reconf.ReconfCluster`` hooks a control phase
    into the frozen round; ``etcd_tpu.batched.shadow.ShadowCluster`` has
    the same rules written in. Two periods of the schedule, compared
    every round in state, membership, read state and log."""
    from benchmark.reference.shadow_reconf import ReconfCluster
    from etcd_tpu.batched.shadow import ShadowCluster
    from etcd_tpu.batched.state import (CONF_DEMOTE, CONF_LEAVE,
                                        CONF_PROMOTE, conf_code)

    kinds = {gen.DEMOTE: CONF_DEMOTE, gen.LEAVE: CONF_LEAVE,
             gen.PROMOTE: CONF_PROMOTE}
    load = gen.make(traffic(), SIZES, 31)
    a = ReconfCluster(3, window=32, max_ents=4, max_props=2,
                      election_timeout=10, heartbeat_timeout=1,
                      max_inflight=256, pre_vote=True, group=g,
                      deterministic_timeouts=True,
                      deliver_shape="vectorized")
    b = ShadowCluster(3, election_timeout=10, heartbeat_timeout=1,
                      max_inflight=256, pre_vote=True, check_quorum=True,
                      group=g, deterministic_timeouts=True,
                      auto_compact_window=32, max_ents=4, max_props=2)
    lead = int(load["leader_slots"][g])
    a.round(campaigns=[lead])
    b.round(campaigns=[lead])
    for rnd in range(256):
        row = gen.row(load, rnd)
        iso = () if row["cut"] is None else (row["cut"],)
        code = 0
        if row["conf"] is not None:
            kind, node = row["conf"]
            code = conf_code(kinds[kind], node or 0)
        a.round(offer=2, tick=True, isolate=iso, control=row)
        b.round(offer=2, tick=True, isolate=iso, reads=row["reads"],
                conf=code, drained=row["drained"],
                transfer_to=row["transfer_to"])
        assert a.snapshot_state() == b.snapshot_state(), rnd
        assert a.membership() == b.membership(), rnd
        assert a.read_state() == b.read_state(), rnd
    for s in range(3):
        assert a.log_terms(s) == b.log_terms(s)
    assert a.conf_applied == b.conf_applied == [8, 8, 8]
    assert sum(x[0] for x in a.read_state()) > 100
    assert len(set(a.history())) == 3 and all(a.history())


# -- the readers -------------------------------------------------------------------------


def reader_ctx():
    before = {"reads_confirmed": 100, "conf_changes_applied": 0,
              "elections_won": 2, "sent_timeout_now": 2}
    after = {"reads_confirmed": 1100, "conf_changes_applied": 240,
             "elections_won": 11, "sent_timeout_now": 12}
    return {"raw": {
        "telemetry": {"before": before, "after": after},
        "watch": {"before": {"joint_instance_rounds": 1000,
                             "read_open_instance_rounds": 500},
                  "after": {"joint_instance_rounds": 4000,
                            "read_open_instance_rounds": 2600}},
        "groups": 10, "replicas": 3, "rounds": 200,
        "proposals_per_round": 2, "entries_committed": 3000}}


def test_readers():
    ctx = reader_ctx()
    assert reader.joint_pct(ctx) == 50.0
    assert reader.rounds_to_confirm(ctx) == 2.1
    assert telemetry_reader.per_kgr(ctx, ["reads_confirmed"]) == 500.0
    assert telemetry_reader.per_kgr(ctx, ["conf_changes_applied"]) == 120.0
    assert telemetry_reader.committed_pct(ctx) == 75.0
    assert telemetry_reader.share_pct(
        ctx, ["elections_won"], ["sent_timeout_now"]) == 90.0


def test_readers_find_nothing_in_another_drivers_run():
    ctx = {"raw": {"groups": 8, "rounds": 64}}
    assert reader.joint_pct(ctx) is None
    assert reader.rounds_to_confirm(ctx) is None
    ctx = reader_ctx()
    del ctx["raw"]["watch"]["after"]["joint_instance_rounds"]
    assert reader.joint_pct(ctx) is None
    ctx = reader_ctx()
    del ctx["raw"]["telemetry"]
    assert reader.rounds_to_confirm(ctx) is None
    ctx = reader_ctx()
    ctx["raw"]["telemetry"]["after"]["reads_confirmed"] = 100
    assert reader.rounds_to_confirm(ctx) is None


# -- the cell's entries: where they stand, which cells they list, each read tiny -----

SEVEN = ["round.control_pct", "read.confirmed_per_kgr",
         "read.rounds_to_confirm", "reconf.joint_pct",
         "reconf.applied_per_kgr", "reconf.committed_pct",
         "reconf.transfer_won_pct"]
WERE = ["engine64k-r3", "engine10k-r5", "engine100k-r3"]
CELLS_WERE = CELLS_AT_36[:3]


def entries_rule(b: dict) -> None:
    """The seven stand right after the 20 entries PR 28's file had, in
    their order. ``raft_control`` runs in every cell (transfers and
    ReadIndex batches are the round's, asked or not), so its share
    lists the five PR 36 found and after them every later cell; the
    two reads' entries list this cell first and after it the cells
    whose traffic asks reads (``test_lists.py`` holds each, cell by
    cell); the four ``reconf.*`` keep the cell they were written
    for."""
    rows = b["per_layer"]
    assert [m["name"] for m in rows[20:27]] == SEVEN
    assert not set(SEVEN) & {m["name"] for m in rows[:20] + rows[27:]}
    assert rows[20]["workloads"][:5] == CELLS_AT_36
    for m in rows[20:23]:
        assert CELL in m["workloads"] and in_workloads_order(b, m)
    for m in rows[21:23]:
        assert m["workloads"][0] == CELL
    own_entries(b, SEVEN[3:], 23, CELL)


def test_the_seven_are_live_with_these_workloads():
    entries_rule(bench())
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "parked", "engine1m-r3_layers.json"))


def gained_rule(b: dict) -> None:
    """PR 32's three additions, each right after the three entries its
    list had: the configuration, the cell, the cell's name under its
    end-to-end metric (the order-relative form of ``test_replace.py::
    test_the_cell_follows_the_cells_that_were_there``). What a later PR
    appends after them is its own."""
    assert [c["name"] for c in b["configs"]][:4] == WERE + ["engine1m-r3"]
    assert [w["name"] for w in b["workloads"]][:4] == CELLS_WERE + [CELL]
    moved = [e for e in b["end_to_end"] if e["name"] == "group_rounds_per_s"]
    assert moved[0]["workloads"][:4] == CELLS_WERE + [CELL]
    assert b["workloads"][3]["chips"] == 1
    assert b["workloads"][3]["config"] == "engine1m-r3"


def test_the_benchmark_gained_one_config_one_cell_and_one_name():
    gained_rule(bench())
    cfg = config()
    assert cfg["reduced"] == [] and cfg["sizes"]["num_groups"] == 1_048_576
    assert 1 <= len(cfg["source"]) <= 200
    assert set(cfg["assumed"]) >= {"window_ents_props", "round",
                                   "randomized_timeout", "drain_cycle",
                                   "snapshots"}


def _append_a_cell(b: dict) -> None:
    """What the next ``model_config`` PR does: a configuration, a cell
    and its name under ``group_rounds_per_s``, each at its list's end."""
    b["configs"].append(dict(b["configs"][3], name="engine2m-r3",
                             file="benchmark/configs/engine2m-r3.json"))
    b["workloads"].append(dict(b["workloads"][3], name="engine2m-r3.drain",
                               config="engine2m-r3", traffic="drain"))
    b["end_to_end"][0]["workloads"].append("engine2m-r3.drain")


# Index 3 is ``engine1m-r3``'s entry in each of the three lists.
OPEN = {
    "a configuration, a cell and its name appended": _append_a_cell,
    "the cell after it taken away again": lambda b: (
        b["configs"].pop(), b["workloads"].pop(),
        b["end_to_end"][0]["workloads"].pop()),
}
TIGHT = {
    "the configuration removed": lambda b: b["configs"].pop(3),
    "the cell removed": lambda b: b["workloads"].pop(3),
    "its name under the metric removed": lambda b: (
        b["end_to_end"][0]["workloads"].pop(3)),
    "the configuration renamed": lambda b: b["configs"][3].update(
        name="engine1m-r3b"),
    "the cell renamed": lambda b: b["workloads"][3].update(
        name="engine1m-r3.joint"),
    "the configurations re-ordered": lambda b: swap(b["configs"], 2, 3),
    "the cells re-ordered": lambda b: swap(b["workloads"], 3, 4),
    "the names under the metric re-ordered": lambda b: swap(
        b["end_to_end"][0]["workloads"], 0, 3),
    "the cell moved to four chips": lambda b: b["workloads"][3].update(
        chips=4),
    "a cell put before it": lambda b: b["workloads"].insert(
        0, dict(b["workloads"][0], name="engine64k-r3.other")),
}


@pytest.mark.parametrize("edit", OPEN.values(), ids=OPEN.keys())
def test_the_rule_lets_a_later_pr_append_a_cell(tmp_path, edit):
    gained_rule(edited_copy(tmp_path, edit))


@pytest.mark.parametrize("edit", TIGHT.values(), ids=TIGHT.keys())
def test_the_rule_holds_the_cells_entries_where_they_are(tmp_path, edit):
    with pytest.raises(AssertionError):
        gained_rule(edited_copy(tmp_path, edit))


@pytest.fixture(scope="module")
def layer_run(root):
    cell = harness.Cell(root, CELL)
    ctx, checks = harness.measure(cell, 2**31 + 32, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert verdict(checks), [c for c in checks if not c.ok]
    return cell, ctx


def test_each_counter_reader_on_a_tiny_run(layer_run):
    """No trace on the CPU: the six counter metrics are read, the trace
    share finds nothing and is left out."""
    cell, ctx = layer_run
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values(layer)
    assert set(SEVEN[1:]) <= set(layer) and SEVEN[0] not in layer
    units = {m["name"]: m["unit"] for m in bench()["per_layer"]}
    for name in SEVEN[1:]:
        assert layer[name]["unit"] == units[name]
        assert layer[name]["value"] > 0.0
    # About half the rounds in a joint configuration; a batch takes two
    # rounds and a little (the cut, the elections); commits fall short
    # of what is offered by the transfers and the cut.
    assert 35.0 < layer["reconf.joint_pct"]["value"] < 65.0
    assert 2.0 <= layer["read.rounds_to_confirm"]["value"] < 3.0
    assert 80.0 < layer["reconf.committed_pct"]["value"] < 100.0
    assert layer["reconf.transfer_won_pct"]["value"] <= 100.0
    # Four changes a replica a period of 128 rounds.
    assert layer["reconf.applied_per_kgr"]["value"] == pytest.approx(
        1e3 * 4 * 3 / 128)


def test_the_trace_reader_on_a_reduced_trace(layer_run):
    """The control phase's share of the round from a reduced trace as
    ``reduce/trace.py`` gives it (the chip's scopes; seconds of PR 27's
    builder's traced run, rounded)."""
    cell, ctx = layer_run
    scope_s = {"raft_deliver": 1.4556, "raft_route": 0.3115,
               "unscoped": 0.1472, "raft_emit": 0.1027,
               "raft_telemetry": 0.0527, "raft_tick": 0.0315,
               "raft_propose": 0.0303, "raft_control": 0.0186}
    red = {"scope_s": scope_s, "leaf_s": sum(scope_s.values()),
           "modules": {}}
    layer = harness.per_layer_metrics(cell, dict(ctx, trace=red))
    harness.refuse_bad_values(layer)
    assert layer["round.control_pct"]["value"] == pytest.approx(
        100 * 0.0186 / sum(scope_s.values()))
    del scope_s["raft_control"]
    layer = harness.per_layer_metrics(cell, dict(ctx, trace=red))
    assert "round.control_pct" not in layer


# -- the cell driven tiny: the timed path broken, and the controls ------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("reconf")))


def test_the_scan_sees_the_control_schedule(root, monkeypatch):
    """The timed path broken underneath: the engine is handed a control
    schedule that asks nothing, so no leadership moves, no change is
    offered and no read is asked."""
    from etcd_tpu.batched import MultiRaftEngine

    real = MultiRaftEngine.run_rounds

    def run_rounds(self, rounds, tick=True, propose_n=None, isolate=None,
                   control=None):
        real(self, rounds, tick=tick, propose_n=propose_n, isolate=isolate,
             control=np.zeros_like(control))

    monkeypatch.setattr(MultiRaftEngine, "run_rounds", run_rounds)
    cell = harness.Cell(root, CELL)
    _ctx, checks = harness.measure(cell, 12, 0.3, False,
                                   time.perf_counter(), require_tpu=False)
    bad = {c.name for c in checks if not c.ok}
    assert {"groups_that_confirmed_no_read_in_the_window",
            "replicas_that_did_not_apply_four_changes_a_period",
            "run_without_a_round_in_a_joint_configuration",
            "run_without_a_transfer_won",
            "sampled_replicas_state_differs_from_reference",
            "sampled_replicas_read_state_differs_from_reference",
            "sampled_replicas_history_differs_from_reference"} <= bad


def test_a_call_that_does_nothing_is_not_correct(root, monkeypatch):
    real_call = engine_reconf.Driver.call

    def call(self):
        if self.calls == 3:
            self.calls += 1
            self.rounds_done += self.rpc
            return
        real_call(self)

    monkeypatch.setattr(engine_reconf.Driver, "call", call)
    cell = harness.Cell(root, CELL)
    ctx, checks = harness.measure(cell, 4, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert ctx["raw"]["calls"] >= 2
    assert not verdict(checks)


def test_a_program_without_the_control_plane_fails_at_once(root, monkeypatch):
    """The parent: its ``run_rounds`` takes no control schedule. The
    driver says so before it builds anything."""
    from etcd_tpu.batched import MultiRaftEngine

    def run_rounds(self, rounds, tick=True, propose_n=None, isolate=None):
        raise AssertionError("not reached")

    monkeypatch.setattr(MultiRaftEngine, "run_rounds", run_rounds)
    cell = harness.Cell(root, CELL)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="control schedule"):
        harness.measure(cell, 4, 0.3, False, time.perf_counter(),
                        require_tpu=False)
    assert time.perf_counter() - t0 < 5


@pytest.fixture(scope="module")
def driven(root):
    """The cell's driver after a tiny window, kept open for the
    controls."""
    cell = harness.Cell(root, CELL)
    seed = 2**31 + 77
    load = gen.make(cell.traffic, cell.config["sizes"], seed)
    driver = engine_reconf.Driver(cell.config, cell.traffic, seed, "")
    driver.setup(load, gen)
    raw = gen.run(driver, load, cell.traffic, 0.3,
                  harness.Probe(False, 0.0, tempfile.gettempdir()))
    yield driver, load, raw
    driver.close()


def test_the_window_holds_the_whole_cycle(driven):
    driver, load, raw = driven
    assert raw["calls"] % 2 == 0 and raw["periods"] >= 1
    assert raw["attempted"] == raw["rounds"] and raw["failed"] == 0
    moved = {k: raw["telemetry"]["after"][k] - v
             for k, v in raw["telemetry"]["before"].items()}
    n = driver.groups * 3
    assert moved["conf_changes_applied"] == 4 * n * raw["periods"]
    assert moved["sent_snapshot"] == 0 and moved["reads_confirmed"] > 0
    assert moved["sent_timeout_now"] > 0 and moved["elections_won"] > 0
    watch = {k: raw["watch"]["after"][k] - v
             for k, v in raw["watch"]["before"].items()}
    assert watch["joint_instance_rounds"] > 0
    assert watch["reads_below_commit"] == 0
    assert watch["joint_commits_in_stall"] == 0
    assert 0 < raw["entries_committed"] <= (
        raw["rounds"] * driver.groups * load["proposals_per_round"])


def test_sound_reference_is_correct(driven):
    driver, load, raw = driven
    checks = driver.check(load, raw)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks) and len(checks) == 26
    assert driver.rounds_done % load["period_rounds"] == 0
    assert driver.derailed == []
    # Read once however often `check` is called.
    calls, final = driver.calls, driver.final
    driver.check(load, raw)
    assert driver.final is final and driver.calls == calls


@pytest.mark.parametrize("control", engine_reconf.CONTROLS)
def test_control_is_not_correct(driven, control):
    """Commit on the incoming majority alone commits through the cut
    and is caught up with: only the history tells, and where the cut
    node was carried past the ring, what its snapshot left. A read
    confirmed at once runs the read state ahead."""
    driver, load, raw = driven
    checks = driver.check(load, raw, control=control)
    assert not verdict(checks)
    bad = {c.name for c in checks if not c.ok}
    assert "sampled_replicas_history_differs_from_reference" in bad
    assert bad <= {"sampled_replicas_state_differs_from_reference",
                   "sampled_replicas_log_differs_from_reference",
                   "sampled_replicas_membership_differs_from_reference",
                   "sampled_replicas_read_state_differs_from_reference",
                   "sampled_replicas_history_differs_from_reference"}
    if control == engine_reconf.CONTROLS[1]:
        assert "sampled_replicas_read_state_differs_from_reference" in bad
    # Not by the reference's crash: none left the protocol
    # (``control_reconf.py`` counts the same way).
    assert driver.derailed == []


def test_the_existing_control_script_names_the_first_control(driven):
    """``benchmark/control.py`` passes ``control=True``."""
    driver, load, raw = driven
    assert not verdict(driver.check(load, raw, control=True))
    with pytest.raises(ValueError):
        driver.check(load, raw, control="no_such_control")
