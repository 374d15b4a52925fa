"""The fault cell's own pieces on the CPU: the generator's schedule from
the seed, each new comparison shown to fail on a fault handed to it, the
classes' derivation, the sample, the reference wrapper against the
program's oracle, the telemetry readers, the cell's seven per-layer
entries (live since PR 36) with the cells each lists and each read on a
tiny run, and the cell driven tiny with its timed path broken and under
both controls. (That the cell runs tiny and is correct, and the
contract's rules for its live entries, are ``test_harness.py``'s,
``test_contract.py``'s and ``test_layers.py``'s, from the data.)"""

import json
import os
import tempfile
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.compare import engine_checks, verdict
from benchmark.drivers import engine_faults
from benchmark.fault_checks import (LEADER, REPLICATE, group_checks,
                                    quiet_checks, schedule_classes,
                                    window_checks)
from benchmark.generators import engine_faults_rounds as gen
from benchmark.readers import telemetry as reader

from .util import (CELLS_AT_36, REPO, bench, in_workloads_order, own_entries,
                   tiny_root)

CELL = "engine100k-r3.elections"
SIZES = {"num_groups": 16, "num_replicas": 3}


def traffic():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "elections.json")) as f:
        return json.load(f)


def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "engine100k-r3.json")) as f:
        return json.load(f)


# -- the generator ------------------------------------------------------------------


def test_generator_is_deterministic_in_the_seed():
    a = gen.make(traffic(), SIZES, 2**31 + 5)
    b = gen.make(traffic(), SIZES, 2**31 + 5)
    assert (a["leader_slots"] == b["leader_slots"]).all()
    assert a["first_cut_node"] == b["first_cut_node"]
    assert (gen.schedule(a, 0, 512) == gen.schedule(b, 0, 512)).all()
    others = [gen.make(traffic(), SIZES, s) for s in range(6, 12)]
    assert any((o["leader_slots"] != a["leader_slots"]).any()
               for o in others)
    assert {o["first_cut_node"] for o in others} == {0, 1, 2}
    assert a["leader_slots"].shape == (16,)
    assert set(np.unique(a["leader_slots"])) <= {0, 1, 2}


def test_schedule_is_the_issues():
    """Period 128: all up for 32 rounds, one node away for 64, healed
    for 32; the node rotates; never two away; both edges of an outage
    fall inside a call of 64 rounds."""
    load = gen.make(traffic(), SIZES, 7)
    k0 = load["first_cut_node"]
    sched = gen.schedule(load, 0, 3 * 128)
    assert sched.shape == (384, 3) and sched.dtype == bool
    assert sched.sum(axis=1).max() == 1
    for period in range(3):
        rows = sched[period * 128:(period + 1) * 128]
        k = (k0 + period) % 3
        assert not rows[:32].any() and not rows[96:].any()
        assert rows[32:96, k].all() and rows[32:96].sum() == 64
    rpc = load["rounds_per_call"]
    for call in range(6):
        rows = sched[call * rpc:(call + 1) * rpc].any(axis=1)
        assert rows.any() and not rows.all(), "an edge inside every call"
    assert (gen.schedule(load, 40, 8) == sched[40:48]).all()
    assert gen.cut_node(load, 31) is None and gen.cut_node(load, 32) == k0
    assert gen.cut_node(load, 95) == k0 and gen.cut_node(load, 96) is None


def test_a_period_that_is_not_whole_calls_is_refused():
    with pytest.raises(ValueError):
        gen.make(dict(traffic(), period_rounds=100), SIZES, 1)


# -- the comparisons, each handed a fault -------------------------------------------

G, R, W = 6, 3, 32


def sound_state():
    """Six groups in the state the cell ends in: one leader, replicas
    agreed, two replicas at the leader's commit and one carried by
    snapshots half a ring behind."""
    n = G * R
    st = {f: np.zeros(n, np.int32) for f in
          ("term", "role", "lead", "commit", "last", "snap_index")}
    st["log_term"] = np.zeros((n, W), np.int32)
    for g in range(G):
        lead = g % R
        for s in range(R):
            i = g * R + s
            st["term"][i] = 3
            st["role"][i] = LEADER if s == lead else 0
            st["lead"][i] = lead + 1
            behind = 16 if s == (lead + 1) % R else 0
            st["commit"][i] = 200 + g - behind
            st["last"][i] = st["commit"][i] + (0 if behind else 4)
            st["snap_index"][i] = st["last"][i] - (0 if behind else 16)
            for idx in range(st["snap_index"][i] + 1, st["last"][i] + 1):
                st["log_term"][i, idx % W] = 2 if idx < 195 else 3
    return st


def test_sound_state_passes_the_group_checks():
    checks = group_checks(sound_state(), G, R, W)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert len(checks) == 5 and all(c.limit == 0 for c in checks)


def two_leaders_in_a_term(st):
    st["role"][1 * R + 2] = LEADER
    return "groups_with_two_leaders_in_a_term"


def no_leader(st):
    st["role"][2 * R:3 * R] = 0
    return "groups_without_exactly_one_leader"


def a_stale_leader_beside_the_new_one(st):
    i = 3 * R + (3 + 1) % R
    st["role"][i], st["term"][i] = LEADER, 2
    return "groups_without_exactly_one_leader"


def replicas_disagree_on_the_leader(st):
    st["lead"][4 * R + 1] = 0
    return "groups_disagreeing_on_term_or_leader"


def a_rewritten_committed_entry(st):
    lead = 5 % R
    i = 5 * R + lead
    st["log_term"][i, int(st["commit"][i] - 1) % W] = 4
    return "groups_whose_committed_prefixes_differ"


def a_replica_past_half_the_ring_behind(st):
    st["commit"][0 * R + 1] -= 1
    return "replicas_lagging_their_leader_past_half_the_ring"


GROUP_FAULTS = [two_leaders_in_a_term, no_leader,
                a_stale_leader_beside_the_new_one,
                replicas_disagree_on_the_leader,
                a_rewritten_committed_entry,
                a_replica_past_half_the_ring_behind]


@pytest.mark.parametrize("fault", GROUP_FAULTS, ids=lambda f: f.__name__)
def test_group_fault_is_not_correct(fault):
    st = sound_state()
    name = fault(st)
    checks = group_checks(st, G, R, W)
    assert not verdict(checks)
    assert name in {c.name for c in checks if not c.ok}


def test_two_leaders_in_different_terms_are_not_two_in_a_term():
    st = sound_state()
    a_stale_leader_beside_the_new_one(st)
    bad = {c.name for c in group_checks(st, G, R, W) if not c.ok}
    assert "groups_with_two_leaders_in_a_term" not in bad


def window_args():
    before = {"elections_started": 5, "elections_won": 2,
              "sent_snapshot": 0, "sent_append": 10}
    after = {"elections_started": 50, "elections_won": 20,
             "sent_snapshot": 300, "sent_append": 9000}
    return [np.full(G, 100), np.full(G, 900), np.zeros(G * R, np.int32),
            before, after]


def test_sound_window_passes():
    assert verdict(window_checks(*window_args()))


def an_invariant_bit(args):
    args[2][7] = 1 << 5
    return "instances_with_an_invariant_bit_set"


def a_group_that_committed_nothing(args):
    args[1][3] = 100
    return "groups_that_committed_nothing_in_the_window"


def no_election_won(args):
    args[4]["elections_won"] = args[3]["elections_won"]
    return "window_without_elections_won"


def no_election_started(args):
    args[4]["elections_started"] = args[3]["elections_started"]
    return "window_without_elections_started"


def no_snapshot_sent(args):
    args[4]["sent_snapshot"] = 0
    return "window_without_sent_snapshot"


@pytest.mark.parametrize("fault", [
    an_invariant_bit, a_group_that_committed_nothing, no_election_won,
    no_election_started, no_snapshot_sent], ids=lambda f: f.__name__)
def test_window_fault_is_not_correct(fault):
    args = window_args()
    name = fault(args)
    checks = window_checks(*args)
    assert not verdict(checks)
    assert [c.name for c in checks if not c.ok] == [name]


def quiet_state():
    """``sound_state``'s groups once load has stopped: every replica
    level with its leader, every progress row in REPLICATE."""
    st = sound_state()
    by_group = st["commit"].reshape(G, R)
    st["commit"] = np.repeat(by_group.max(axis=1), R)
    st["last"] = st["commit"].copy()
    st["pr_state"] = np.full((G * R, R), REPLICATE, np.int32)
    return st


def test_a_quiet_state_passes():
    checks = quiet_checks(quiet_state(), G, R)
    assert verdict(checks) and [c.limit for c in checks] == [0]


def a_follower_still_carried_by_snapshots(st):
    lead = 2 % R
    st["pr_state"][2 * R + lead, (lead + 1) % R] = 2  # SNAPSHOT
    return 1


def a_follower_still_probed(st):
    lead = 4 % R
    st["pr_state"][4 * R + lead, (lead + 2) % R] = 0  # PROBE
    return 1


def a_follower_a_commit_behind(st):
    st["commit"][3 * R + (3 + 1) % R] -= 1
    return 1


def a_follower_an_entry_short(st):
    st["last"][1 * R + (1 + 2) % R] -= 1
    return 1


def a_group_without_a_leader(st):
    st["role"][5 * R:6 * R] = 0
    return R


def a_followers_own_stale_progress_row(st):
    """Only the leader's row of ``pr_state`` means anything."""
    lead = 0 % R
    st["pr_state"][0 * R + (lead + 1) % R, :] = 0
    return 0


@pytest.mark.parametrize("fault", [
    a_follower_still_carried_by_snapshots, a_follower_still_probed,
    a_follower_a_commit_behind, a_follower_an_entry_short,
    a_group_without_a_leader, a_followers_own_stale_progress_row],
    ids=lambda f: f.__name__)
def test_quiet_fault_is_counted(fault):
    st = quiet_state()
    want = fault(st)
    (check,) = quiet_checks(st, G, R)
    assert check.name == "replicas_not_caught_up_once_load_stops"
    assert check.value == want and check.ok == (want == 0)


# -- the classes, and the sample the reference follows ------------------------------


def test_classes_at_ten_ticks_are_leader_slot_and_g_mod_10():
    """The docstring's derivation: at ``election_timeout`` 10 and R = 3
    the timeout-hash residues of a group's instance ids are fixed by
    g mod 10, so a class is (first leader, g mod 10): at most 30."""
    groups = 102_400
    load = gen.make(traffic(), dict(SIZES, num_groups=groups), 2**31 + 27)
    classes = schedule_classes(load["leader_slots"], 3, 10)
    assert classes.shape == (groups,) and classes.max() + 1 == 30
    g = np.arange(groups)
    by_hand = load["leader_slots"].astype(np.int64) * 10 + g % 10
    pairs = set(zip(classes.tolist(), by_hand.tolist()))
    assert len(pairs) == 30, "the two partitions are one"
    # The residues themselves, in Python integers, at the largest ids.
    for gg in (0, 9, 90_393, 102_399):
        for s in range(3):
            want = ((gg * 3 + s + 1) * 7919) % 10
            assert want == ((((gg % 10) * 3 + s + 1) * 7919) % 10)


def test_classes_at_a_long_timeout_do_not_overflow():
    slots = np.array([0, 1, 2, 0, 1, 2], np.int32)
    classes = schedule_classes(slots, 3, 1 << 20)
    assert len(set(classes.tolist())) == 6


def test_a_class_member_that_differs_is_not_correct():
    st = sound_state()
    classes = np.array([0, 1, 2, 0, 1, 2])  # g and g+3 ran one schedule
    for f in st:  # make the class members equal first
        rows = st[f].reshape((G, R) + st[f].shape[1:])
        rows[3:] = rows[:3]
    args = (G, R, W, classes, [], lambda g: None, lambda g, s: None)
    names = lambda cs: {c.name for c in cs if not c.ok}  # noqa: E731
    assert "groups_unequal_within_leader_class" not in names(
        engine_checks(st, *args, skip_fields=()))
    st["last"][4 * R + 2] += 1
    assert "groups_unequal_within_leader_class" in names(
        engine_checks(st, *args, skip_fields=()))


@pytest.mark.parametrize("seed", [7, 2**31 + 13, 2_700_000_011])
def test_the_sample_holds_distinct_classes_a_third_past_the_old_wrap(seed):
    cfg = config()
    load = gen.make(traffic(), cfg["sizes"], seed)
    driver = engine_faults.Driver(cfg, traffic(), seed, "")
    sample = driver.sample(load)
    n = cfg["shadow_groups"]
    assert sample == driver.sample(load) == sorted(set(sample))
    assert len(sample) == n
    classes = driver.classes(load)
    assert len(set(classes[sample])) == min(n, 30)
    past = [g for g in sample
            if (g + 1) * 3 - 1 >= engine_faults.WRAPPED_FROM_IID]
    assert len(past) >= -(-n // 3)
    assert (engine_faults.WRAPPED_FROM_IID + 1) * 7919 >= 2**31
    assert engine_faults.WRAPPED_FROM_IID * 7919 < 2**31
    other = engine_faults.Driver(cfg, traffic(), seed + 1, "")
    assert other.sample(load) != sample


def test_the_sample_of_a_tiny_cell_is_whole():
    cfg = config()
    cfg["sizes"]["num_groups"], cfg["shadow_groups"] = 8, 4
    load = gen.make(traffic(), cfg["sizes"], 5)
    sample = engine_faults.Driver(cfg, traffic(), 5, "").sample(load)
    assert len(sample) == 4 == len(set(sample))


# -- the reference wrapper against the program's own oracle ----------------------------


@pytest.mark.parametrize("shape", ["merged", "vectorized", "lanes"])
def test_reference_wrapper_steps_like_the_programs_oracle(shape):
    """``reference.shadow_faults.FaultsCluster`` wraps the frozen copy;
    ``etcd_tpu.batched.shadow.ShadowCluster`` has the same rules written
    in. Two periods of the schedule, compared every round."""
    from benchmark.reference.shadow_faults import FaultsCluster
    from etcd_tpu.batched.shadow import ShadowCluster

    load = gen.make(traffic(), SIZES, 31)
    for g in (0, 7, 13):
        a = FaultsCluster(3, window=32, max_ents=4, max_props=2,
                          election_timeout=10, heartbeat_timeout=1,
                          max_inflight=256, pre_vote=True, group=g,
                          deterministic_timeouts=True, deliver_shape=shape)
        b = ShadowCluster(3, election_timeout=10, heartbeat_timeout=1,
                          max_inflight=256, pre_vote=True,
                          check_quorum=True, group=g,
                          deterministic_timeouts=True,
                          auto_compact_window=32, max_ents=4, max_props=2,
                          deliver_shape=shape)
        lead = int(load["leader_slots"][g])
        a.round(campaigns=[lead])
        b.round(campaigns=[lead])
        for rnd in range(256):
            k = gen.cut_node(load, rnd)
            iso = () if k is None else (k,)
            a.round(offer=2, tick=True, isolate=iso)
            b.round(offer=2, tick=True, isolate=iso)
            assert a.snapshot_state() == b.snapshot_state(), (g, rnd)
        for s in range(3):
            assert a.log_terms(s) == b.log_terms(s)
        terms = {t for t, *_ in a.snapshot_state()}
        assert max(terms) >= 1


# -- the telemetry readers ---------------------------------------------------------


def reader_ctx():
    before = {"elections_started": 10, "elections_won": 5,
              "sent_snapshot": 0, "sent_vote_req": 4, "sent_vote_resp": 2,
              "sent_append": 100, "recv_messages": 7}
    after = {"elections_started": 110, "elections_won": 45,
             "sent_snapshot": 400, "sent_vote_req": 54, "sent_vote_resp": 32,
             "sent_append": 1100, "recv_messages": 9999}
    return {"raw": {"telemetry": {"before": before, "after": after},
                    "groups": 10, "rounds": 200, "proposals_per_round": 2,
                    "entries_committed": 3000}}


def test_telemetry_readers():
    ctx = reader_ctx()
    assert reader.per_kgr(ctx, ["elections_started"]) == 50.0
    assert reader.per_kgr(ctx, ["sent_snapshot"]) == 200.0
    assert reader.share_pct(ctx, ["elections_won"],
                            ["elections_started"]) == 40.0
    # 80 vote messages of 80 + 400 snapshots + 1000 appends sent.
    assert reader.share_pct(ctx, ["sent_vote_req", "sent_vote_resp"],
                            ["sent_*"]) == pytest.approx(100 * 80 / 1480)
    assert reader.committed_pct(ctx) == 75.0


def test_telemetry_readers_find_nothing_in_another_drivers_run():
    ctx = {"raw": {"groups": 8, "rounds": 64}}
    assert reader.per_kgr(ctx, ["elections_started"]) is None
    assert reader.share_pct(ctx, ["elections_won"],
                            ["elections_started"]) is None
    assert reader.committed_pct(ctx) is None
    ctx = reader_ctx()
    assert reader.per_kgr(ctx, ["no_such_counter"]) is None
    ctx["raw"]["telemetry"]["after"]["elections_started"] = 10
    assert reader.share_pct(ctx, ["elections_won"],
                            ["elections_started"]) is None


# -- the cell's per-layer entries, and each read on a tiny run ------------------------

SEVEN = ["round.tick_pct", "round.telemetry_pct",
         "election.started_per_kgr", "election.snapshots_per_kgr",
         "election.won_pct", "election.vote_msg_pct",
         "election.committed_pct"]


def entries_rule(b: dict) -> None:
    """The seven stand right after the 13 entries PR 24's file had, in
    their order. The two shares of the round follow the work: they
    list the cells PR 36 found first (``raft_tick`` runs in every one,
    ``raft_telemetry`` where the configuration turns the plane on) and
    after them whatever cell's program runs the scope too
    (``test_lists.py`` holds that, cell by cell); the five counter
    metrics keep the cell they were written for."""
    rows = b["per_layer"]
    assert [m["name"] for m in rows[13:20]] == SEVEN
    assert not set(SEVEN) & {m["name"] for m in rows[:13] + rows[20:]}
    files = {c["name"]: c["file"] for c in b["configs"]}
    plane_on = []
    for w in b["workloads"]:
        if w["name"] in CELLS_AT_36:
            with open(os.path.join(REPO, files[w["config"]])) as f:
                if json.load(f)["sizes"].get("telemetry"):
                    plane_on.append(w["name"])
    assert CELL in plane_on and len(plane_on) == 3
    assert rows[13]["workloads"][:5] == CELLS_AT_36
    assert rows[14]["workloads"][:3] == plane_on
    for m in rows[13:15]:
        assert in_workloads_order(b, m)
    own_entries(b, SEVEN[2:], 15, CELL, new_layers={"telemetry plane"})


def test_the_seven_are_live_with_these_workloads():
    entries_rule(bench())
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "parked", "engine100k-r3_layers.json"))


@pytest.fixture(scope="module")
def layer_run(root):
    cell = harness.Cell(root, CELL)
    ctx, checks = harness.measure(cell, 2**31 + 28, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert verdict(checks), [c for c in checks if not c.ok]
    return cell, ctx


def test_each_counter_reader_on_a_tiny_run(layer_run):
    """No trace on the CPU: the five counter metrics are read, the two
    trace shares find nothing and are left out."""
    cell, ctx = layer_run
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values(layer)
    assert set(SEVEN[2:]) <= set(layer)
    assert not set(SEVEN[:2]) & set(layer)
    units = {m["name"]: m["unit"] for m in bench()["per_layer"]}
    for name in SEVEN[2:]:
        assert layer[name]["unit"] == units[name]
        assert layer[name]["value"] > 0.0
    assert layer["election.won_pct"]["value"] <= 100.0
    assert layer["election.committed_pct"]["value"] < 100.0


def test_each_trace_reader_on_a_reduced_trace(layer_run):
    """The two shares of the round from a reduced trace as
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
    assert layer["round.tick_pct"]["value"] == pytest.approx(
        100 * 0.0315 / sum(scope_s.values()))
    assert layer["round.telemetry_pct"]["value"] == pytest.approx(
        100 * 0.0527 / sum(scope_s.values()))
    # A program with no telemetry plane has no such scope: left out.
    del scope_s["raft_telemetry"]
    layer = harness.per_layer_metrics(cell, dict(ctx, trace=red))
    assert "round.telemetry_pct" not in layer
    assert "round.tick_pct" in layer


# -- the cell driven tiny: the timed path broken, and the controls ------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("faults")))


def test_the_scan_sees_the_schedule(root, monkeypatch):
    """The timed path broken underneath: the engine is handed no
    schedule, so no node is ever cut off."""
    from etcd_tpu.batched import MultiRaftEngine

    real = MultiRaftEngine.run_rounds

    def run_rounds(self, rounds, tick=True, propose_n=None, isolate=None):
        real(self, rounds, tick=tick, propose_n=propose_n,
             isolate=None if isolate is None else np.zeros_like(isolate))

    monkeypatch.setattr(MultiRaftEngine, "run_rounds", run_rounds)
    cell = harness.Cell(root, CELL)
    _ctx, checks = harness.measure(cell, 12, 0.3, False,
                                   time.perf_counter(), require_tpu=False)
    bad = {c.name for c in checks if not c.ok}
    assert "window_without_sent_snapshot" in bad
    assert "sampled_replicas_state_differs_from_reference" in bad


def test_a_call_that_does_nothing_is_not_correct(root, monkeypatch):
    real_call = engine_faults.Driver.call

    def call(self):
        if self.calls == 3:
            self.calls += 1
            self.rounds_done += self.rpc
            return
        real_call(self)

    monkeypatch.setattr(engine_faults.Driver, "call", call)
    cell = harness.Cell(root, CELL)
    ctx, checks = harness.measure(cell, 4, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert ctx["raw"]["calls"] >= 2
    assert not verdict(checks)


@pytest.fixture(scope="module")
def driven(root):
    """The cell's driver after a tiny window, kept open for the
    controls."""
    cell = harness.Cell(root, CELL)
    seed = 2**31 + 77
    load = gen.make(cell.traffic, cell.config["sizes"], seed)
    driver = engine_faults.Driver(cell.config, cell.traffic, seed, "")
    driver.setup(load, gen)
    raw = gen.run(driver, load, cell.traffic, 0.3,
                  harness.Probe(False, 0.0, tempfile.gettempdir()))
    yield driver, load, raw
    driver.close()


def test_window_is_never_shorter_than_a_period(driven):
    driver, load, raw = driven
    assert raw["calls"] >= 2 and raw["rounds"] >= load["period_rounds"]
    assert raw["attempted"] == raw["rounds"] and raw["failed"] == 0
    assert raw["group_rounds_per_s"] > 0
    moved = {k: raw["telemetry"]["after"][k] - v
             for k, v in raw["telemetry"]["before"].items()}
    assert moved["elections_won"] > 0 and moved["sent_snapshot"] > 0
    assert 0 < raw["entries_committed"] <= (
        raw["rounds"] * driver.groups * load["proposals_per_round"])


def test_sound_reference_is_correct(driven):
    driver, load, raw = driven
    checks = driver.check(load, raw)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks)
    assert driver.rounds_done % load["period_rounds"] == 0
    assert driver.derailed == []


def test_the_healed_follower_replicates_once_load_stops(driven):
    """Under load the node that was away is carried by snapshots (the
    state ``correct`` compares holds a progress row in SNAPSHOT in
    every group); after the one quiet call no row is, and that call is
    off the schedule's timeline and run once however often ``check``
    is."""
    driver, load, raw = driven
    driver.check(load, raw)
    calls, final = driver.calls, driver.final
    r = driver.cfg.num_replicas
    loaded = final["state"]
    lead_rows = loaded["pr_state"][loaded["role"] == LEADER]
    assert lead_rows.shape == (driver.groups, r)
    assert ((lead_rows == 2).sum(axis=1) == 1).all()
    assert "replicas_not_caught_up_once_load_stops" in {
        c.name for c in driver.check(load, raw)}
    assert not verdict(quiet_checks(loaded, driver.groups, r))
    assert verdict(quiet_checks(final["quiet"], driver.groups, r))
    assert driver.final is final and driver.calls == calls


@pytest.mark.parametrize("control", engine_faults.CONTROLS)
def test_control_is_not_correct(driven, control):
    driver, load, raw = driven
    checks = driver.check(load, raw, control=control)
    assert not verdict(checks)
    bad = {c.name for c in checks if not c.ok}
    assert bad <= {"sampled_replicas_state_differs_from_reference",
                   "sampled_replicas_log_differs_from_reference"}
    assert "sampled_replicas_state_differs_from_reference" in bad
    # Not by the reference's crash alone (``control_faults.py`` counts
    # the same way): replicas of groups that stayed inside the protocol
    # differ too.
    differing = {c.name: c.value for c in checks}[
        "sampled_replicas_state_differs_from_reference"]
    assert differing > len(driver.derailed) * driver.cfg.num_replicas
    assert driver.derailed == []


def test_the_existing_control_script_names_the_first_control(driven):
    """``benchmark/control.py`` passes ``control=True``."""
    driver, load, raw = driven
    assert not verdict(driver.check(load, raw, control=True))
    with pytest.raises(ValueError):
        driver.check(load, raw, control="no_such_control")
