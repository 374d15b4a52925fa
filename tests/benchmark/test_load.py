"""The load cell's own pieces on the CPU (ISSUE 47): the generator's
popularity table and thresholds and their rules, the plain reference's
draws against the program's bit for bit, each new comparison shown to
fail on a fault handed to it, the conservation law against the plain
reference's logs entry by entry, the readers, the cell's entries in
``BENCHMARK.json`` (appended after what was there), the six per-layer
entries with the cell each lists, and the cell driven tiny: sound,
broken on the program's side, on a program that takes no load plane,
and under both controls.

Round-step programs (``tests/batched/conftest.py``): none new. The tiny
cell is ``engine1m-r3``'s BatchedConfig at the CPU tests' 8 groups
(``test_scan_reconf.RC3``'s key).
"""

import json
import math
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.compare import verdict
from benchmark.drivers import engine_load
from benchmark.generators import engine_load_rounds as gen
from benchmark.load_checks import (conservation_checks, count_checks,
                                   run_checks, sampled_engine_checks,
                                   window_checks)
from benchmark.readers import load as reader
from benchmark.reference import shadow_load

from .test_contract import NAME, SOURCES, UNIT
from .util import (REPO, UNLISTED, bench, listed_cells, own_entries, reaches,
                   shared_with, tiny_root)

CONFIG = "engine1m-r3-zipf"
CELL = CONFIG + ".ycsb-a"
R, P = 3, 2
SIX = ["scan.load_pct", "load.active_pct", "load.committed_per_kgr",
       "load.committed_pct", "load.dropped_pct",
       "load.read_rounds_to_confirm"]


def load_json(kind, name):
    with open(os.path.join(REPO, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def traffic():
    return load_json("traffic", "ycsb-a")


def config():
    return load_json("configs", CONFIG)


def sizes(groups):
    return dict(config()["sizes"], num_groups=groups)


# -- the generator ------------------------------------------------------------------


def test_traffic_is_the_issues():
    assert traffic() == {
        "name": "ycsb-a", "generator": "engine_load_rounds",
        "loop": "open, in rounds", "ops_per_group_round": 0.125,
        "read_proportion": 0.5, "update_proportion": 0.5,
        "rounds_per_call": 64, "tick": True, "trace_calls": 1}
    load = gen.make(traffic(), config()["sizes"], 1)
    assert load["ops_per_round"] == 131072.0 and load["groups"] == 1 << 20
    assert load["proposals_per_round"] == P and load["period_rounds"] == 64


def test_fnv1a64_is_ycsbs_hash():
    def plain(val):
        h = 0xCBF29CE484222325
        for _ in range(8):
            h = ((h ^ (val & 0xFF)) * 0x100000001B3) % 2**64
            val >>= 8
        return h

    values = [0, 1, 255, 256, 2**26 - 1, 2**32 + 5, 2**63]
    assert gen.fnv1a64(np.asarray(values, np.uint64)).tolist() == [
        plain(v) for v in values]


@pytest.mark.parametrize("groups", [8, 4096])
def test_popularity_is_each_groups_share_and_sums_to_one(groups):
    pop = gen.popularity(groups, 64, 0.99)
    assert pop.shape == (groups,) and pop.dtype == np.float64
    assert abs(pop.sum() - 1.0) < 1e-12 and (pop > 0).all()
    # Record by record, in plain Python.
    records = groups * 64
    zeta = math.fsum((i + 1) ** -0.99 for i in range(records))
    want = [0.0] * groups
    hashed = gen.fnv1a64(np.arange(records, dtype=np.uint64)).tolist()
    for i in range(records if groups == 8 else 2000):
        want[hashed[i] % groups] += (i + 1) ** -0.99 / zeta
    if groups == 8:
        assert np.allclose(pop, want, rtol=1e-12)
    else:  # the head alone: every group holds at least that much
        assert (pop >= np.asarray(want) * (1 - 1e-12)).all()
    hot = hashed[0] % groups
    assert pop.argmax() == hot and pop[hot] >= 1 / zeta
    # Chunked or not, the same table.
    real, gen.CHUNK = gen.CHUNK, 100
    try:
        assert np.allclose(gen.popularity(groups, 2, 0.99),
                           _unchunked(groups, 2), rtol=1e-12)
    finally:
        gen.CHUNK = real


def _unchunked(groups, per_group):
    rank = np.arange(groups * per_group, dtype=np.uint64)
    w = (rank + np.uint64(1)).astype(np.float64) ** -0.99
    out = np.bincount((gen.fnv1a64(rank) % np.uint64(groups)).astype(int),
                      weights=w, minlength=groups)
    return out / out.sum()


def test_make_is_from_the_seed_and_the_table_is_not():
    a = gen.make(traffic(), sizes(4000), 2**31 + 5)
    b = gen.make(traffic(), sizes(4000), 2**31 + 5)
    c = gen.make(traffic(), sizes(4000), 6)
    assert (a["leader_slots"] == b["leader_slots"]).all()
    assert (a["leader_slots"] != c["leader_slots"]).any()
    assert set(np.unique(a["leader_slots"])) == {0, 1, 2}
    assert a["draw_seed"] == 2**31 + 5 and c["draw_seed"] == 6
    assert gen.make(traffic(), sizes(8), 2**32 + 3)["draw_seed"] == 3
    assert a["ops_per_round"] == 500.0


@pytest.mark.parametrize("edit", [
    dict(read_proportion=0.6), dict(update_proportion=-0.5,
                                    read_proportion=1.5),
    dict(ops_per_group_round=0)])
def test_a_mix_out_of_its_rules_is_refused(edit):
    with pytest.raises(ValueError):
        gen.make(dict(traffic(), **edit), sizes(8), 1)


def test_thresholds_are_the_arrival_law():
    load = gen.make(traffic(), sizes(8), 1)  # one operation a round
    pop = np.asarray([0.0, 1e-12, 0.1, 0.5, 1, 2, 4, 8000]) / 1.0
    load["ops_per_round"] = 2.0  # lambda_u = lambda_r = pop
    upd, rd = gen.thresholds(load, pop)
    assert upd.dtype == rd.dtype == np.uint32
    lam_u, lam_r = gen.lambdas(load, pop)
    assert (lam_u == pop).all() and (lam_r == pop).all()
    assert upd.tolist() == [
        0, 0, int(0.05 * 2**32), 2**30, 2**31, gen.ALWAYS, gen.ALWAYS,
        gen.ALWAYS]
    want = [int((1 - math.exp(-x)) * 2**32) for x in pop[:7]]
    assert abs(np.asarray(rd[:7].tolist()) - np.asarray(want)).max() <= 1
    assert rd[7] == gen.ALWAYS and rd[0] == 0
    said = gen.summary(load, pop)
    assert said["saturated_groups"] == 3
    assert said["groups_above_half_capacity"] == 4
    assert said["over_capacity_share"] == pytest.approx(
        (2 + 7998) / pop.sum())
    assert said["hottest_group_share"] == 8000.0


def test_at_the_cells_size_the_table_is_the_issues():
    """The numbers ISSUE 47 reckoned, from the table a run builds (4 s
    here): about 1,766 groups at P updates in every round, 35-36% of
    the update demand above capacity, the hottest group 4.9% of all
    operations, 6.4-6.7% of the groups active in a round."""
    load = gen.make(traffic(), config()["sizes"], 7)
    pop = gen.popularity(1 << 20, 64, 0.99)
    said = gen.summary(load, pop)
    assert 1700 <= said["saturated_groups"] <= 1800
    assert 3500 <= said["groups_above_half_capacity"] <= 3700
    assert 0.35 <= said["over_capacity_share"] <= 0.36
    assert 0.049 <= said["hottest_group_share"] <= 0.0495
    assert 6.4 <= said["groups_active_pct"] <= 6.7
    assert 3.2e-7 <= said["median_group_share"] <= 3.5e-7
    upd, rd = gen.thresholds(load, pop)
    assert int((upd == gen.ALWAYS).sum()) == said["saturated_groups"]
    assert (upd > 0).all() and (rd > 0).all()


# -- the plain reference's draws -------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 47, 2**31 + 42, 2**32 - 1])
def test_the_references_draws_are_the_programs_bit_for_bit(seed):
    """The yardstick imports nothing of the program; this test holds
    its copy of the rule to the program's, word by word."""
    import jax.numpy as jnp

    from etcd_tpu.batched import engine as program

    assert shadow_load.ALWAYS == program.LOAD_ALWAYS == gen.ALWAYS
    groups = np.concatenate([np.arange(40), [65535, 65536, 1048575]])
    keys = shadow_load.stream_keys(groups, P + 1)
    dev_key = jnp.asarray(groups, jnp.uint32) * jnp.uint32(
        program.LOAD_GROUP_MUL)
    for t in (0, 1, 64, 831, 2**20 + 3):
        base = shadow_load.round_base(seed, t)
        dev_base = program.load_base(jnp.uint32(seed), jnp.int32(t))
        assert int(base) == int(dev_base)
        for k in range(P + 1):
            got = shadow_load.fmix32(keys[k] ^ base)
            assert (got == np.asarray(program.load_word(dev_base, dev_key,
                                                        k))).all()


def test_replay_adds_up_what_group_offers_says_round_by_round():
    rng = np.random.default_rng(3)
    upd = rng.integers(0, 2**32, 16, dtype=np.uint64).astype(np.uint32)
    rd = rng.integers(0, 2**32, 16, dtype=np.uint64).astype(np.uint32)
    upd[:2], rd[:2] = (gen.ALWAYS, 0), (0, gen.ALWAYS)
    offered, totals = shadow_load.replay(upd, rd, 9, 64, 200, P)
    by_group = [shadow_load.group_offers(upd, rd, 9, g, 64, 200, P)
                for g in range(16)]
    assert offered.tolist() == [sum(ns) for ns, _ in by_group]
    assert offered[0] == 200 * P and offered[1] == 0
    assert totals["offered"] == int(offered.sum())
    assert totals["reads_asked"] == sum(sum(rs) for _, rs in by_group)
    assert totals["active"] == sum(
        sum(1 for n, r in zip(ns, rs) if n or r) for ns, rs in by_group)
    assert sum(by_group[1][1]) == 200 and not any(by_group[0][1])
    # A round late: round t draws what round t - 1 drew.
    late, _ = shadow_load.group_offers(upd, rd, 9, 5, 65, 199, P, shift=1)
    assert late == by_group[5][0][:199]


def test_a_read_asked_while_a_batch_is_in_flight_waits():
    """The one departure from ``ReconfCluster``: asked in one round and
    not in the next, a read still opens the batch after the one in
    flight, as the device's latch does."""
    def stepped(cls_round):
        sh = shadow_load.LoadCluster(
            R, window=32, max_ents=4, max_props=2, election_timeout=10,
            heartbeat_timeout=1, max_inflight=256, pre_vote=True, group=0,
            deterministic_timeouts=True, deliver_shape="vectorized")
        sh.round(campaigns=[1])
        for _ in range(8):
            sh.load_round(0, False, tick=False)
        for read in (True, True, False, False, False, False):
            cls_round(sh, read)
        return sh.read_state()[1]

    kept = stepped(lambda sh, read: sh.load_round(1, read, True))
    # Two batches: the second asked while the first was in flight.
    assert kept[0] == 2 and kept[2] is True
    assert shadow_load.LoadCluster._read is not (
        shadow_load.ReconfCluster._read)


# -- the comparisons, each handed its fault ------------------------------------------


def quiet_state(groups=4):
    """After a closing call: every replica level and committed."""
    lead = np.arange(groups) % R
    last = np.repeat(np.asarray([9, 3, 40, 17])[:groups], R)
    role = np.zeros(groups * R, np.int64)
    role[np.arange(groups) * R + lead] = 2
    return ({"last": last.copy(), "commit": last.copy(), "role": role},
            lead)


def conserved(state, lead, **over):
    kw = dict(last_before=np.asarray([3, 3, 4, 3]),
              offered_ref=np.asarray([6, 0, 40, 14]),
              dropped=np.asarray([12, 0, 84, 28]),
              won=np.zeros(4, np.int64))
    kw.update(over)
    # Group 2: its leader refused 4 of 40 (R x 40 - 84 = 36 appended).
    return conservation_checks(state, 4, R, leader_slots=lead, **kw)


def test_a_sound_quiet_state_conserves_what_was_offered():
    state, lead = quiet_state()
    checks = conserved(state, lead)
    assert verdict(checks) and len(checks) == 5
    assert all(c.limit == 0 for c in checks)


@pytest.mark.parametrize("fault, name", [
    ("lost", "groups_whose_log_grew_by_less_than_was_offered_and_taken"),
    ("duplicated",
     "groups_whose_log_grew_by_more_than_was_offered_and_taken"),
    ("dropped_and_not_counted",
     "groups_whose_log_grew_by_less_than_was_offered_and_taken"),
    ("replica_short", "replicas_short_of_their_groups_log_once_load_stops"),
    ("uncommitted", "replicas_with_entries_uncommitted_once_load_stops"),
    ("quiet_group_appended",
     "groups_offered_nothing_that_appended_or_lost_their_leader"),
    ("quiet_group_lost_its_leader",
     "groups_offered_nothing_that_appended_or_lost_their_leader"),
    ("election_not_counted",
     "groups_whose_log_grew_by_more_than_was_offered_and_taken"),
])
def test_a_fault_in_the_conservation_is_not_correct(fault, name):
    state, lead = quiet_state()
    over = {}
    if fault == "lost":  # every replica one entry short
        state["last"][0:3] -= 1
        state["commit"][0:3] -= 1
    elif fault == "duplicated":
        state["last"][9:12] += 1
        state["commit"][9:12] += 1
    elif fault == "dropped_and_not_counted":
        over["dropped"] = np.asarray([12, 0, 83, 28])
    elif fault == "replica_short":
        state["last"][4] -= 1
        state["commit"][4] -= 1
    elif fault == "uncommitted":
        state["commit"][7] -= 1
    elif fault == "quiet_group_appended":
        state["last"][3:6] += 1
        state["commit"][3:6] += 1
    elif fault == "quiet_group_lost_its_leader":
        state["role"][3:6] = [2, 0, 0]
    else:  # a new leader's empty entry nobody counted
        state["last"][0:3] += 1
        state["commit"][0:3] += 1
    bad = {c.name for c in conserved(state, lead, **over) if not c.ok}
    assert name in bad
    if fault == "election_not_counted":
        assert verdict(conserved(state, lead, won=np.asarray([1, 0, 0, 0])))


def test_counts_run_and_window_checks_and_their_faults():
    counts = {"offered": 10, "reads_asked": 7, "active": 12}
    assert verdict(count_checks(counts, dict(counts)))
    for name in counts:
        bad = count_checks(counts, dict(counts, **{name: counts[name] - 1}))
        assert [c.name for c in bad if not c.ok] == [
            f"load_count_{name}_differs_from_the_replay"]
    inv = np.zeros(12, np.int32)
    moved = {"sent_snapshot": 0, "elections_started": 0}
    watch = {"reads_below_commit": 0, "joint_instance_rounds": 0}
    assert verdict(run_checks(inv, moved, watch))
    for where, key, name in [
            (moved, "sent_snapshot", "snapshots_sent_in_the_run"),
            (moved, "elections_started", "elections_started_in_the_run"),
            (watch, "reads_below_commit",
             "reads_confirmed_below_an_earlier_commit_of_the_group"),
            (watch, "joint_instance_rounds", "run_in_a_joint_configuration")]:
        m, w = dict(moved), dict(watch)
        (m if where is moved else w)[key] = 1
        assert [c.name for c in run_checks(inv, m, w) if not c.ok] == [name]
    inv[5] = 4
    assert not verdict(run_checks(inv, moved, watch))
    assert verdict(window_checks(5, 4, 3, 2))
    for i in range(4):
        args = [5, 4, 3, 2]
        args[i] = 0
        assert not verdict(window_checks(*args))


def test_the_sample_is_held_to_the_reference_on_its_own_rows():
    g_n, w = 6, 8
    state = {"term": np.full(g_n * R, 2), "role": np.tile([2, 0, 0], g_n),
             "lead": np.full(g_n * R, 1), "commit": np.full(g_n * R, 3),
             "last": np.full(g_n * R, 3),
             "snap_index": np.zeros(g_n * R, np.int64),
             "log_term": np.full((g_n * R, w), 2)}
    ref_state = lambda g: [(2, r, 1, 3, 3) for r in (2, 0, 0)]  # noqa: E731
    ref_log = lambda g, s: [(1, 2), (2, 2), (3, 2)]  # noqa: E731
    checks = sampled_engine_checks(state, R, w, [1, 4], ref_state, ref_log)
    assert verdict(checks)
    assert "groups_unequal_within_leader_class" not in {
        c.name for c in checks}
    state["commit"][4 * R + 1] = 2  # a sampled replica; group 3 is not
    state["commit"][3 * R] = 1
    bad = sampled_engine_checks(state, R, w, [1, 4], ref_state, ref_log)
    assert {c.name: c.value for c in bad if not c.ok} == {
        "sampled_replicas_state_differs_from_reference": 1}


# -- the readers ---------------------------------------------------------------------------


def test_readers():
    raw = {"groups": 1000, "rounds": 100, "replicas": 3,
           "entries_committed": 4100,
           "load": {"offered": 4000, "reads_asked": 3000, "active": 6500,
                    "dropped": 8040, "unoffered_committed": 2,
                    "uncommitted_open": 138}}
    ctx = {"raw": raw, "trace": None}
    assert reader.active_pct(ctx) == 6.5
    assert reader.committed_per_kgr(ctx) == 41.0
    assert reader.committed_pct(ctx) == 100.0 * (4100 - 2 - 138) / 4000
    assert reader.dropped_pct(ctx) == 1.0
    assert reader.load_pct(ctx) is None
    red = {"leaf_s": 2.0, "scope_s": {"raft_load": 0.03, "raft_tick": 1.0}}
    assert reader.load_pct(dict(ctx, trace=red)) == 1.5
    # A parent's trace has no such scope, another cell's run no counts.
    assert reader.load_pct(dict(ctx, trace={
        "leaf_s": 2.0, "scope_s": {"raft_tick": 1.0}})) is None
    other = {"raw": {"groups": 8, "rounds": 64, "entries_committed": 5},
             "trace": None}
    for fn in (reader.active_pct, reader.committed_per_kgr,
               reader.committed_pct, reader.dropped_pct):
        assert fn(other) is None
    assert reader.committed_pct({"raw": dict(
        raw, load=dict(raw["load"], offered=0)), "trace": None}) is None


# -- the cell's entries ----------------------------------------------------------------


def entries_rule(b: dict) -> None:
    """The cell's six stand right after the 54 entries PR 46's file
    had, in their order, for this cell alone; what follows them is a
    later PR's."""
    for m in own_entries(b, SIX, 54, CELL):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES
        assert m["moves"] == "group_rounds_per_s"


def test_the_six_are_appended_for_this_cell_alone():
    b = bench()
    entries_rule(b)
    assert listed_cells(SIX) == {name: [CELL] for name in SIX}
    got = {m["name"]: (m["unit"], m["better"], m["source"], m["layer"])
           for m in b["per_layer"][54:60]}
    assert got == {
        "scan.load_pct": ("%", "lower", "device_trace", "closed-loop engine"),
        "load.active_pct": ("%", "lower", "program_counter",
                            "closed-loop engine"),
        "load.committed_per_kgr": ("per_kgr", "higher", "program_counter",
                                   "telemetry plane"),
        "load.committed_pct": ("%", "higher", "program_counter",
                               "telemetry plane"),
        "load.dropped_pct": ("%", "lower", "program_counter",
                             "telemetry plane"),
        "load.read_rounds_to_confirm": ("rounds", "lower", "program_counter",
                                        "closed-loop engine")}
    assert load_json("layer_metrics", SIX[5])["reader"] == (
        "reconf.rounds_to_confirm")


def test_the_cell_reports_what_names_no_cells_its_six_and_the_shared():
    """At least the eleven that name no cells, by name; its own six;
    and of the other entries with a list only shared ones, which the
    rule of ``test_lists.py`` holds to what this cell's run gives."""
    b = bench()
    mine = {s["name"] for s in harness.Cell(REPO, CELL).per_layer}
    unlisted = {m["name"] for m in b["per_layer"] if "workloads" not in m}
    assert UNLISTED <= unlisted <= mine == reaches(b, CELL)
    assert mine >= unlisted | set(SIX) | shared_with(b, CELL)
    assert {"round.route_pct", "route.roofline_pct", "round.lanes_run",
            "scan.tiles_pct", "read.rounds_to_confirm",
            "round.rare_pct"} <= shared_with(b, CELL)
    assert "round.bulk_pct" not in mine  # the append lane is not split


def follows_rule(b: dict) -> None:
    """By rule, not by position from the end: the configuration, the
    cell and its name under the rate come after everything PR 46's
    file had, in its order."""
    before = ["engine64k-r3", "engine10k-r5", "engine100k-r3", "engine1m-r3",
              "engine512k-r3of4", "engine1m-r3of4-x4",
              "engine768k-r3of4-rebalance"]
    names = [c["name"] for c in b["configs"]]
    assert names[:7] == before and names.index(CONFIG) == 7
    cells = [w["name"] for w in b["workloads"]]
    assert [w["config"] for w in b["workloads"]][:7] == before
    assert cells.index(CELL) == 7 and cells.count(CELL) == 1
    rate = b["end_to_end"][0]
    assert (rate["name"], rate["bound"]) == ("group_rounds_per_s", 0.01)
    assert rate["workloads"][:8] == cells[:8]
    assert [w["name"] for w in b["workloads"] if w["chips"] == 4] == [
        "engine1m-r3of4-x4.replace-readindex-x4"]
    assert b["run_seconds"] == 30


def test_the_cell_follows_what_was_there():
    follows_rule(bench())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 << 10


def test_the_entries_are_the_issues():
    b = bench()
    entry = [c for c in b["configs"] if c["name"] == CONFIG][0]
    cell = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert cell == dict(cell, config=CONFIG, traffic="ycsb-a", chips=1)
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert entry["reduced"] == []
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] == (
        "YCSB core workloads/workloada (50% read, 50% update, "
        "requestdistribution=zipfian, constant 0.99, scrambled); scale "
        "BASELINE configs[4] 1M-shard, uncut; etcd raftConfig "
        "bootstrap.go:523-536")
    assert len(entry["source"]) == 188 and 1 <= len(entry["why"]) <= 200
    assert 1 <= len(cell["why"]) <= 200
    cfg = config()
    assert (cfg["name"], cfg["source"], cfg["reduced"]) == (
        CONFIG, entry["source"], [])
    assert (cfg["driver"], cfg["reference"], cfg["shadow_groups"]) == (
        "engine_load", "engine_shadow_load", 30)
    old = load_json("configs", "engine1m-r3")
    # The sizes are the lockstep cell's to the digit: the two cells
    # differ in who is offered what.
    assert cfg["sizes"] == old["sizes"]
    assert cfg["guarantees"][:3] == [old["guarantees"][i] for i in (0, 1, 4)]
    assert len(cfg["guarantees"]) == 6
    assert cfg["dataset"]["records_per_group"] == 64
    assert cfg["dataset"]["zipfian_constant"] == 0.99
    assert "fnv1a64" in cfg["dataset"]["sharding"]
    assert set(cfg["assumed"]) >= {
        "arrivals", "draws", "sharding", "records_per_group",
        "static_popularity", "ycsb_scrambled", "reads", "window_ents_props",
        "round"}
    for key in ("window_ents_props", "round", "randomized_timeout"):
        assert cfg["assumed"][key] == old["assumed"][key]
    # The constants the configuration states are the reference's.
    for word in ("0x9E3779B1", "0x85EBCA77", "0xC2B2AE3D", "0x85EBCA6B",
                 "0xC2B2AE35", "0xFFFFFFFF"):
        assert word in cfg["assumed"]["draws"], word
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert f"`{CELL}`" in perf and f"`{CONFIG}`" in perf


def test_the_reference_imports_nothing_of_the_program():
    """Nothing but what ``shadow_reconf.py`` already does: numpy, typing
    and the frozen files beside it."""
    with open(os.path.join(REPO, "benchmark", "reference",
                           "shadow_load.py")) as f:
        imports = [ln.strip() for ln in f if ln.startswith(("import ",
                                                            "from "))]
    assert imports == [
        "from __future__ import annotations",
        "from typing import Dict, Tuple",
        "import numpy as np",
        "from .shadow_reconf import ReconfCluster"]


# -- the cell driven tiny ----------------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark cut to 8 groups: 512 records, an operation a
    round."""
    return tiny_root(str(tmp_path_factory.mktemp("load")))


def test_the_cell_resolves_to_files_that_exist(root):
    c = harness.Cell(root, CELL)
    assert c.chips == 1
    assert c.module("drivers", c.config["driver"]).Driver
    g = c.module("generators", c.traffic["generator"])
    assert g.make and g.run and g.preload
    assert {m["name"] for m in c.end_to_end} == {
        "group_rounds_per_s", "setup_s"}
    for spec in c.per_layer:
        assert callable(c.reader(spec))


@pytest.fixture(scope="module")
def layer_run(root):
    cell = harness.Cell(root, CELL)
    ctx, checks = harness.measure(cell, 2**31 + 42, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks) and len(checks) == 29
    return cell, ctx


def test_each_reader_on_a_tiny_run(layer_run):
    cell, ctx = layer_run
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values(layer)
    assert set(SIX[1:]) <= set(layer) and "scan.load_pct" not in layer
    units = {m["name"]: m["unit"] for m in bench()["per_layer"]}
    for name in SIX[1:]:
        assert layer[name]["unit"] == units[name]
    raw = ctx["raw"]
    load = gen.make(cell.traffic, cell.config["sizes"], 2**31 + 42)
    pop = gen.popularity(8, 64, 0.99)
    upd, rd = gen.thresholds(load, pop)
    # The window opens a settle call and a warm-up call in.
    _, want = shadow_load.replay(upd, rd, load["draw_seed"], 128,
                                 raw["rounds"], P)
    assert {k: raw["load"][k] for k in want} == want
    assert layer["load.active_pct"]["value"] == pytest.approx(
        100 * want["active"] / (8 * raw["rounds"]))
    assert layer["load.dropped_pct"]["value"] == 0.0
    assert 99.0 < layer["load.committed_pct"]["value"] <= 100.0
    assert layer["load.committed_per_kgr"]["value"] == pytest.approx(
        1e3 * raw["entries_committed"] / (8 * raw["rounds"]))
    assert 1.9 < layer["load.read_rounds_to_confirm"]["value"] < 2.5
    assert raw["attempted"] == raw["rounds"] and raw["failed"] == 0


@pytest.fixture(scope="module")
def driven(root):
    """The cell's driver after a tiny window, kept open for the
    controls."""
    cell = harness.Cell(root, CELL)
    seed = 2**31 + 77
    load = gen.make(cell.traffic, cell.config["sizes"], seed)
    driver = engine_load.Driver(cell.config, cell.traffic, seed, "")
    driver.setup(load, gen)
    raw = gen.run(driver, load, cell.traffic, 0.3,
                  harness.Probe(False, 0.0, tempfile.gettempdir()))
    yield driver, load, raw
    driver.close()


def test_sound_reference_is_correct_and_the_engine_kept_the_round(driven):
    driver, load, raw = driven
    checks = driver.check(load, raw)
    assert verdict(checks), [c for c in checks if not c.ok]
    eng = driver.eng
    assert eng.load_round == (driver.settle_rounds + driver.rounds_done
                              + driver.quiet_rounds)
    assert driver.settle_rounds == driver.quiet_rounds == 64
    calls, final = driver.calls, driver.final
    driver.check(load, raw)
    assert driver.final is final and driver.calls == calls
    assert eng.load_round == 128 + driver.rounds_done
    # By popularity: the two hottest, one about the median, the coldest.
    sample = driver.sample(load)
    order = np.argsort(-driver.popularity, kind="stable").tolist()
    assert sample == sorted(order[:2] + [order[4]] + order[7:])


@pytest.mark.parametrize("groups, n", [(1 << 20, 30), (4000, 30), (8, 8)])
def test_the_sample_is_drawn_by_popularity_at_any_size(groups, n):
    cfg = dict(config(), shadow_groups=n)
    cfg["sizes"] = sizes(groups)
    driver = engine_load.Driver(cfg, traffic(), 1, "")
    rng = np.random.default_rng(groups)
    driver.popularity = rng.permutation(
        np.arange(1, groups + 1, dtype=np.float64) ** -0.99)
    sample = driver.sample(None)
    assert len(sample) == len(set(sample)) == min(n, groups)
    rank = np.argsort(np.argsort(-driver.popularity)).tolist()
    ranks = sorted(rank[g] for g in sample)
    k = min(n, groups) // 3
    assert ranks[:len(ranks) - 2 * k] == list(range(len(ranks) - 2 * k))
    assert ranks[-k:] == list(range(groups - k, groups))
    if groups > n:
        mid = ranks[-2 * k:-k]
        assert mid == list(range(mid[0], mid[0] + k))
        assert abs(mid[0] + k / 2 - groups / 2) <= 1


def test_the_conservation_law_is_what_the_references_logs_hold(driven):
    """Entry by entry: every group followed by the plain reference on
    its own offers. Each reference log grew since the load began by
    exactly the entries the replay says the group was offered (nothing
    was dropped here), the same entries on every replica, all
    committed; the device's rows hold the same."""
    driver, load, raw = driven
    cfg = driver.cfg
    if driver.final is None:
        driver.check(load, raw)
    state = driver.final["state"]
    every = list(range(driver.groups))
    ref = driver.reference(load, every)
    offered, _ = shadow_load.replay(
        *driver.thr, load["draw_seed"], driver.settle_rounds,
        driver.rounds_done, cfg.max_props_per_round)
    assert offered.sum() > 0
    assert not driver.final["proposals_dropped"].any() or (
        driver.final["proposals_dropped"] == (R - 1) * offered).all()
    for g in every:
        logs = [ref[g].log_terms(s) for s in range(R)]
        states = ref[g].snapshot_state()
        assert len({st[4] for st in states}) == 1
        for s in range(R):
            term, _role, _lead, commit, last = states[s]
            assert commit == last
            assert last - int(driver.before["last"][g]) == int(offered[g])
            # Entries above the ring's floor, consecutive, one term.
            assert [i for i, _t in logs[s]] == list(
                range(last - len(logs[s]) + 1, last + 1))
            i = g * R + s
            assert int(state["last"][i]) == last
            assert int(state["commit"][i]) == commit


@pytest.mark.parametrize("control", engine_load.CONTROLS)
def test_control_is_not_correct(driven, control):
    """The draws a round late: the sampled replicas' histories differ.
    Uniform popularity: the conservation law fails too, for most
    groups."""
    driver, load, raw = driven
    checks = driver.check(load, raw, control=control)
    assert not verdict(checks)
    bad = {c.name: c.value for c in checks if not c.ok}
    assert "sampled_replicas_history_differs_from_reference" in bad
    if control == engine_load.CONTROLS[1]:
        assert (bad.get(
            "groups_whose_log_grew_by_more_than_was_offered_and_taken", 0)
            + bad.get(
            "groups_whose_log_grew_by_less_than_was_offered_and_taken", 0)
        ) >= driver.groups // 2
        assert "load_count_offered_differs_from_the_replay" in bad
    assert driver.derailed == []


def test_the_existing_control_script_names_the_first_control(driven):
    driver, load, raw = driven
    assert not verdict(driver.check(load, raw, control=True))
    with pytest.raises(ValueError):
        driver.check(load, raw, control="no_such_control")


def test_a_program_without_a_load_plane_fails_at_once(root, monkeypatch):
    """The parent: its ``run_rounds`` takes no ``load``. The driver
    says so as it is made, before the generator or anything else is
    built."""
    from etcd_tpu.batched import MultiRaftEngine

    def run_rounds(self, rounds, tick=True, propose_n=None, isolate=None,
                   control=None, starts=None):
        raise AssertionError("not reached")

    def init(self, cfg, start_index=0, spare=None, nodes=None):
        raise AssertionError("not reached")

    def popularity(*a):
        raise AssertionError("not reached")

    monkeypatch.setattr(MultiRaftEngine, "run_rounds", run_rounds)
    monkeypatch.setattr(MultiRaftEngine, "__init__", init)
    monkeypatch.setattr(gen, "popularity", popularity)
    cell = harness.Cell(root, CELL)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="takes no load plane"):
        harness.measure(cell, 4, 0.3, False, time.perf_counter(),
                        require_tpu=False)
    assert time.perf_counter() - t0 < 5


@pytest.mark.parametrize("how", ["another_stream", "a_read_dropped",
                                 "an_update_more"])
def test_the_timed_path_broken_is_not_correct(root, monkeypatch, how):
    """The program's side broken: its draws made with another constant
    (every group is offered something else than the reference replays),
    a hot group's reads not asked, a group offered an update more than
    its draws say."""
    from etcd_tpu.batched import MultiRaftEngine
    from etcd_tpu.batched import engine as program

    if how == "another_stream":
        monkeypatch.setattr(program, "LOAD_STREAM_MUL", 0xC2B2AE3F)
    else:
        real = MultiRaftEngine.run_rounds

        def run_rounds(self, rounds, tick=True, load=None, **kw):
            upd, rd, seed = load
            if upd.any() and how == "a_read_dropped":
                rd = np.where(rd == rd.max(), 0, rd).astype(np.uint32)
            elif upd.any():
                upd = upd.copy()
                upd[upd.argmin()] = 0xFFFFFFFF
            real(self, rounds, tick=tick, load=(upd, rd, seed), **kw)

        monkeypatch.setattr(MultiRaftEngine, "run_rounds", run_rounds)
    cell = harness.Cell(root, CELL)
    _ctx, checks = harness.measure(cell, 12, 0.3, False,
                                   time.perf_counter(), require_tpu=False)
    bad = {c.name for c in checks if not c.ok}
    assert "sampled_replicas_history_differs_from_reference" in bad
    if how == "a_read_dropped":
        assert "load_count_reads_asked_differs_from_the_replay" in bad
        assert not any("log_grew" in name for name in bad)
    else:
        assert "load_count_offered_differs_from_the_replay" in bad
        assert "groups_whose_log_grew_by_more_than_was_offered_and_taken" \
            in bad


def test_the_control_script_runs_the_cells_own_cases(root):
    """``benchmark/control_load.py`` on the tiny copy: sound true, both
    controls false, nobody derailed, exit 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "control_load.py"),
         "--workload", CELL, "--seed", str(2**31 + 3), "--seconds", "0.3",
         "--root", root, "--any-device"],
        capture_output=True, text=True, env=env, timeout=600)
    lines = [json.loads(ln.split(" ", 1)[1])
             for ln in out.stdout.splitlines()
             if ln.startswith("[control] ")]
    assert out.returncode == 0, out.stderr[-2000:]
    assert [ln["case"] for ln in lines] == ["sound"] + list(
        engine_load.CONTROLS)
    assert [ln["correct"] for ln in lines] == [True, False, False]
    assert all(ln["derailed_groups"] == 0 for ln in lines)
    assert all(ln["in_protocol_replicas_differing"] > 0 for ln in lines[1:])
