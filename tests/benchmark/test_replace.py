"""The replacement cell's own pieces on the CPU: the generator's schedule
from the seed, its refusals and what each node applies in a window, each
new comparison shown to fail on a fault handed to it, the classes'
derivation at R=4, the reference wrapper against the program's oracle
round for round, the readers, the cell's entries in ``BENCHMARK.json``
(appended after the cells that were there, nothing before them moved),
the cell's five per-layer entries (live since PR 36) with the cell each
lists and each metric read on a tiny run, and the cell driven tiny with
its timed path broken and under both controls. (The guide's share test
does not apply: nothing here is a share of a layer.)"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.compare import verdict
from benchmark.drivers import engine_replace
from benchmark.fault_checks import group_checks, schedule_classes
from benchmark.generators import engine_replace_rounds as gen
from benchmark.readers import reconf as reconf_reader
from benchmark.readers import replace as reader
from benchmark.readers import telemetry as telemetry_reader
from benchmark.replace_checks import (FRESH, empty_slot_checks, live_view,
                                      membership_checks, run_checks,
                                      window_checks)

from .test_contract import NAME
from .util import CELLS_AT_36, REPO, bench, own_entries, tiny_root

CELL = "engine512k-r3of4.replace-readindex"
SIZES = {"num_groups": 20, "num_replicas": 4}
R = 4


def traffic():
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "replace-readindex.json")) as f:
        return json.load(f)


def config():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "engine512k-r3of4.json")) as f:
        return json.load(f)


# -- the generator ------------------------------------------------------------------


def test_generator_is_deterministic_in_the_seed():
    a = gen.make(traffic(), SIZES, 2**31 + 5)
    b = gen.make(traffic(), SIZES, 2**31 + 5)
    c = gen.make(traffic(), SIZES, 2**31 + 6)
    assert (a["leader_slots"] == b["leader_slots"]).all()
    assert a["first_spare_node"] == b["first_spare_node"]
    assert (a["leader_slots"] != c["leader_slots"]).any()
    # Nobody leads from the spare: it holds nothing.
    spares = set()
    for seed in range(40):
        load = gen.make(traffic(), SIZES, seed)
        assert (load["leader_slots"] != load["first_spare_node"]).all()
        assert set(load["leader_slots"]) <= set(range(R))
        spares.add(load["first_spare_node"])
    assert spares == set(range(R))


def test_schedule_is_the_issues():
    t = traffic()
    assert (t["period_rounds"], t["add_learner_round"],
            t["transfer_from_round"], t["swap_round"],
            t["retire_from_round"], t["cut_from_round"], t["cut_rounds"],
            t["stall_from_cut_round"], t["leave_round"], t["wipe_round"]) == (
        128, 8, 24, 40, 72, 82, 6, 2, 96, 120)
    assert (t["proposals_per_round"], t["reads"], t["rounds_per_call"]) == (
        2, True, 64)
    load = gen.make(t, SIZES, 3)
    e0 = load["first_spare_node"]
    for k in range(5):
        e, d, n, m = gen.nodes(load, k)
        assert (e, d, n, m) == tuple((e0 + k + i) % R for i in range(4))
        at = lambda t_: gen.row(load, 128 * k + t_)  # noqa: E731
        first = None if k == 0 else (gen.LEAVE, None, None)
        assert at(0)["conf"] == first and at(7)["conf"] == first
        assert at(8)["conf"] == at(39)["conf"] == (gen.ADD_LEARNER, e, None)
        assert at(40)["conf"] == at(95)["conf"] == (gen.SWAP, e, d)
        assert at(96)["conf"] == at(127)["conf"] == (gen.LEAVE, None, None)
        assert at(23)["drained"] is None and at(96)["drained"] is None
        assert (at(24)["drained"], at(24)["transfer_to"]) == (d, n)
        assert (at(95)["drained"], at(95)["transfer_to"]) == (d, n)
        assert at(71)["retired"] is None
        assert at(72)["retired"] == at(127)["retired"] == d
        assert [at(t_)["cut"] for t_ in range(81, 89)] == (
            [None] + [n] * 6 + [None])
        assert [at(t_)["stall"] for t_ in range(81, 89)] == (
            [False] * 3 + [True] * 4 + [False])
        assert [at(t_)["wipe"] for t_ in (119, 120, 121)] == [None, d, None]
        assert all(at(t_)["reads"] for t_ in range(128))
    # The node wiped is the next period's spare.
    assert gen.nodes(load, 1)[0] == gen.nodes(load, 0)[1]
    assert gen.rows(load, 5, 3) == [gen.row(load, t_) for t_ in (5, 6, 7)]


@pytest.mark.parametrize("edit", [
    {"add_learner_round": 64}, {"swap_round": 64}, {"wipe_round": 64},
    {"cut_from_round": 58}, {"swap_round": 20}, {"wipe_round": 130},
    {"leave_round": 85}, {"period_rounds": 100},
], ids=lambda e: "-".join(f"{k}={v}" for k, v in e.items()))
def test_a_schedule_with_an_edge_out_of_place_is_refused(edit):
    with pytest.raises(ValueError):
        gen.make(dict(traffic(), **edit), SIZES, 1)


def test_three_replicas_have_no_spare():
    with pytest.raises(ValueError, match="spare"):
        gen.make(traffic(), {"num_groups": 8, "num_replicas": 3}, 1)


def test_what_each_node_applies_in_a_window():
    load = gen.make(traffic(), SIZES, 9)
    e, d, n, m = gen.nodes(load, 0)
    want = np.zeros(R, int)
    want[[n, m]], want[[e, d]] = 3, 2
    assert (gen.applies(load, 0, 128) == want).all()
    # The driver's window opens a call after the schedule begins.
    e1, d1, n1, m1 = gen.nodes(load, 1)
    want = np.zeros(R, int)
    want[[e, n, m]] += 1                      # period 0's LeaveJoint
    want[[d1, n1, m1]] += 1                   # period 1's learner
    want[[e1, d1, n1, m1]] += 1               # and its swap
    assert (gen.applies(load, 64, 192) == want).all()
    assert (gen.applies(load, 0, 256)
            == gen.applies(load, 0, 128) + gen.applies(load, 128, 256)).all()
    assert gen.applies(load, 0, 256).sum() == 2 * 10


# -- the comparisons, each handed its fault ------------------------------------------

G = 6


def home_state(d: int) -> dict:
    """A deployment of G groups at a period's end: the three nodes that
    are not d vote, d is fresh."""
    n = G * R
    slot = np.arange(n) % R
    live = np.arange(R) != d
    on_d = slot == d
    state = {
        "role": np.where(slot == (d + 1) % R, 2, 0),
        "term": np.where(on_d, 0, 3),
        "lead": np.where(on_d, 0, (d + 1) % R + 1),
        "commit": np.where(on_d, 0, 500),
        "last": np.where(on_d, 0, 504),
        "snap_index": np.where(on_d, 0, 484),
        "log_term": np.where(on_d[:, None], 0, 3) * np.ones((n, 32), int),
        "voter": np.where(on_d[:, None], False, live[None, :]),
        "voter_out": np.zeros((n, R), bool),
        "learner": np.zeros((n, R), bool),
        "learner_next": np.zeros((n, R), bool),
        "in_joint": np.zeros(n, bool),
        "read_index": np.where(on_d, -1, 498),
        "votes": np.where(on_d[:, None], -1, 1) * np.ones((n, R), int),
        "next": np.where(on_d[:, None], 1, 505) * np.ones((n, R), int),
        "election_elapsed": np.where(on_d, 7, 0),
        "read_req_latch": np.ones(n, bool),
        "randomized_timeout": 10 + ((np.arange(n) + 1) * 7919) % 10,
        "conf_index": np.zeros(n, int),
        "history": np.arange(n),
    }
    return state


@pytest.mark.parametrize("d", range(R))
def test_home_state_passes(d):
    state = home_state(d)
    checks = (group_checks(live_view(state, G, R, d), G, R - 1, 32)
              + membership_checks(state, G, R, d)
              + empty_slot_checks(state, G, R, d, 10, 7, True))
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks)
    # Handed the whole deployment, the empty slot reads as a replica
    # that lags and disagrees: why the view is taken.
    assert not verdict(group_checks(state, G, R, 32))


@pytest.mark.parametrize("field,row,col,value,name", [
    ("voter", 4, 2, True, "voters_are_not_the_live_nodes"),
    ("voter", 5, 3, False, "voters_are_not_the_live_nodes"),
    ("learner", 9, 1, True, "learner_or_a_joint"),
    ("voter_out", 9, 0, True, "learner_or_a_joint"),
    ("learner_next", 8, 0, True, "learner_or_a_joint"),
    ("in_joint", 11, None, True, "learner_or_a_joint"),
    ("term", 6, None, 2, "not_a_fresh_replica"),
    ("voter", 6, 0, True, "not_a_fresh_replica"),
    ("log_term", 6, 5, 1, "not_a_fresh_replica"),
    ("election_elapsed", 6, None, 8, "not_a_fresh_replica"),
    ("randomized_timeout", 6, None, 10, "not_a_fresh_replica"),
    ("next", 6, 2, 0, "not_a_fresh_replica"),
    ("conf_index", 6, None, 3, "not_a_fresh_replica"),
])
def test_a_fault_at_the_periods_end_is_not_correct(field, row, col, value,
                                                   name):
    d = 2
    state = home_state(d)
    if col is None:
        state[field][row] = value
    else:
        state[field][row, col] = value
    checks = (membership_checks(state, G, R, d)
              + empty_slot_checks(state, G, R, d, 10, 7, True))
    bad = [c.name for c in checks if not c.ok]
    assert len(bad) == 1 and name in bad[0], bad


def test_fresh_is_what_the_program_calls_empty():
    """``FRESH`` is stated in the yardstick; the program's
    ``empty_replica`` has to read the same, field for field."""
    import jax.numpy as jnp

    from etcd_tpu.batched import BatchedConfig
    from etcd_tpu.batched.state import empty_replica, init_state

    cfg = BatchedConfig(num_groups=2, num_replicas=4, window=32,
                        max_ents_per_msg=4, max_props_per_round=2,
                        conf_entries=True, replace_replicas=True)
    empty = empty_replica(cfg, init_state(cfg, start_index=5),
                          jnp.arange(cfg.num_instances))
    for f in empty._fields[:-1]:
        got = np.asarray(getattr(empty, f))
        if f == "randomized_timeout":
            assert (got == 10 + ((np.arange(8) + 1) * 7919) % 10).all()
        else:
            assert (got == FRESH.get(f, 0)).all(), f
    assert not any(np.asarray(x).any() for x in empty.conf)


def window_args(periods=2):
    applies = np.asarray([5, 4, 6, 5])
    return dict(
        commit_open=np.full(G, 100), commit_close=np.full(G, 600),
        reads_open=np.full(G, 40), reads_close=np.full(G, 200),
        applied_open=np.tile([3, 2, 2, 3], G),
        applied_close=np.tile([3, 2, 2, 3] + applies, G),
        applies=applies, snaps_open=np.full(G, 1),
        snaps_close=np.full(G, 1 + periods), periods=periods)


def test_sound_window_passes():
    checks = window_checks(**window_args())
    assert verdict(checks) and len(checks) == 5
    # Two snapshots a new replica are allowed, a third is not.
    args = window_args()
    args["snaps_close"] = np.full(G, 1 + 4)
    assert verdict(window_checks(**args))


@pytest.mark.parametrize("fault,name", [
    (lambda a: a["commit_close"].__setitem__(2, 100), "committed_nothing"),
    (lambda a: a["reads_close"].__setitem__(3, 40), "confirmed_no_read"),
    (lambda a: a["applied_close"].__setitem__(5, 9), "their_place_gives"),
    (lambda a: a["snaps_close"].__setitem__(1, 6), "more_than_two"),
    (lambda a: a.__setitem__("periods", 0), "no_whole_period"),
])
def test_window_fault_is_not_correct(fault, name):
    args = window_args()
    fault(args)
    bad = [c.name for c in window_checks(**args) if not c.ok]
    assert any(name in b for b in bad), bad


def sound_run(periods=3):
    watch = dict.fromkeys(
        ("reads_below_commit", "joint_commits_in_stall", "conf_marks_lost",
         "outsider_votes_or_campaigns", "swaps_before_ready"), 0)
    watch.update(joint_instance_rounds=900, swaps_taken=G * periods,
                 replicas_reset=G * periods, conf_restores=G * periods)
    counters = {"sent_timeout_now": 4, "elections_won": 10}
    return np.zeros(G * R, int), counters, watch


def test_sound_run_passes():
    checks = run_checks(*sound_run(), G, 3)
    assert verdict(checks) and len(checks) == 11
    inv, counters, watch = sound_run()
    watch["conf_restores"] += 2  # a second snapshot restores nothing new
    assert verdict(run_checks(inv, counters, watch, G, 3))


@pytest.mark.parametrize("where,key,value,name", [
    ("inv", 3, 1 << 8, "invariant_bit"),
    ("watch", "reads_below_commit", 1, "below_an_earlier_commit"),
    ("watch", "joint_commits_in_stall", 2, "through_the_cut"),
    ("watch", "conf_marks_lost", 1, "overwritten"),
    ("watch", "outsider_votes_or_campaigns", 1, "outside_its_configuration"),
    ("watch", "swaps_before_ready", 1, "level_in_replicate"),
    ("watch", "swaps_taken", G * 3 - 1, "swaps_taken_other_than"),
    ("watch", "swaps_taken", G * 3 + 1, "swaps_taken_other_than"),
    ("watch", "replicas_reset", 0, "replicas_reset_other_than"),
    ("watch", "conf_restores", G * 3 - 1, "gave_a_configuration"),
    ("watch", "joint_instance_rounds", 0, "without_a_round_in_a_joint"),
    ("counters", "sent_timeout_now", 0, "without_a_transfer_won"),
])
def test_run_fault_is_not_correct(where, key, value, name):
    inv, counters, watch = sound_run()
    {"inv": inv, "counters": counters, "watch": watch}[where][key] = value
    bad = [c.name for c in run_checks(inv, counters, watch, G, 3)
           if not c.ok]
    assert len(bad) == 1 and name in bad[0], bad


# -- the classes at R=4 -----------------------------------------------------------------


def test_classes_are_first_leader_and_g_mod_5():
    """``(iid + 1) * 7919 mod 10`` with iid = 4g + s is
    ``9 * (4g + s + 1) mod 10``: 4g mod 10 has period 5 in g, so the
    four residues of a group are fixed by g mod 5, and with three
    seated slots to lead from there are 15 classes."""
    groups = 400
    load = gen.make(traffic(), {"num_groups": groups, "num_replicas": R}, 11)
    slots = load["leader_slots"]
    classes = schedule_classes(slots, R, 10)
    assert len(np.unique(classes)) == 15
    g = np.arange(groups)
    by_hand = {}
    for i in range(groups):
        by_hand.setdefault((int(slots[i]), i % 5), set()).add(int(classes[i]))
    assert len(by_hand) == 15 and all(len(v) == 1 for v in by_hand.values())
    for s in range(R):
        res = ((4 * g + s + 1) * 7919) % 10
        assert (res == res[g % 5]).all()
        assert (res == (9 * (4 * (g % 5) + s + 1)) % 10).all()
    # At another timeout the residues fall otherwise: by the function,
    # not by 15.
    assert len(np.unique(schedule_classes(slots, R, 7))) == 3 * 7


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_the_sample_holds_one_group_of_each_class(seed):
    cfg = dict(config(), shadow_groups=15)
    cfg["sizes"] = dict(cfg["sizes"], num_groups=300)
    load = gen.make(traffic(), cfg["sizes"], seed)
    driver = engine_replace.Driver(cfg, traffic(), seed, "")
    sample = driver.sample(load)
    assert len(sample) == 15 == len(set(sample))
    assert len(set(driver.classes(load)[sample])) == 15


# -- the reference against the program's oracle, round for round -----------------------


@pytest.mark.parametrize("g", [0, 3])
def test_reference_wrapper_steps_like_the_programs_oracle(g):
    """The yardstick's ``ReplaceCluster`` (over the frozen copies) and
    the program's ``ShadowCluster`` (``spare``, ``replace``): two writings of the same emulation, equal after
    every round of two periods in state, membership, read state and
    log; and the reference's history is the engine's rule."""
    from benchmark.reference.raft.logger import DefaultLogger, set_logger
    from benchmark.reference.shadow_replace import ReplaceCluster
    from etcd_tpu.batched import engine as engine_mod
    from etcd_tpu.batched.shadow import ShadowCluster
    from etcd_tpu.batched.state import (CONF_ADD_LEARNER, CONF_LEAVE,
                                        CONF_SWAP, conf_code)

    set_logger(DefaultLogger(level=2))
    kinds = {gen.ADD_LEARNER: CONF_ADD_LEARNER, gen.SWAP: CONF_SWAP,
             gen.LEAVE: CONF_LEAVE}
    load = gen.make(traffic(), SIZES, 2**31 + 34)
    e0 = load["first_spare_node"]
    kw = dict(election_timeout=10, heartbeat_timeout=1, max_inflight=256,
              pre_vote=True, group=g, deterministic_timeouts=True)
    ref = ReplaceCluster(R, spare=e0, window=32, max_ents=4, max_props=2,
                         deliver_shape="vectorized", **kw)
    ora = ShadowCluster(R, check_quorum=True, auto_compact_window=32,
                        max_ents=4, max_props=2, spare=e0, replace=True,
                        **kw)
    lead = int(load["leader_slots"][g])
    ref.round(campaigns=[lead])
    ora.round(campaigns=[lead])
    for _ in range(16):
        ref.round(control=None)
        ora.round()
    history = [0] * R
    snapshots = 0
    for rnd in range(2 * 128):
        row = gen.row(load, rnd)
        away = [s for s in (row["cut"], row["retired"]) if s is not None]
        ref.round(offer=2, tick=True, isolate=away, control=row)
        c = row["conf"]
        code = 0 if c is None else conf_code(kinds[c[0]], c[1] or 0,
                                             c[2] or 0)
        ora.round(tick=True, offer=2, isolate=away, reads=True, conf=code,
                  drained=row["drained"], transfer_to=row["transfer_to"],
                  wipe=row["wipe"])
        assert ref.snapshot_state() == ora.snapshot_state(), rnd
        assert ref.membership() == ora.membership(), rnd
        assert ref.read_state() == ora.read_state(), rnd
        for s in range(R):
            assert ref.log_terms(s) == ora.log_terms(s), (rnd, s)
        bits = lambda ids: sum(1 << i for i in ids)  # noqa: E731
        for s, (st, mem, rd) in enumerate(zip(
                ora.snapshot_state(), ora.membership(), ora.read_state())):
            term, role, lead_, commit, last = st
            history[s] = engine_mod.history_fold(history[s], (
                term, role, lead_, commit, last, *rd, bool(mem[1]),
                bits(mem[0]), bits(mem[1]), bits(mem[2])))
        snapshots += sum(m is not None and m.type.name == "MsgSnap"
                         for t_ in ref.inbox for lanes in t_ for m in lanes)
    assert ref.history() == history
    assert snapshots == 2  # one a new replica
    assert sum(ref.conf_applied) == 2 * 10


# -- the readers -------------------------------------------------------------------------


def raw_of(swaps=16, snaps=16, short=96, rounds=256, groups=8):
    return {"raw": {
        "groups": groups, "replicas": 4, "rounds": rounds,
        "proposals_per_round": 2, "entries_committed": 3900,
        "telemetry": {"before": {"sent_snapshot": 8},
                      "after": {"sent_snapshot": 8 + snaps}},
        "watch": {
            "before": {"swaps_taken": 8,
                       "learner_rounds_short_of_replicate": 48,
                       "joint_instance_rounds": 1000},
            "after": {"swaps_taken": 8 + swaps,
                      "learner_rounds_short_of_replicate": 48 + short,
                      "joint_instance_rounds": 1000 + 2048}}}}


def test_readers():
    ctx = raw_of()
    assert reader.snapshots_per_swap(ctx) == 1.0
    assert reader.snapshots_per_swap(raw_of(snaps=400)) == 25.0
    assert reader.catchup_rounds(ctx) == 6.0
    assert reader.swapped_per_kgr(ctx) == pytest.approx(7.8125)
    assert reconf_reader.joint_pct(ctx) == pytest.approx(25.0)
    assert telemetry_reader.committed_pct(ctx) == pytest.approx(
        100 * 3900 / 4096)
    # No swap in the window: nothing to divide by, nothing reported.
    assert reader.snapshots_per_swap(raw_of(swaps=0)) is None
    assert reader.catchup_rounds(raw_of(swaps=0)) is None


def test_readers_find_nothing_in_another_drivers_run():
    """The parent program's scan counts no swaps, another driver reads
    no watch at all: the line leaves the metric out and nothing
    raises."""
    bare = {"raw": {"groups": 8, "replicas": 3, "rounds": 128}}
    older = {"raw": dict(bare["raw"], watch={
        "before": {"joint_instance_rounds": 0},
        "after": {"joint_instance_rounds": 5}},
        telemetry={"before": {"sent_snapshot": 0},
                   "after": {"sent_snapshot": 0}})}
    for ctx in (bare, older):
        for fn in (reader.snapshots_per_swap, reader.catchup_rounds,
                   reader.swapped_per_kgr):
            assert fn(ctx) is None


# -- the cell's entries: where they stand, which cell they list, each read tiny -------

FIVE = ["replace.snapshots_per_swap", "replace.catchup_rounds",
        "replace.joint_pct", "replace.committed_pct",
        "replace.swapped_per_kgr"]


def entries_rule(b: dict) -> None:
    """The five stand right after the 27 entries PR 32's file had, in
    their order, for this cell alone."""
    own_entries(b, FIVE, 27, CELL)


def test_the_five_are_live_with_exactly_these_workloads():
    entries_rule(bench())
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "parked", "engine512k-r3of4_layers.json"))


def follows_rule(b: dict) -> None:
    """The three additions ISSUE 34 names, each at the end of its list
    as PR 34 found it: the configuration, the cell, the cell's name
    under its end-to-end metric. Held in the order-relative form (this
    cell's entries come right after ``engine1m-r3``'s, which come after
    the three before them), so that the next cell appended after this
    one does not fail it (``test_reconf.py`` holds ``engine1m-r3``'s
    in the same form since PR 36, and shows it open and tight)."""
    was = ["engine64k-r3", "engine10k-r5", "engine100k-r3", "engine1m-r3"]
    cells = CELLS_AT_36[:4]
    assert [c["name"] for c in b["configs"]][:5] == was + [
        "engine512k-r3of4"]
    assert [w["name"] for w in b["workloads"]][:5] == cells + [CELL]
    rate, setup = b["end_to_end"][:2]
    assert (rate["name"], rate["bound"], rate["workloads"][:5]) == (
        "group_rounds_per_s", 0.01, cells + [CELL])
    assert setup == {"name": "setup_s", "unit": "s", "better": "lower",
                     "bound": 0.25, "source": "host_clock"}
    assert set(FIVE) <= {m["name"] for m in b["per_layer"]}
    assert b["run_seconds"] == 30


def test_the_cell_follows_the_cells_that_were_there():
    follows_rule(bench())
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert len(f.read()) < 64 << 10
    assert not os.path.exists(os.path.join(
        REPO, "benchmark", "parked", "engine512k-r3of4_cell.json"))


def test_the_cells_entries_pass_the_contracts_rules():
    """``test_contract.py``'s rules for a configuration, a cell and the
    files they resolve to, which reach a live cell from the data."""
    b = bench()
    cfg = [c for c in b["configs"] if c["name"] == "engine512k-r3of4"][0]
    w = [x for x in b["workloads"] if x["name"] == CELL][0]
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and NAME.match(w["name"])
    assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
    assert cfg["file"] == "benchmark/configs/engine512k-r3of4.json"
    assert [c["file"] for c in b["configs"]].count(cfg["file"]) == 1
    data = config()
    assert (data["name"], data["source"], data["reduced"]) == (
        cfg["name"], cfg["source"], cfg["reduced"])
    assert data["guarantees"] and data["reference"]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
    pairs = [(x["config"], x["traffic"]) for x in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len({x["name"] for x in b["workloads"]}) == len(b["workloads"])
    listing = [m["name"] for m in b["end_to_end"]
               if CELL in m.get("workloads", [])]
    assert listing == ["group_rounds_per_s"]
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert f"`{w['name']}`" in perf and f"`{w['config']}`" in perf


def test_the_cell_resolves_to_files_that_exist(root):
    c = harness.Cell(root, CELL)
    assert c.chips == 1
    assert c.module("drivers", c.config["driver"]).Driver
    g = c.module("generators", c.traffic["generator"])
    assert g.make and g.run and g.preload
    names = {m["name"] for m in c.end_to_end}
    assert names == {"group_rounds_per_s", "setup_s"}
    assert c.per_layer, "every cell reports a per-layer metric"
    for spec in c.per_layer:
        assert callable(c.reader(spec))


def test_the_cells_entries_are_the_issues():
    b = bench()
    entry = [c for c in b["configs"] if c["name"] == "engine512k-r3of4"][0]
    cell = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert cell == dict(cell, config="engine512k-r3of4",
                        traffic="replace-readindex", chips=1)
    assert entry["reduced"] == ["num_groups"]
    for word in ("confchange_v2_replace_leader.txt", "restore",
                 "server.go:80/1446", "BASELINE configs[4]"):
        assert word in entry["source"], word
    cfg = config()
    assert cfg["reduced"] == ["num_groups"] and "num_groups" in (
        cfg["reduced_why"])
    s = cfg["sizes"]
    assert (s["num_groups"], s["num_replicas"]) == (786_432, 4)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "engine1m-r3.json")) as f:
        old = json.load(f)
    # Everything else is engine1m-r3's, plus the new static field.
    assert {k: v for k, v in s.items() if k not in (
        "num_groups", "num_replicas", "replace_replicas")} == {
        k: v for k, v in old["sizes"].items() if k not in (
            "num_groups", "num_replicas")}
    assert s["replace_replicas"] is True
    assert cfg["guarantees"][:6] == old["guarantees"]
    assert len(cfg["guarantees"]) == 9
    assert set(cfg["assumed"]) >= {
        "learner_ready", "slot_reuse", "snapshot_catch_up", "lockstep",
        "replacement_cycle", "randomized_timeout"}


@pytest.fixture(scope="module")
def layer_run(root):
    cell = harness.Cell(root, CELL)
    ctx, checks = harness.measure(cell, 2**31 + 34, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert verdict(checks), [c for c in checks if not c.ok]
    return cell, ctx


def test_each_reader_on_a_tiny_run(layer_run):
    cell, ctx = layer_run
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values(layer)
    assert set(FIVE) <= set(layer)
    units = {m["name"]: m["unit"] for m in bench()["per_layer"]}
    for name in FIVE:
        assert layer[name]["unit"] == units[name]
        assert layer[name]["value"] > 0.0
    # One snapshot carried each new replica: catch-up works.
    assert layer["replace.snapshots_per_swap"]["value"] == 1.0
    assert 4.0 <= layer["replace.catchup_rounds"]["value"] <= 12.0
    assert layer["replace.swapped_per_kgr"]["value"] == pytest.approx(
        1e3 / 128)
    # Joint from the swap (about 42) to LeaveJoint (about 98) on three
    # or four of four slots.
    assert 25.0 < layer["replace.joint_pct"]["value"] < 50.0
    assert 80.0 < layer["replace.committed_pct"]["value"] < 100.0


# -- the cell driven tiny: the timed path broken, and the controls ------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("replace")))


def test_the_scan_sees_the_schedule(root, monkeypatch, capsys):
    """The timed path broken underneath: the engine is handed a control
    schedule that asks nothing, so nobody joins, nobody is retired and
    no slot is reset."""
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
    # How many periods the run held is the host's speed; after four the
    # node retired is the first spare again, and a schedule that asked
    # nothing left exactly the voters that cycle ends on.
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench:reference] ")][0]
    periods = json.loads(line.split(" ", 1)[1])["rounds"] // 128
    assert ("replicas_whose_voters_are_not_the_live_nodes" in bad) == bool(
        periods % R)
    assert {"wiped_slots_that_are_not_a_fresh_replica",
            "replicas_that_did_not_apply_the_changes_their_place_gives",
            "swaps_taken_other_than_one_a_group_a_period",
            "replicas_reset_other_than_one_a_group_a_period",
            "new_replicas_no_snapshot_gave_a_configuration",
            "run_without_a_round_in_a_joint_configuration",
            "sampled_replicas_state_differs_from_reference",
            "sampled_replicas_history_differs_from_reference"} <= bad


def test_a_snapshot_that_states_nothing_is_not_correct(root, monkeypatch):
    """The timed path broken where this PR mended it: the round's
    snapshot handler is the parent's, which takes the masks to be
    current. The new replica is restored into no configuration."""
    from etcd_tpu.batched import step

    real = step._handle_snapshot

    def parents(cfg, st, m, slot=None):
        return real(cfg._replace(replace_replicas=False), st, m, slot)

    monkeypatch.setattr(step, "_handle_snapshot", parents)
    step._step_round_jit.cache_clear()
    try:
        cell = harness.Cell(root, CELL)
        _ctx, checks = harness.measure(cell, 14, 0.3, False,
                                       time.perf_counter(),
                                       require_tpu=False)
    finally:
        step._step_round_jit.cache_clear()
    bad = {c.name for c in checks if not c.ok}
    assert "sampled_replicas_membership_differs_from_reference" in bad
    assert "sampled_replicas_history_differs_from_reference" in bad


def test_a_program_without_replicas_to_replace_fails_at_once(root,
                                                             monkeypatch):
    """The parent: its engine builds no spare slot. The driver says so
    before it builds anything."""
    from etcd_tpu.batched import MultiRaftEngine

    def init(self, cfg, start_index=0):
        raise AssertionError("not reached")

    monkeypatch.setattr(MultiRaftEngine, "__init__", init)
    cell = harness.Cell(root, CELL)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="spare slot"):
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
    driver = engine_replace.Driver(cell.config, cell.traffic, seed, "")
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
    assert moved["conf_changes_applied"] == 10 * driver.groups * (
        raw["periods"])
    assert moved["sent_snapshot"] == driver.groups * raw["periods"]
    assert moved["sent_timeout_now"] > 0 and moved["elections_won"] > 0
    watch = {k: raw["watch"]["after"][k] - v
             for k, v in raw["watch"]["before"].items()}
    assert watch["swaps_taken"] == driver.groups * raw["periods"]
    assert watch["replicas_reset"] == driver.groups * raw["periods"]
    assert watch["conf_restores"] == driver.groups * raw["periods"]
    for name in ("reads_below_commit", "joint_commits_in_stall",
                 "outsider_votes_or_campaigns", "swaps_before_ready"):
        assert watch[name] == 0, name


def test_sound_reference_is_correct(driven):
    driver, load, raw = driven
    checks = driver.check(load, raw)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks) and len(checks) == 32
    assert driver.rounds_done % load["period_rounds"] == 0
    assert driver.derailed == []
    calls, final = driver.calls, driver.final
    driver.check(load, raw)
    assert driver.final is final and driver.calls == calls


@pytest.mark.parametrize("control", engine_replace.CONTROLS)
def test_control_is_not_correct(driven, control):
    """Without its ConfState a snapshot carries nobody: the reference's
    new replica never joins, and state, log, masks and history differ.
    Commit on the incoming majority alone commits through the stalled
    rounds and is caught up with: only the history tells."""
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
    if control == engine_replace.CONTROLS[0]:
        assert "sampled_replicas_membership_differs_from_reference" in bad
    else:
        assert "sampled_replicas_membership_differs_from_reference" not in (
            bad)
    assert driver.derailed == []


def test_the_existing_control_script_names_the_first_control(driven):
    driver, load, raw = driven
    assert not verdict(driver.check(load, raw, control=True))
    with pytest.raises(ValueError):
        driver.check(load, raw, control="no_such_control")


def test_the_control_script_runs_the_cells_own_cases(root):
    """``benchmark/control_replace.py`` on the tiny copy: sound true,
    both controls false, nobody derailed, exit 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "control_replace.py"),
         "--workload", CELL, "--seed", str(2**31 + 3), "--seconds", "0.3",
         "--root", root, "--any-device"],
        capture_output=True, text=True, env=env, timeout=600)
    lines = [json.loads(ln.split(" ", 1)[1])
             for ln in out.stdout.splitlines()
             if ln.startswith("[control] ")]
    assert out.returncode == 0, out.stderr[-2000:]
    assert [ln["case"] for ln in lines] == ["sound"] + list(
        engine_replace.CONTROLS)
    assert [ln["correct"] for ln in lines] == [True, False, False]
    assert all(ln["derailed_groups"] == 0 for ln in lines)
    assert all(ln["in_protocol_replicas_differing"] > 0 for ln in lines[1:])
