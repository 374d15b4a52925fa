"""The trickle cell's own pieces on the CPU (ISSUE 42): the generator's
starts from the seed and its rules, the cycle against the lockstep
file's, each new comparison shown to fail on a fault handed to it, the
reference on one group's own rows against the lockstep reference on the
shifted schedule, the readers, the cell's entries in ``BENCHMARK.json``
(appended after what was there), the six per-layer entries with the
cell each lists, and the cell driven tiny: sound, with its timed path
broken, on a program that takes no phased schedule, and under both
controls.

Round-step programs (``tests/batched/conftest.py``): none new. The tiny
cell is ``engine512k-r3of4``'s BatchedConfig at the CPU tests' 8 groups
(``test_scan_replace.RP4``'s key).
"""

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
from benchmark.drivers import engine_trickle
from benchmark.generators import engine_replace_rounds as lockstep_gen
from benchmark.generators import engine_trickle_rounds as gen
from benchmark.readers import trickle as reader
from benchmark.trickle_checks import (fresh_slot_checks, membership_checks,
                                      move_checks, resting_checks,
                                      run_checks, window_checks)

from .test_contract import NAME, SOURCES, UNIT
from .util import (REPO, SHARED_AT_52, UNLISTED, apply_tiny, bench,
                   cell_root, listed_cells, own_entries, reaches,
                   shared_with, tiny_root)

CONFIG = "engine768k-r3of4-rebalance"
CELL = CONFIG + ".trickle-readindex"
SIZES = {"num_groups": 4000, "num_replicas": 4}
R = 4
SIX = ["trickle.moves_per_kgr", "trickle.in_motion_pct",
       "trickle.catchup_rounds", "trickle.snapshots_per_move",
       "trickle.committed_pct", "scan.phase_pct"]


def load_json(kind, name):
    with open(os.path.join(REPO, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


def traffic():
    return load_json("traffic", "trickle-readindex")


def config():
    return load_json("configs", CONFIG)


# -- the generator ------------------------------------------------------------------


def test_traffic_is_the_issues():
    t = traffic()
    assert t == {
        "name": "trickle-readindex", "generator": "engine_trickle_rounds",
        "loop": "closed", "proposals_per_round": 2, "reads": True,
        "rounds_per_call": 64, "tick": True, "trace_calls": 1,
        "cycle_rounds": 128, "add_learner_round": 8,
        "transfer_from_round": 24, "swap_round": 40,
        "retire_from_round": 72, "cut_rounds": 0, "leave_round": 96,
        "wipe_round": 120, "batch_every_rounds": 16, "batch_groups": 48,
        "schedule_rounds": 8192}
    # The cycle is the lockstep file's, edge for edge, without the cut.
    old = load_json("traffic", "replace-readindex")
    for edge in gen.EDGES:
        assert t[edge] == old[edge], edge
    assert t["cycle_rounds"] == old["period_rounds"]
    for key in ("proposals_per_round", "reads", "rounds_per_call", "tick"):
        assert t[key] == old[key]


def test_starts_are_from_the_seed_and_no_group_is_drawn_twice():
    a = gen.make(traffic(), SIZES, 2**31 + 5)
    b = gen.make(traffic(), SIZES, 2**31 + 5)
    c = gen.make(traffic(), SIZES, 2**31 + 6)
    assert (a["starts"] == b["starts"]).all()
    assert (a["leader_slots"] == b["leader_slots"]).all()
    assert (a["starts"] != c["starts"]).any()
    drawn = np.concatenate(a["batches"])
    assert len(set(drawn.tolist())) == len(drawn)
    # Batch size and stagger as the traffic file says; half the groups
    # at most are ever scheduled.
    assert all(len(x) == 48 for x in a["batches"])
    assert len(a["batches"]) == SIZES["num_groups"] // 2 // 48 == 41
    for i, batch in enumerate(a["batches"]):
        assert (a["starts"][batch] == 16 * i).all()
    never = np.setdiff1d(np.arange(SIZES["num_groups"]), drawn)
    assert (a["starts"][never] == gen.NEVER).all()
    assert gen.NEVER == np.iinfo(np.int32).max
    assert a["batches_in_flight"] == 8
    # Nobody leads from the empty slot, whatever the seed.
    spares = set()
    for seed in range(40):
        load = gen.make(traffic(), SIZES, seed)
        assert (load["leader_slots"] != load["first_spare_node"]).all()
        spares.add(load["first_spare_node"])
    assert spares == set(range(R))


def test_at_the_cells_size_the_schedule_runs_far_past_any_window():
    sizes = config()["sizes"]
    load = gen.make(traffic(), sizes, 2**31 + 9)
    assert len(load["batches"]) == 512
    assert int((load["starts"] != gen.NEVER).sum()) == 512 * 48
    assert load["starts"][load["starts"] != gen.NEVER].max() == 8192 - 16
    # Drawn over the whole id range: every tile of the chip (16 of
    # 49,152 groups) holds movers of the first eight batches.
    first = np.concatenate(load["batches"][:8])
    assert len(set((first // 49_152).tolist())) == 16


def test_every_counted_edge_of_every_batch_is_off_a_calls_first_round():
    t = traffic()
    load = gen.make(t, SIZES, 3)
    rpc, every = t["rounds_per_call"], t["batch_every_rounds"]
    for edge in gen.EDGES:
        if edge == "leave_round":
            continue
        assert t[edge] % 16 == 8, edge
        for i in range(len(load["batches"])):
            assert (i * every + t[edge]) % rpc
    # The lockstep file's 96 is kept, and one batch in four meets it
    # on a call's first round (the configuration's `assumed` says so).
    assert t["leave_round"] % 16 == 0
    assert [(i * every + t["leave_round"]) % rpc == 0
            for i in range(4)] == [False, False, True, False]


@pytest.mark.parametrize("edit", [
    {"add_learner_round": 64}, {"swap_round": 48}, {"wipe_round": 112},
    {"swap_round": 20}, {"wipe_round": 130}, {"cycle_rounds": 100},
    {"cut_rounds": 6}, {"batch_every_rounds": 24}, {"batch_groups": 0},
    {"batch_groups": 2001},
], ids=lambda e: "-".join(f"{k}={v}" for k, v in e.items()))
def test_a_schedule_out_of_its_rules_is_refused(edit):
    with pytest.raises(ValueError):
        gen.make(dict(traffic(), **edit), SIZES, 1)


def test_three_replicas_have_no_empty_slot():
    with pytest.raises(ValueError, match="empty slot"):
        gen.make(traffic(), {"num_groups": 400, "num_replicas": 3}, 1)


def test_a_groups_cycle_is_the_lockstep_cells_without_the_cut():
    """Round k of a move against round k of the lockstep generator's
    first period on the same nodes: equal but for the node-wide cut
    (and its stall mark), which a group's move does not have."""
    load = gen.make(traffic(), SIZES, 11)
    old = lockstep_gen.make(load_json("traffic", "replace-readindex"),
                            SIZES, 11)
    old["first_spare_node"] = load["first_spare_node"]
    assert gen.nodes(load) == lockstep_gen.nodes(old, 0)
    for k in range(128):
        want = dict(lockstep_gen.row(old, k), cut=None, stall=False)
        assert gen.row(load, k) == want, k
    steady = gen.row(load, -1)
    assert steady == gen.row(load, 128) == gen.row(load, -gen.NEVER)
    assert steady == {"drained": None, "transfer_to": None, "conf": None,
                      "cut": None, "retired": None, "wipe": None,
                      "stall": False, "reads": True}
    assert gen.cycle(load) == [gen.row(load, k) for k in range(128)]


def test_moves_counts_the_edges_that_fell_in_the_run():
    load = gen.make(traffic(), SIZES, 5)
    assert gen.moves(load, "wipe_round", 120) == 0
    assert gen.moves(load, "wipe_round", 121) == 48
    assert gen.moves(load, "wipe_round", 256) == 48 * 9
    assert gen.moves(load, "swap_round", 128) == 48 * 6
    assert gen.moves(load, "swap_round", 128, slack=7) == 48 * 6
    # What the driver counts when a run ends on a multiple of 128: the
    # batch that stands at round 48 of its cycle has been offered its
    # swap for eight rounds and has taken it; the next has not been
    # offered it. (The chip's first runs, PR 42, read 48 apart here.)
    assert gen.moves(load, "swap_round", 512, slack=7) == 48 * 30
    starts = np.unique(load["starts"])[:-1]
    assert (512 - starts[29], 512 - starts[30]) == (48, 32)
    assert gen.moves(load, "swap_round", 128, slack=8) == 48 * 5


# -- the comparisons, each handed its fault ------------------------------------------

G = 6
E, D = 1, 2
K = np.asarray([-50, 3, 200, 130, 60, -10**9])  # a group's own round


def resting_state() -> dict:
    """Six groups when a run ends: two not started and one whose
    learner is not on offer yet hold {d, n, m}, slot e fresh; two done
    hold {n, m, e}, slot d fresh; one in the middle of its move."""
    n = G * R
    slot = np.arange(n) % R
    k = np.repeat(K, R)
    empty = np.where(k >= 128, D, E)
    moving = (k >= 8) & (k < 128)
    on_empty = (slot == empty) & ~moving
    lead = np.where(k >= 128, 3, 0)
    voter = (np.arange(R)[None, :] != empty[:, None]) & ~on_empty[:, None]
    return {
        "role": np.where((slot == lead) & ~on_empty, 2, 0),
        "term": np.where(on_empty, 0, 3),
        "lead": np.where(on_empty, 0, lead + 1),
        "commit": np.where(on_empty, 0, 500),
        "last": np.where(on_empty, 0, 504),
        "snap_index": np.where(on_empty, 0, 484),
        "log_term": np.where(on_empty[:, None], 0, 3) * np.ones((n, 32), int),
        "voter": voter,
        "voter_out": np.zeros((n, R), bool),
        "learner": np.zeros((n, R), bool),
        "learner_next": np.zeros((n, R), bool),
        "in_joint": np.zeros(n, bool),
        "read_index": np.where(on_empty, -1, 498),
        "votes": np.where(on_empty[:, None], -1, 1) * np.ones((n, R), int),
        "next": np.where(on_empty[:, None], 1, 505) * np.ones((n, R), int),
        "election_elapsed": np.where(
            on_empty, np.where(k >= 128, k - 121, 700), 0),
        "read_req_latch": np.ones(n, bool),
        "randomized_timeout": 10 + ((np.arange(n) + 1) * 7919) % 10,
        "conf_index": np.zeros(n, int),
        "history": np.arange(n),
    }


def state_checks(state):
    done, waiting = np.flatnonzero(K >= 128), np.flatnonzero(K < 0)
    return (resting_checks(state, K, E, D, R, 32, 128, 8)
            + membership_checks(state, K, E, D, R, 128, 8, 40)
            + fresh_slot_checks(state, done, D, R, 10, K[done] - 121, True,
                                "slots_reset")
            + fresh_slot_checks(state, waiting, E, R, 10,
                                np.full(len(waiting), 700), True,
                                "empty_slots"))


def test_resting_state_passes():
    checks = state_checks(resting_state())
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks) and len(checks) == 10


@pytest.mark.parametrize("field,row,col,value,name", [
    ("voter", 8, D, True, "finished_move_whose_voters"),
    ("voter", 12, 3, False, "finished_move_whose_voters"),
    ("learner", 8, 1, True, "finished_move_whose_voters"),
    ("voter", 0, E, True, "not_started_whose_voters"),
    ("voter", 4, D, False, "not_started_whose_voters"),
    ("voter_out", 20, 0, True, "not_started_whose_voters"),
    ("in_joint", 5, None, True, "outside_their_own_cycles"),
    ("in_joint", 13, None, True, "outside_their_own_cycles"),
    ("term", 10, None, 2, "slots_reset"),
    ("election_elapsed", 14, None, 3, "slots_reset"),
    ("randomized_timeout", 10, None, 10, "slots_reset"),
    ("log_term", 1, 5, 1, "empty_slots"),
    ("election_elapsed", 21, None, 699, "empty_slots"),
    ("role", 2, None, 2, "exactly_one_leader"),
    ("term", 15, None, 4, "disagreeing_on_term"),
])
def test_a_fault_in_the_resting_state_is_not_correct(field, row, col, value,
                                                     name):
    state = resting_state()
    if col is None:
        state[field][row] = value
    else:
        state[field][row, col] = value
    bad = [c.name for c in state_checks(state) if not c.ok]
    assert any(name in b for b in bad), bad


def test_a_group_in_the_middle_of_its_move_is_held_to_none_of_them():
    """Group 4 (round 60 of its cycle: joint, a learner just swapped
    in, a leadership moved) may read anything here: it is held to its
    class and to the reference."""
    state = resting_state()
    rows = slice(4 * R, 5 * R)
    state["in_joint"][rows] = True
    state["voter_out"][rows] = True
    state["term"][rows] = [5, 6, 7, 8]
    state["role"][rows] = 2
    assert verdict(state_checks(state))


def sound_moves():
    k = np.asarray([-50, 3, 200, 130, 60, 12, 16, -10**9])
    snaps = np.asarray([0, 0, 1, 1, 1, 0, 1, 0])
    applied = np.zeros(len(k) * R, int)
    applied[2 * R:5 * R] = [3, 2, 2, 3] * 2 + [2, 1, 2, 2]
    watch = {"swaps_taken": 3, "replicas_reset": 2, "conf_restores": 3}
    return dict(k=k, snaps=snaps, applied=applied, watch=watch,
                swaps_due=3, resets_due=2, num_replicas=R,
                add_learner_round=8, slack=7)


def test_sound_moves_pass():
    checks = move_checks(**sound_moves())
    assert verdict(checks) and len(checks) == 5
    # A learner four rounds on offer may or may not have its snapshot.
    args = sound_moves()
    args["snaps"][5] = 1
    assert verdict(move_checks(**args))


@pytest.mark.parametrize("fault,name", [
    (lambda a: a["snaps"].__setitem__(2, 2), "other_than_one_snapshot"),
    (lambda a: a["snaps"].__setitem__(6, 0), "other_than_one_snapshot"),
    (lambda a: a["snaps"].__setitem__(0, 1), "other_than_one_snapshot"),
    (lambda a: a["snaps"].__setitem__(5, 2), "other_than_one_snapshot"),
    (lambda a: a["applied"].__setitem__(1, 1), "has_not_started"),
    (lambda a: a["watch"].__setitem__("swaps_taken", 2), "swaps_taken"),
    (lambda a: a["watch"].__setitem__("swaps_taken", 4), "swaps_taken"),
    (lambda a: a["watch"].__setitem__("replicas_reset", 3),
     "replicas_reset"),
    (lambda a: a["watch"].__setitem__("conf_restores", 2),
     "gave_a_configuration"),
])
def test_a_fault_in_the_moves_is_not_correct(fault, name):
    args = sound_moves()
    fault(args)
    bad = [c.name for c in move_checks(**args) if not c.ok]
    assert len(bad) == 1 and name in bad[0], bad


def sound_run():
    watch = dict.fromkeys(
        ("reads_below_commit", "joint_commits_in_stall", "conf_marks_lost",
         "outsider_votes_or_campaigns", "swaps_before_ready"), 0)
    watch["joint_instance_rounds"] = 900
    return (np.zeros(G * R, int),
            {"sent_timeout_now": 4, "elections_won": 10}, watch)


def test_sound_run_and_window_pass():
    assert verdict(run_checks(*sound_run()))
    assert len(run_checks(*sound_run())) == 8
    full = np.full(G, 9)
    assert verdict(window_checks(full, full + 1, full, full + 1, 2))


@pytest.mark.parametrize("where,key,value,name", [
    ("inv", 3, 1 << 8, "invariant_bit"),
    ("watch", "reads_below_commit", 1, "below_an_earlier_commit"),
    ("watch", "joint_commits_in_stall", 2, "stalled_round"),
    ("watch", "conf_marks_lost", 1, "overwritten"),
    ("watch", "outsider_votes_or_campaigns", 1, "outside_its_configuration"),
    ("watch", "swaps_before_ready", 1, "level_in_replicate"),
    ("watch", "joint_instance_rounds", 0, "without_a_round_in_a_joint"),
    ("counters", "sent_timeout_now", 0, "without_a_transfer_won"),
])
def test_run_fault_is_not_correct(where, key, value, name):
    inv, counters, watch = sound_run()
    {"inv": inv, "counters": counters, "watch": watch}[where][key] = value
    bad = [c.name for c in run_checks(inv, counters, watch) if not c.ok]
    assert len(bad) == 1 and name in bad[0], bad


@pytest.mark.parametrize("fault,name", [
    (dict(commit_close=np.full(G, 9)), "committed_nothing"),
    (dict(reads_close=np.full(G, 9)), "confirmed_no_read"),
    (dict(cycles=0), "no_whole_cycle"),
])
def test_window_fault_is_not_correct(fault, name):
    full = np.full(G, 9)
    args = dict(commit_open=full, commit_close=full + 1, reads_open=full,
                reads_close=full + 1, cycles=1)
    bad = [c.name for c in window_checks(**dict(args, **fault)) if not c.ok]
    assert len(bad) == 1 and name in bad[0], bad


# -- the reference: one group on its own rows -------------------------------------------


@pytest.mark.parametrize("start", [0, 48, gen.NEVER])
def test_the_reference_on_its_own_rows_is_the_lockstep_reference_shifted(
        start):
    """``TrickleCluster`` knows its start and the generator's ``row``;
    ``ReplaceCluster`` handed, round by round, the row of the cycle
    shifted by that start is the same machine: equal after every round
    in state, membership, read state, log and history."""
    from benchmark.reference.raft.logger import DefaultLogger, set_logger
    from benchmark.reference.shadow_replace import ReplaceCluster
    from benchmark.reference.shadow_trickle import TrickleCluster

    set_logger(DefaultLogger(level=2))
    load = gen.make(traffic(), SIZES, 2**31 + 42)
    g = 7
    kw = dict(spare=load["first_spare_node"], window=32, max_ents=4,
              max_props=2, election_timeout=10, heartbeat_timeout=1,
              max_inflight=256, pre_vote=True, group=g,
              deterministic_timeouts=True, deliver_shape="vectorized")
    own = TrickleCluster(R, start=start, row=gen.row, load=load, **kw)
    ref = ReplaceCluster(R, **kw)
    lead = int(load["leader_slots"][g])
    for sh in (own, ref):
        sh.round(campaigns=[lead])
        for _ in range(16):
            sh.round(control=None)
    for rnd in range(48 + 128 + 16):
        own.schedule_round(2, True)
        row = gen.row(load, rnd - start)
        ref.round(offer=2, tick=True,
                  isolate=[] if row["retired"] is None else [row["retired"]],
                  control=row)
        assert own.snapshot_state() == ref.snapshot_state(), rnd
        assert own.membership() == ref.membership(), rnd
        assert own.read_state() == ref.read_state(), rnd
        assert own.history() == ref.history(), rnd
    for s in range(R):
        assert own.log_terms(s) == ref.log_terms(s)
    assert own.rounds == 48 + 128 + 16
    assert sum(own.conf_applied) == (0 if start == gen.NEVER else 10)


# -- the readers -------------------------------------------------------------------------


def test_readers():
    ctx = {"raw": {"groups": 1000, "entries_committed": 255_040, "trickle": {
        "in_motion_open": 7, "in_motion_close": 9, "offered": 256_000,
        "unoffered_committed": 40}}}
    assert reader.in_motion_pct(ctx) == pytest.approx(0.8)
    assert reader.committed_pct(ctx) == pytest.approx(100 * 255 / 256)
    # Nothing is cut off at 100: a count that came out too high reads
    # too high, and the harness refuses the run.
    over = {"raw": dict(ctx["raw"], entries_committed=256_050)}
    assert reader.committed_pct(over) > 100.0
    with pytest.raises(harness.BenchmarkError, match="reads 100.0"):
        harness.refuse_bad_values({"trickle.committed_pct": {
            "value": reader.committed_pct(over), "unit": "%"}})
    # Another driver's run, the parent program's, an untraced run: the
    # line leaves the metric out and nothing raises.
    bare = {"raw": {"groups": 8, "replicas": 3, "rounds": 128}}
    assert reader.in_motion_pct(bare) is None
    assert reader.committed_pct(bare) is None
    assert reader.committed_pct({"raw": dict(bare, entries_committed=5)}
                                ) is None
    assert reader.phase_pct(bare) is None
    assert reader.phase_pct(dict(bare, trace=None)) is None
    parents = dict(bare, trace={"scope_s": {"raft_carry": 1.0},
                                "leaf_s": 1.0, "modules": {}})
    assert reader.phase_pct(parents) is None


def test_the_phase_share_is_a_share_of_the_traced_rounds(capsys):
    from benchmark.reduce.trace import scope_share_pct

    red = {"scope_s": {"raft_phase": 0.5, "raft_carry": 1.5,
                       "raft_route": 2.0},
           "leaf_s": 4.0, "modules": {}}
    want = scope_share_pct(red, "raft_phase")
    ctx = {"raw": {"groups": 8, "replicas": 4, "rounds": 128},
           "trace": red, "traffic": traffic(), "config": config(),
           "device": {"kind": "TPU v5 lite"}}
    assert reader.phase_pct(ctx) == want == pytest.approx(12.5)
    # A reader reads and says nothing.
    assert capsys.readouterr().out == ""


# -- the cell's entries ----------------------------------------------------------------


def entries_rule(b: dict) -> None:
    """The cell's six stand right after the 48 entries PR 40's file
    had, in their order, for this cell alone; what follows them is a
    later PR's."""
    for m in own_entries(b, SIX, 48, CELL):
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES
        assert m["moves"] == "group_rounds_per_s"


def test_the_six_are_appended_for_this_cell_alone():
    b = bench()
    entries_rule(b)
    assert listed_cells(SIX) == {name: [CELL] for name in SIX}
    got = {m["name"]: (m["unit"], m["better"], m["source"])
           for m in b["per_layer"][48:54]}
    assert got == {
        "trickle.moves_per_kgr": ("per_kgr", "higher", "program_counter"),
        "trickle.in_motion_pct": ("%", "lower", "program_counter"),
        "trickle.catchup_rounds": ("rounds", "lower", "program_counter"),
        "trickle.snapshots_per_move": ("per_move", "lower",
                                       "program_counter"),
        "trickle.committed_pct": ("%", "higher", "program_counter"),
        "scan.phase_pct": ("%", "lower", "device_trace")}


def test_the_entries_of_shared_layers_list_this_cell():
    """Until PR 52 eighteen accepted entries listed the five cells of
    PR 36 and this cell's driver said what they read here on a line of
    its own; now each lists the cell (``test_lists.py`` holds every one
    to what the cell's run gives), the line and the driver's list are
    gone, and every entry without a list reaches the cell by itself."""
    b = bench()
    assert not hasattr(engine_trickle, "LISTED_ELSEWHERE")
    assert not hasattr(engine_trickle.Driver, "layers_elsewhere")
    mine = {s["name"] for s in harness.Cell(REPO, CELL).per_layer}
    unlisted = {m["name"] for m in b["per_layer"] if "workloads" not in m}
    assert UNLISTED <= unlisted <= mine == reaches(b, CELL)
    # (At least: a later PR may bring one more view of this cell.)
    assert mine >= unlisted | set(SIX) | shared_with(b, CELL)
    assert set(SHARED_AT_52[:18]) | {
        "round.rare_pct", "emit.ring_pct"} <= shared_with(b, CELL)
    assert "round.bulk_pct" not in mine  # the append lane is not split


def follows_rule(b: dict) -> None:
    """By rule, not by position from the end (as ``test_load.py``'s):
    the configuration, the cell and its name under the rate come after
    everything PR 40's file had, in its order."""
    before = ["engine64k-r3", "engine10k-r5", "engine100k-r3", "engine1m-r3",
              "engine512k-r3of4", "engine1m-r3of4-x4"]
    names = [c["name"] for c in b["configs"]]
    assert names[:6] == before and names.index(CONFIG) == 6
    assert names.count(CONFIG) == 1
    cells = [w["name"] for w in b["workloads"]]
    assert [w["config"] for w in b["workloads"]][:6] == before
    assert cells.index(CELL) == 6 and cells.count(CELL) == 1
    rate = b["end_to_end"][0]
    assert (rate["name"], rate["bound"]) == ("group_rounds_per_s", 0.01)
    assert rate["workloads"][:7] == cells[:7]
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
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell == dict(cell, config=CONFIG, traffic="trickle-readindex",
                        chips=1)
    assert entry["reduced"] == ["num_groups"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert 1 <= len(entry["source"]) <= 200 and 1 <= len(entry["why"]) <= 200
    assert 1 <= len(cell["why"]) <= 200
    for word in ("maxInFlightMsgSnap=16", "isLearnerReady", "raft/confchange",
                 "confchange_v2_replace_leader.txt", "BASELINE configs[4]"):
        assert word in entry["source"], word
    cfg = config()
    assert (cfg["name"], cfg["source"], cfg["reduced"]) == (
        CONFIG, entry["source"], ["num_groups"])
    assert (cfg["driver"], cfg["reference"]) == (
        "engine_trickle", "engine_shadow_trickle")
    old = load_json("configs", "engine512k-r3of4")
    # The sizes are the lockstep cell's to the digit: the two cells
    # differ by the schedule's shape alone.
    assert cfg["sizes"] == old["sizes"]
    assert cfg["reduced_why"] == old["reduced_why"]
    assert cfg["guarantees"][:9] == old["guarantees"]
    assert len(cfg["guarantees"]) == 11
    assert cfg["rebalance"] == {"snapshots_in_flight_per_member": 16,
                                "sending_members": 3}
    assert traffic()["batch_groups"] == 16 * 3
    assert set(cfg["assumed"]) >= {
        "move_cycle", "batch_cadence", "batch_drawn_by_seed",
        "sources_not_checked", "slot_reuse", "learner_ready",
        "randomized_timeout", "shadow_groups"}
    for key in ("slot_reuse", "learner_ready", "randomized_timeout"):
        assert cfg["assumed"][key] == old["assumed"][key]
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert f"`{CELL}`" in perf and f"`{CONFIG}`" in perf


# -- the cell driven tiny ----------------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark cut to 8 groups, and the trickle to a group a
    batch (four batches, 16 rounds apart; four groups never started)
    under a budget of one snapshot: the cell's own cuts, from its file
    under ``tiny/``."""
    return cell_root(str(tmp_path_factory.mktemp("trickle")), CELL)


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
def root24(tmp_path_factory):
    """``root`` at 24 groups: twelve batches of a group, so that moves
    begin, swap and end inside a window that opens a whole cycle in."""
    dst = tiny_root(str(tmp_path_factory.mktemp("trickle24")), groups=24)
    apply_tiny(dst, CELL)
    return dst


@pytest.fixture(scope="module")
def layer_run(root24):
    cell = harness.Cell(root24, CELL)
    ctx, checks = harness.measure(cell, 2**31 + 42, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks) and len(checks) == 34
    return cell, ctx


def test_each_reader_on_a_tiny_run(layer_run):
    cell, ctx = layer_run
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values(layer)
    assert set(SIX[:5]) <= set(layer) and "scan.phase_pct" not in layer
    units = {m["name"]: m["unit"] for m in bench()["per_layer"]}
    for name in SIX[:5]:
        assert layer[name]["unit"] == units[name]
    raw = ctx["raw"]
    # Twelve moves of 24 groups, begun at rounds 0, 16, ... 176. The
    # window opens a whole cycle in, at round 128, on the steady state:
    # the eight begun at 0 ... 112 have their learner and seven of them
    # their old slot still (the first was reset at 120).
    load = gen.make(cell.traffic, cell.config["sizes"], 2**31 + 42)
    opened, closed = 128, 128 + raw["rounds"]
    assert (load["starts"] != gen.NEVER).sum() == 12

    def fell(edge, slack=0):
        return (gen.moves(load, edge, closed, slack)
                - gen.moves(load, edge, opened, slack))

    swaps = fell("swap_round", engine_trickle.SLACK)
    assert swaps == 6  # begun at 96 ... 176
    assert layer["trickle.moves_per_kgr"]["value"] == pytest.approx(
        1e3 * swaps / (24 * raw["rounds"]))
    # A snapshot follows the learner's change by a few rounds.
    assert layer["trickle.snapshots_per_move"]["value"] == pytest.approx(
        fell("add_learner_round", engine_trickle.SLACK) / swaps)
    assert raw["trickle"]["in_motion_open"] == 7
    assert raw["trickle"]["in_motion_close"] == (
        gen.moves(load, "add_learner_round", closed, engine_trickle.SLACK)
        - gen.moves(load, "wipe_round", closed))
    assert layer["trickle.in_motion_pct"]["value"] == pytest.approx(
        100.0 * (7 + raw["trickle"]["in_motion_close"]) / 48)
    assert 99.0 < layer["trickle.committed_pct"]["value"] <= 100.0


@pytest.fixture(scope="module")
def driven24(root24):
    cell = harness.Cell(root24, CELL)
    seed = 2**31 + 78
    load = gen.make(cell.traffic, cell.config["sizes"], seed)
    driver = engine_trickle.Driver(cell.config, cell.traffic, seed, "")
    driver.setup(load, gen)
    raw = gen.run(driver, load, cell.traffic, 0.3,
                  harness.Probe(False, 0.0, tempfile.gettempdir()))
    raw.update(driver.window_counters())
    yield driver, load, raw
    driver.close()


def test_committed_of_offered_is_what_the_reference_logs_hold(driven24):
    """The count ``trickle.committed_pct`` reads, against the plain
    reference of *every* group, entry by entry: an entry of the window
    was offered if it is a normal one and not the first of its term
    (a new leader's own). The driver has the same number from the
    commit indexes and two columns of the telemetry plane, and nothing
    is cut off."""
    from benchmark.reference.raft.types import EntryType
    from benchmark.reference.shadow_trickle import TrickleCluster

    driver, load, raw = driven24
    a, b = driver.marks["open"], driver.marks["close"]

    class Counting(TrickleCluster):
        seen = offered = None

        def schedule_round(self, offer, tick):
            if self.rounds == a["rounds_done"]:
                self.opened = (self.seen, self.offered)
            super().schedule_round(offer, tick)
            log = max((n.raft.raft_log for n in self.nodes),
                      key=lambda lg: lg.committed)
            if self.seen is None:
                self.seen, self.offered = log.committed, 0
            for e in log.slice(self.seen + 1, log.committed + 1, 1 << 62):
                self.offered += (e.type == EntryType.EntryNormal
                                 and log.term(e.index - 1) == e.term)
            self.seen = log.committed
            if self.rounds == b["rounds_done"]:
                self.closed = (self.seen, self.offered)

    rounds, driver.rounds_done = driver.rounds_done, b["rounds_done"]
    try:
        refs = [driver._step_group(load, g, int(load["starts"][g]), Counting)
                for g in range(driver.groups)]
    finally:
        driver.rounds_done = rounds
    moved = sum(r.closed[0] - r.opened[0] for r in refs)
    offered = sum(r.closed[1] - r.opened[1] for r in refs)
    assert moved == raw["entries_committed"]
    t = raw["trickle"]
    assert offered == raw["entries_committed"] - t["unoffered_committed"]
    assert t["offered"] == 24 * raw["rounds"] * 2
    # Some move handed its leadership over inside the window, and its
    # leader appended nothing while it did.
    assert t["unoffered_committed"] > 0 and offered < t["offered"]
    ctx = {"raw": raw}
    assert reader.committed_pct(ctx) == pytest.approx(
        100.0 * offered / t["offered"])
    # A group that was never started committed what it was offered.
    never = [r for g, r in enumerate(refs) if load["starts"][g] == gen.NEVER]
    assert never and all(
        r.closed[1] - r.opened[1] == raw["rounds"] * 2 for r in never)


def test_the_shared_entries_read_this_cells_run_through_the_harness(
        layer_run, capsys):
    """What a line of the driver's own said until PR 52 is on the
    result line: the shared entries the host's counters and spans
    give, each by its own reader through the harness (the device
    trace's need the chip)."""
    cell, ctx = layer_run
    layer = harness.per_layer_metrics(cell, ctx)
    tags = {ln.split("]")[0] for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench:")}
    assert not [t for t in tags if t.endswith("_layers")]
    got = {n: m["value"] for n, m in layer.items() if n in SHARED_AT_52}
    assert set(got) == {
        "round.lanes_run", "read.confirmed_per_kgr",
        "read.rounds_to_confirm", "setup.jax_trace_s",
        "setup.jax_compile_s", "setup.unspanned_s", "round.rare_pct",
        "emit.ring_pct"} | ({"setup.pretrace_s"} & set(got))
    assert 0 < got["round.lanes_run"] <= 6
    # A ReadIndex batch a leader a round or two.
    assert 300 < got["read.confirmed_per_kgr"] <= 1000
    assert 1.0 <= got["read.rounds_to_confirm"] < 4.0
    # A leadership handed over in a few of the window's rounds.
    assert 0 < got["round.rare_pct"] < 50 and 0 < got["emit.ring_pct"] < 100


def test_a_program_without_a_phased_schedule_fails_at_once(root, monkeypatch):
    """The parent: its ``run_rounds`` takes no ``starts``. The driver
    says so before it builds anything."""
    from etcd_tpu.batched import MultiRaftEngine

    def run_rounds(self, rounds, tick=True, propose_n=None, isolate=None,
                   control=None):
        raise AssertionError("not reached")

    def init(self, cfg, start_index=0, spare=None, nodes=None):
        raise AssertionError("not reached")

    monkeypatch.setattr(MultiRaftEngine, "run_rounds", run_rounds)
    monkeypatch.setattr(MultiRaftEngine, "__init__", init)
    cell = harness.Cell(root, CELL)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="phased control schedule"):
        harness.measure(cell, 4, 0.3, False, time.perf_counter(),
                        require_tpu=False)
    assert time.perf_counter() - t0 < 5


@pytest.mark.parametrize("how", ["a_round_late", "lockstep"])
def test_the_timed_path_broken_is_not_correct(root, monkeypatch, how):
    """The program's side of the two controls: the engine is handed
    every start a round late, or every start 0 (the phased argument as
    good as dropped: every group moves at once, as in the lockstep
    cell)."""
    from etcd_tpu.batched import MultiRaftEngine

    real = MultiRaftEngine.run_rounds

    def run_rounds(self, rounds, tick=True, propose_n=None, isolate=None,
                   control=None, starts=None):
        if how == "a_round_late":
            starts = np.where(starts == gen.NEVER, starts, starts + 1)
        else:
            starts = np.full_like(starts, 64)  # the schedule's round 0
        real(self, rounds, tick=tick, propose_n=propose_n, isolate=isolate,
             control=control, starts=starts)

    monkeypatch.setattr(MultiRaftEngine, "run_rounds", run_rounds)
    cell = harness.Cell(root, CELL)
    _ctx, checks = harness.measure(cell, 12, 0.3, False,
                                   time.perf_counter(), require_tpu=False)
    bad = {c.name for c in checks if not c.ok}
    assert "sampled_replicas_history_differs_from_reference" in bad
    if how == "lockstep":
        assert {"changes_applied_by_a_group_that_has_not_started",
                "replicas_of_a_group_not_started_whose_voters_are_not_the_"
                "three_seated",
                "empty_slots_of_groups_not_started_that_are_not_a_fresh_"
                "replica",
                "swaps_taken_other_than_one_a_move_whose_swap_fell_in_the_"
                "run"} <= bad


@pytest.fixture(scope="module")
def driven(root):
    """The cell's driver after a tiny window, kept open for the
    controls."""
    cell = harness.Cell(root, CELL)
    seed = 2**31 + 77
    load = gen.make(cell.traffic, cell.config["sizes"], seed)
    driver = engine_trickle.Driver(cell.config, cell.traffic, seed, "")
    driver.setup(load, gen)
    raw = gen.run(driver, load, cell.traffic, 0.3,
                  harness.Probe(False, 0.0, tempfile.gettempdir()))
    yield driver, load, raw
    driver.close()


def test_the_window_is_whole_cycles_and_the_engine_kept_the_round(driven):
    driver, load, raw = driven
    assert raw["calls"] % 2 == 0 and raw["periods"] >= 1
    assert raw["attempted"] == raw["rounds"] and raw["failed"] == 0
    eng = driver.eng
    assert eng.phase_round == driver.settle_rounds + driver.rounds_done
    assert (driver.starts[load["starts"] != gen.NEVER]
            == load["starts"][load["starts"] != gen.NEVER] + 64).all()
    watch = raw["watch"]["after"]
    assert watch["swaps_taken"] == watch["replicas_reset"] == 4
    assert watch["conf_restores"] == 4


def test_sound_reference_is_correct_and_the_sample_spreads(driven):
    driver, load, raw = driven
    checks = driver.check(load, raw)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert driver.rounds_done % load["cycle_rounds"] == 0
    assert driver.derailed == []
    calls, final = driver.calls, driver.final
    driver.check(load, raw)
    assert driver.final is final and driver.calls == calls
    # Groups never started and groups of the batches, in turn.
    sample = driver.sample(load)
    assert len(sample) == 8 == len(set(sample))
    batch = driver.batch_of(load)[sample]
    assert (batch == 4).sum() == 4 and set(batch.tolist()) == {0, 1, 2, 3, 4}
    assert driver.sampled_batches(load) == [0, 1, 2, 3]


@pytest.mark.parametrize("seed", [5, 2**31 + 11])
def test_the_sample_at_a_larger_size(seed):
    """At 4,000 groups after 640 rounds: one group of each of the 15
    classes never started, and of six batches spread from the first to
    the last started one of each class the batch holds."""
    cfg = dict(config())
    cfg["sizes"] = dict(cfg["sizes"], **SIZES)
    load = gen.make(traffic(), cfg["sizes"], seed)
    driver = engine_trickle.Driver(cfg, traffic(), seed, "")
    driver.gen, driver.rounds_done = gen, 640
    assert driver.sampled_batches(load) == [0, 8, 16, 23, 31, 39]
    sample = driver.sample(load)
    batch = driver.batch_of(load)
    classes = driver.classes(load)
    assert len(set(classes[sample].tolist())) == len(sample) <= 120
    never = [g for g in sample if batch[g] == len(load["batches"])]
    assert len(never) == 15
    for b in driver.sampled_batches(load):
        held = set(classes[load["batches"][b]].tolist())
        got = {int(classes[g]) for g in sample if batch[g] == b}
        assert got == held and 8 <= len(held) <= 15
    assert len(set(classes.tolist())) <= 15 * 42


@pytest.mark.parametrize("control", engine_trickle.CONTROLS)
def test_control_is_not_correct(driven, control):
    """A start a round late: the sampled movers' histories differ and
    nobody else's. Every group on the lockstep schedule: the sampled
    groups never started end in another configuration."""
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
    if control == engine_trickle.CONTROLS[1]:
        assert "sampled_replicas_membership_differs_from_reference" in bad
    assert driver.derailed == []


def test_the_existing_control_script_names_the_first_control(driven):
    driver, load, raw = driven
    assert not verdict(driver.check(load, raw, control=True))
    with pytest.raises(ValueError):
        driver.check(load, raw, control="no_such_control")


def test_the_control_script_runs_the_cells_own_cases(root):
    """``benchmark/control_trickle.py`` on the tiny copy: sound true,
    both controls false, nobody derailed, exit 0."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "control_trickle.py"),
         "--workload", CELL, "--seed", str(2**31 + 3), "--seconds", "0.3",
         "--root", root, "--any-device"],
        capture_output=True, text=True, env=env, timeout=600)
    lines = [json.loads(ln.split(" ", 1)[1])
             for ln in out.stdout.splitlines()
             if ln.startswith("[control] ")]
    assert out.returncode == 0, out.stderr[-2000:]
    assert [ln["case"] for ln in lines] == ["sound"] + list(
        engine_trickle.CONTROLS)
    assert [ln["correct"] for ln in lines] == [True, False, False]
    assert all(ln["derailed_groups"] == 0 for ln in lines)
    assert all(ln["in_protocol_replicas_differing"] > 0 for ln in lines[1:])
