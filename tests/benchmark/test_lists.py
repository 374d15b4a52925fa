"""The rule that took ``CELLS_AT_36``'s place (ISSUE 52): an entry of
a layer that more than one cell's program runs (``util.shared``: it
reads the device trace or the program's spans, or lists more than one
cell) lists a cell if and only if that cell's run gives its reader
something to read.

Everything a case needs comes from data that a later PR can bring
without editing a file here. The cells and entries are those of
``BENCHMARK.json``. A cell's cuts to a CPU run of seconds are its own
file under ``tiny/`` (``util.apply_tiny``). The scopes its program
holds are read from the program: the closed loop its window calls is
traced to a jaxpr and the registered scopes (``step.DEVICE_SCOPES``) of
its equations taken (``util.LoopScopes``; a CPU trace has no device
plane), so a cell that brings a scope brings it in its program and in
no table. Which entry reads which scope is found by handing each reader
a trace of that one scope (``share_of``). Every cell runs once, alone,
on a root of its own: a cell that fails costs its own cases.

The contract that keeps the lists open runs the same per-cell cases on
``util.later_root``: a copy of the benchmark to which the next PRs added
a configuration, a traffic mix, a cell with its tiny cuts, its name on
the lists and one more entry, all as files, none by an edit here; and
every ``*_rule(b)`` of every test module of this directory, found by
their names, is held to that copy's ``BENCHMARK.json``. A pin written
later fails there, in the PR that writes it.

Beside it: the bytes the roofline counts against hand counts, and the
three occupancy readers at 0, at all and with no marks.
"""

import importlib
import inspect
import json
import os
import pkgutil

import pytest

from benchmark.compare import verdict
from benchmark.readers import lanes, nodes
from benchmark.readers import trace as trace_reader
from benchmark.reduce import roofline, roofline_ici

from .util import (CELLS_AT_36, LATER, LATER_ENTRY, REPO, SHARED_AT_52,
                   bench, cell_root, edited_copy, in_workloads_order,
                   later_root, one_more, one_more_root, real_tiles, run_tiny,
                   shared)

B = bench()
CELLS = [w["name"] for w in B["workloads"]]
LATER_B = json.loads(json.dumps(B))
one_more(LATER_B)
# (root, cell): every committed cell on its own root, and the later
# cell on the root the next PRs would leave.
CASES = [("committed", c) for c in CELLS] + [("later", LATER)]
FILES = {"committed": B, "later": LATER_B}
E4 = [21, 49, 17, 10, 22, 13]  # step.lane_slot_bytes(4)
E64 = [21, 45, 17, 10, 22, 13, 244]  # step.lane_slot_bytes(64, 3)


def spec_of(root: str, name: str) -> dict:
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def as_traced(ctx: dict, scopes) -> dict:
    """The run with what a ``--trace 1`` run on the chip adds: a second
    of every scope the cell's program holds and of ``unscoped``, and
    one traced call, whose lanes are the window's last call's."""
    scope_s = dict.fromkeys(sorted(scopes), 1.0)
    scope_s["unscoped"] = 1.0
    raw = dict(ctx["raw"], traced_calls=1)
    occ = raw.get("occupancy")
    if occ:
        rpc = raw["rounds_per_call"]
        traced = {k: ([x + rpc * (x > y) for x, y in zip(v, occ["before"][k])]
                      if isinstance(v, list) else v)
                  for k, v in occ["after"].items()}
        traced["calls"] = occ["after"]["calls"] + 1
        raw["occupancy"] = dict(occ, traced=traced)
    return dict(ctx, raw=raw, device={"kind": "TPU v5 lite"}, trace={
        "scope_s": scope_s, "leaf_s": float(len(scope_s)),
        "modules": {"jit_closed_loop": {"count": 1, "seconds": 1.0}}})


def share_of(cell, spec: dict, scopes) -> set:
    """The scopes of which the entry's reader gives the share: handed a
    trace of that scope and one other, a second each, it says 50."""
    out = set()
    for scope in scopes:
        got = cell.reader(spec)(
            {"raw": {}, "trace": {"scope_s": {scope: 1.0, "_other": 1.0},
                                  "leaf_s": 2.0, "modules": {}}},
            **spec.get("params", {}))
        if got == 50.0:
            out.add(scope)
    return out


@pytest.fixture(scope="module")
def run_of(tmp_path_factory):
    """``run_of(root kind, cell)``: (the cell, its traced-like ctx, what
    each entry with a list gave its reader, the scopes its program
    holds, every entry's scopes by ``share_of``), run once and kept; a
    run that failed fails each case that asks for it, and no other."""
    kept = {}

    def run(kind: str, name: str):
        if (kind, name) not in kept:
            try:
                kept[kind, name] = True, _run(kind, name)
            except Exception as e:  # kept: raised in each of its cases
                kept[kind, name] = False, e
        ok, got = kept[kind, name]
        if not ok:
            raise got
        return got

    def _run(kind: str, name: str):
        dst = str(tmp_path_factory.mktemp("lists"))
        if kind == "later":
            # Tiles from the sizes as a PR commits them, before the cut.
            tiles = real_tiles(name, one_more_root(
                str(tmp_path_factory.mktemp("uncut"))))
            root = later_root(dst)
        else:
            tiles = real_tiles(name)
            root = cell_root(dst, name)
        cell, ctx, checks, scopes = run_tiny(root, name, tiles=tiles)
        assert verdict(checks), (name, [c for c in checks if not c.ok])
        traced = as_traced(ctx, scopes)
        b, gave, reads = cell.bench, {}, {}
        held = sorted(scopes | {"unscoped"})
        for m in b["per_layer"]:
            spec = spec_of(root, m["name"])
            if m["source"] == "device_trace":
                reads[m["name"]] = share_of(cell, spec, held)
            if "workloads" in m:
                gave[m["name"]] = cell.reader(spec)(
                    dict(traced), **spec.get("params", {}))
        return cell, traced, gave, scopes, reads

    return run


def case_ids(kind: str):
    return [(kind, c, e) for k, c in CASES if k == kind
            for e in shared(FILES[kind])]


@pytest.mark.parametrize(
    "kind,name,entry", case_ids("committed") + case_ids("later"),
    ids=lambda v: v)
def test_an_entry_lists_a_cell_iff_its_reader_finds_something(
        run_of, kind, name, entry):
    cell, _traced, gave, _scopes, _reads = run_of(kind, name)
    spec = spec_of(cell.root, entry)
    # A count of events that reads 0 where the traffic asks for none
    # says so in its file: there 0 is nothing to read. (An occupancy
    # share reads 0 where the branch is there and was not taken, which
    # is a reading.)
    found = gave[entry] is not None and not (
        spec.get("silent_at_zero") and gave[entry] == 0)
    row = [m for m in cell.bench["per_layer"] if m["name"] == entry][0]
    assert (name in row["workloads"]) == found, gave[entry]
    # A listed entry reaches the cell through the harness, by its file.
    mine = [s for s in cell.per_layer if s["name"] == entry]
    assert bool(mine) == found
    for s in mine:
        assert s["reader"] == spec["reader"] and callable(cell.reader(s))


@pytest.mark.parametrize("kind,name", CASES, ids=lambda v: v)
def test_the_shares_on_the_cells_line_partition_its_trace(
        run_of, kind, name):
    """Every scope the cell's program holds that any entry of the file
    reads is read by a share the cell lists, each by one, and no share
    the cell lists reads a scope its program has not: the shares on the
    line add up to 100 less the scopes no entry reads (``raft_lease``
    today: in no live configuration more than a masked select)."""
    cell, traced, _gave, scopes, reads = run_of(kind, name)
    mine = {s["name"] for s in cell.per_layer}
    read_anywhere = set().union(*reads.values())
    held = scopes | {"unscoped"}
    by_scope = {}
    for entry, its in reads.items():
        if entry in mine:
            for scope in its:
                by_scope.setdefault(scope, []).append(entry)
    assert set(by_scope) == held & read_anywhere
    assert all(len(v) == 1 for v in by_scope.values()), by_scope
    got = {s["name"]: cell.reader(s)(dict(traced), **s["params"])
           for s in cell.per_layer if reads.get(s["name"])}
    assert None not in got.values()
    assert sum(got.values()) == pytest.approx(
        100.0 * len(by_scope) / len(held))
    assert len(held) - len(by_scope) <= 1  # the one scope nobody reads


def test_the_later_entry_reads_the_later_cells_run(run_of):
    """The entry a later PR appends without a list reaches the later
    cell through the harness and reads its run."""
    cell, traced, _gave, _scopes, _reads = run_of("later", LATER)
    mine = [s for s in cell.per_layer if s["name"] == LATER_ENTRY]
    assert len(mine) == 1
    assert cell.reader(mine[0])(dict(traced), **mine[0]["params"]) > 0
    assert cell.config["name"] == LATER.split(".")[0]
    assert cell.traffic["name"] == LATER.split(".", 1)[1]


def test_the_rule_refuses_a_later_cell_left_off_a_list_it_reads(run_of):
    """The contract has teeth: the later cell's run gives the readers
    of the newest cell's lists something to read, its own entries'
    among them, so a PR that appended the cell and not its name to
    those lists fails the rule's case for each."""
    cell, _traced, gave, scopes, _reads = run_of("later", LATER)
    newest = B["workloads"][-1]["name"]
    on = [m["name"] for m in B["per_layer"]
          if newest in m.get("workloads", []) and m["name"] in shared(B)]
    assert len(on) >= 15 and "raft_log" in scopes
    for entry in on:
        assert gave[entry] is not None, entry
        row = [m for m in cell.bench["per_layer"] if m["name"] == entry][0]
        assert row["workloads"][-1] == LATER
    # And the cuts came from the file the later cell brought: without
    # it the period would be the committed 4,096 rounds.
    assert cell.traffic["period_rounds"] == 512


def test_every_list_is_in_the_order_of_the_cells():
    for m in B["per_layer"]:
        if "workloads" in m:
            assert m["workloads"] and in_workloads_order(B, m), m["name"]
    # No accepted entry lost a cell: the twelve that listed the five
    # cells of PR 36 list them still, first.
    rows = {m["name"]: m for m in B["per_layer"]}
    for name in ("round.route_pct", "route.roofline_pct", "round.tick_pct",
                 "round.control_pct", "round.propose_pct", "round.emit_pct",
                 "round.unscoped_pct", "round.lanes_run", "scan.carry_pct",
                 "setup.jax_trace_s", "setup.jax_compile_s",
                 "setup.unspanned_s"):
        assert rows[name]["workloads"][:5] == CELLS_AT_36, name
    # The three of PR 52 stand after the deep-log cell's six, by index
    # from the front; what follows them is a later PR's.
    names = [m["name"] for m in B["per_layer"]]
    assert names[66:69] == SHARED_AT_52[18:]
    # What PR 52 found shared is under the rule still, but the one
    # entry that one cell alone lists (the bulk half: the one split
    # lane).
    assert set(SHARED_AT_52) - set(shared(B)) <= {"round.bulk_pct"}


# -- the bytes, by hand ------------------------------------------------------------

RUNS = {
    "steady_appends": [0, 64, 16, 0, 64, 16],
    "every_lane": [64, 64, 64, 64, 64, 64],
    "a_vote_in_a_quiet_call": [1, 0, 64, 1, 0, 64],
}


@pytest.mark.parametrize("runs_by_lane", RUNS.values(), ids=RUNS.keys())
@pytest.mark.parametrize("slots,bulk", [(E4, 0), (E64, 5)],
                         ids=["E4", "E64_head3"])
def test_lane_bytes_against_a_hand_count(slots, bulk, runs_by_lane):
    rows, r = 6, 3  # two groups of three
    by_hand = 0
    for k, n in enumerate(runs_by_lane):
        by_hand += n * rows * r * slots[k] * 2  # read once, written once
    if len(slots) == 7:
        by_hand += bulk * rows * r * 244 * 2
    assert roofline.lane_bytes(rows, r, runs_by_lane, slots, bulk) == by_hand
    # Between chips: the R - 1 other nodes' slots, sent once.
    assert roofline_ici.sent_bytes(runs_by_lane, rows, r, slots[:6]) == sum(
        n * rows * (r - 1) * slots[k] for k, n in enumerate(runs_by_lane))
    if runs_by_lane == RUNS["steady_appends"] and len(slots) == 6:
        # 78.5 bytes a slot-round where the old count had 300.
        assert by_hand / (2 * rows * r * 64) == 78.5


def test_the_slot_bytes_are_the_programs():
    from etcd_tpu.batched import step

    assert step.lane_slot_bytes(4).tolist() == E4
    assert step.lane_slot_bytes(64, 3).tolist() == E64


def test_lane_bytes_refuses_what_is_not_six_lanes():
    with pytest.raises(ValueError):
        roofline.lane_bytes(6, 3, [1] * 5, E4)
    with pytest.raises(ValueError):
        roofline.lane_bytes(6, 3, [1] * 6, E4[:5])


def test_a_tail_without_its_runs_is_refused_and_not_left_out():
    """A split append lane states a seventh slot size; a caller that
    does not say how often the tail ran (the node-placed driver counts
    none) raises, on one chip and between chips: it never reads low."""
    with pytest.raises(ValueError, match="tail"):
        roofline.lane_bytes(6, 3, [1] * 6, E64)
    with pytest.raises(ValueError, match="tail"):
        roofline_ici.sent_bytes([1] * 6, 6, 4, E64)
    assert roofline_ici.sent_bytes([1] * 6, 6, 4, E64, bulk_runs=0) == (
        6 * 3 * sum(E64[:6]))
    assert roofline_ici.sent_bytes([1] * 6, 6, 4, E64, bulk_runs=1) == (
        6 * 3 * sum(E64))


class _Counting:
    """An engine's counters and shape, and nothing else of it."""

    def __init__(self):
        from etcd_tpu.batched import BatchedConfig

        self.cfg = BatchedConfig(num_groups=2, num_replicas=3, window=32,
                                 max_ents_per_msg=4, max_props_per_round=2)
        self._tiles = 1
        self.reads = 0

    def lane_rounds(self):
        import numpy as np

        self.reads += 1
        return np.full(6, self.reads)

    rare_rounds = lane_rounds

    def bulk_rounds(self):
        return 0

    emit_ring_rounds = bulk_rounds


def test_the_reading_after_the_trace_is_taken_when_told_not_by_asking():
    """``window_counters`` reads nothing: asked before the generator
    said the trace had stopped it has no third reading (and the
    roofline then reads nothing), asked twice it says the same; the
    third reading is ``traced_closes``'s, with the calls made by
    then."""
    from benchmark.drivers import engine

    d = engine.Driver({"sizes": {"num_groups": 2}}, {}, 1, "")
    d.eng, d.calls = _Counting(), 3
    d.marks = {"open": {"occupancy": engine.occupancy(d)}}
    assert d.window_counters() == {}
    d.window_closes()
    reads = d.eng.reads
    first = d.window_counters()
    assert first == d.window_counters() and d.eng.reads == reads
    assert first["occupancy"]["traced"] is None and "lanes" not in first
    d.calls = 4
    d.traced_closes()
    occ = d.window_counters()["occupancy"]
    assert occ["traced"]["calls"] == 4 and occ["after"]["calls"] == 3
    assert occ["traced"]["lanes"] == [occ["after"]["lanes"][0] + 2] * 6
    assert (occ["rows"], occ["replicas"], occ["slot_bytes"]) == (6, 3, E4)


def roofline_ctx(traced_calls=1, **occ):
    occupancy = {
        "before": {"lanes": [0, 64, 16, 0, 64, 16], "bulk": 0, "calls": 1},
        "after": {"lanes": [0, 128, 32, 0, 128, 32], "bulk": 0, "calls": 2},
        "traced": {"lanes": [0, 192, 48, 0, 192, 48], "bulk": 0, "calls": 3},
        "rows": 6, "replicas": 3, "app_head": 0, "slot_bytes": E4,
        "ring_tiles": 1}
    occupancy.update(occ)
    return {"raw": {"traced_calls": traced_calls, "occupancy": occupancy},
            "traffic": {"rounds_per_call": 64},
            "config": {"sizes": {}}, "device": {"kind": "TPU v5 lite"},
            "trace": {"scope_s": {"raft_route": 1e-6}, "leaf_s": 1e-6,
                      "modules": {"jit_closed_loop": {
                          "count": 1, "seconds": 1e-6}}}}


def test_the_roofline_counts_the_traced_calls_own_lanes(capsys):
    got = trace_reader.route_roofline_pct(
        roofline_ctx(), "closed_loop", "rounds_per_call")
    need = 2 * 6 * 3 * (64 * 49 + 16 * 17 + 64 * 22 + 16 * 13)
    assert got == pytest.approx(100.0 * need / 819e9 / 1e-6)
    said = [json.loads(ln.split("] ", 1)[1])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench:roofline] ")]
    assert len(said) == 1 and said[0]["bytes_needed"] == need
    assert said[0]["lane_runs"] == [0, 64, 16, 0, 64, 16]
    assert said[0]["slot_bytes"] == E4 and said[0]["bulk_runs"] == 0
    assert (said[0]["rows"], said[0]["replicas"]) == (6, 3)


NOTHING = {
    "a driver that read no counters": lambda c: c["raw"].pop("occupancy"),
    "no reading after the traced calls": lambda c: c["raw"][
        "occupancy"].update(traced=None),
    "a run that traced nothing": lambda c: c["raw"].update(traced_calls=0),
    "calls between the readings that were not traced": lambda c: c["raw"][
        "occupancy"]["traced"].update(calls=5),
    "no trace": lambda c: c.update(trace=None),
    "a program that does not route": lambda c: c["trace"].update(
        scope_s={"raft_ici": 1.0}),
}


@pytest.mark.parametrize("edit", NOTHING.values(), ids=NOTHING.keys())
def test_the_roofline_finds_nothing(edit):
    ctx = roofline_ctx()
    edit(ctx)
    assert trace_reader.route_roofline_pct(
        ctx, "closed_loop", "rounds_per_call") is None


# -- the three occupancy readers ---------------------------------------------------

READERS = {
    "round.rare_pct": (lanes.rare_pct, "rare", [0, 0], [64, 64]),
    "emit.ring_pct": (lanes.ring_pct, "ring", 0, 128),
    "round.bulk_pct": (lanes.bulk_pct, "bulk", 0, 64),
}


def occupancy_ctx(key, before, after, **shape):
    return {"raw": {"rounds": 64, "occupancy": dict(
        {"before": {key: before}, "after": {key: after},
         "app_head": 3, "ring_tiles": 2}, **shape)}}


@pytest.mark.parametrize("name", READERS)
@pytest.mark.parametrize("how", ["at_0", "at_all", "no_marks"])
def test_an_occupancy_reader(name, how):
    read, key, zero, whole = READERS[name]
    if how == "at_0":
        assert read(occupancy_ctx(key, zero, zero)) == 0.0
    elif how == "at_all":
        # Every round (every tile-round of two tiles; both handlers).
        assert read(occupancy_ctx(key, zero, whole)) == 100.0
    else:
        assert read({"raw": {"rounds": 64}}) is None
        assert read({"raw": {"rounds": 64, "occupancy": {
            "before": {key: zero}}}}) is None
        assert read({"raw": {"rounds": 0, "occupancy": occupancy_ctx(
            key, zero, whole)["raw"]["occupancy"]}}) is None
    spec = spec_of(REPO, name)
    assert spec["reader"] == "lanes." + read.__name__
    assert "ANY tile" in spec["counts"]
    assert "tile-rounds" in spec["counts"].lower()


def test_bulk_is_nothing_where_the_lane_is_not_split_and_ring_needs_tiles():
    assert lanes.bulk_pct(occupancy_ctx("bulk", 0, 0, app_head=0)) is None
    assert lanes.ring_pct(occupancy_ctx("ring", 0, 4, ring_tiles=0)) is None


def test_the_nodes_readers_count_with_the_same_function():
    ici = {"after_call": [[0] * 6, [0, 8, 8, 0, 8, 8]], "open": 0,
           "close": 1, "tile_rows": 8, "tiles": 1, "replicas": 4,
           "slot_bytes": E4}
    ctx = {"raw": {"ici": ici, "rounds_per_call": 8}}
    assert nodes.mb_per_round(ctx) == pytest.approx(
        roofline.lane_bytes(8, 3, [0, 8, 8, 0, 8, 8], E4, passes=1)
        / 8 / 1e6)
    assert nodes.mb_per_round(ctx) == pytest.approx(
        8 * 8 * 3 * (49 + 17 + 22 + 13) / 8 / 1e6)


# -- the lists stay open ----------------------------------------------------------


def position_rules() -> dict:
    """Every ``*_rule(b)`` of every test module of this directory, by
    ``module.function``: found by name, so that a test file a later PR
    adds is held to ``one_more`` from its first day."""
    found = {}
    pkg = importlib.import_module(__package__)
    for info in pkgutil.iter_modules(pkg.__path__):
        if not info.name.startswith("test_") or info.name == "test_lists":
            continue
        mod = importlib.import_module(f"{__package__}.{info.name}")
        for n, f in vars(mod).items():
            if (n.endswith("_rule") and inspect.isfunction(f)
                    and f.__module__ == mod.__name__
                    and list(inspect.signature(f).parameters) == ["b"]):
                found[f"{info.name}.{n}"] = f
    return found


POSITION_RULES = position_rules()


@pytest.mark.parametrize("rule", POSITION_RULES.values(),
                         ids=POSITION_RULES.keys())
def test_a_rule_of_position_holds_on_the_file_and_admits_one_more(
        tmp_path, rule):
    """Each test file's rules on where its PR's additions stand, on
    ``BENCHMARK.json`` as it is and on a copy to which the next PRs
    appended a configuration, a cell (its name at the end of every
    list that the newest cell is on) and a per-layer entry."""
    rule(bench())
    later = edited_copy(tmp_path, one_more)
    assert len(later["per_layer"]) == len(B["per_layer"]) + 1
    assert len(later["workloads"]) == len(B["workloads"]) + 1
    assert later == LATER_B
    rule(later)


def test_the_rules_found_are_at_least_those_of_pr_52():
    """At least these, by name: a rule may be added, none of these may
    go unheld."""
    assert {
        "test_spans.live_entries_rule", "test_reconf.gained_rule",
        "test_reconf.entries_rule", "test_faults.entries_rule",
        "test_replace.entries_rule", "test_replace.follows_rule",
        "test_nodes.follows_rule", "test_nodes.entries_rule",
        "test_trickle.follows_rule", "test_trickle.entries_rule",
        "test_load.follows_rule", "test_load.entries_rule",
        "test_catchup.follows_rule", "test_catchup.entries_rule",
        "test_layers.the_23_rule"} <= set(POSITION_RULES)
