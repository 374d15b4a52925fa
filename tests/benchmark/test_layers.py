"""The 23 per-layer entries PR 36 made live, by name: each resolves to
its file and reader and reaches the cells it lists and no other (what
the three parked files' tests checked beyond ``test_contract.py::
test_metric_entry``), the eight shares of the round partition it in each
of the five cells, and ``round.lanes_run`` of each engine driver on a
tiny run against what ``lane_rounds()`` itself read round the window.

Every rule here is about those 23 entries and those five cells and
takes the benchmark's root, so that it is run twice: on the repo, and on
a temporary root to which the next PRs' additions were made (a cell
with its configuration, a span entry, a share that splits ``unscoped``,
the parked served cells with their entries). An entry or a cell a later
PR appends is held by ``test_contract.py``'s rules, from the data, and
by nothing in this file."""

import importlib
import json
import os
import time

import pytest

from benchmark import harness
from benchmark.compare import verdict

from . import test_faults, test_reconf, test_replace
from .test_spans import ONE_MORE, live_entries_rule
from .util import CELLS_AT_36, REPO, _edit, in_workloads_order, tiny_root

CELLS = CELLS_AT_36
NEW = [*test_faults.SEVEN, *test_reconf.SEVEN, *test_replace.FIVE,
       "round.propose_pct", "round.emit_pct", "round.unscoped_pct",
       "round.lanes_run"]
# The ``named_scope``s of the round (``step.py``) and ``reduce/
# trace.py``'s name for leaf device time under none of them, with the
# seconds of the builder's traced 1M run of PR 35, rounded.
SCOPE_S = {"raft_deliver": 2.618, "raft_route": 1.989, "unscoped": 1.484,
           "raft_control": 0.758, "raft_telemetry": 0.694,
           "raft_emit": 0.509, "raft_tick": 0.411, "raft_propose": 0.244}
SHARES = ["round.route_pct", "round.deliver_pct", "round.tick_pct",
          "round.control_pct", "round.propose_pct", "round.emit_pct",
          "round.unscoped_pct", "round.telemetry_pct"]


def bench_of(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


# -- the rules, of a root ----------------------------------------------------------


def the_23_rule(b: dict) -> None:
    """The 23 stand right after the 13 that were there, in R0b's order;
    the four this PR wrote list the five cells first, and after them
    the cells whose program runs the layer too, in the order of the
    cells (``test_lists.py`` holds which). What follows the 23 is a
    later PR's."""
    rows = b["per_layer"]
    assert len(NEW) == 23 and len(set(NEW)) == 23
    assert [m["name"] for m in rows[13:36]] == NEW
    assert not set(NEW) & {m["name"] for m in rows[:13] + rows[36:]}
    for m in rows[32:36]:
        assert m["workloads"][:5] == CELLS and in_workloads_order(b, m)


def entry_rule(root: str, name: str) -> None:
    """Beyond ``test_metric_entry``: the entry names its cells, one of
    the five among them, each a cell that reports the metric it moves
    and each once, in the order of the cells; its file's
    reader resolves; the harness hands it to the cells it lists and to
    no other cell of the root; ``PERF.md`` names it."""
    b = bench_of(root)
    m = [x for x in b["per_layer"] if x["name"] == name][0]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    assert m["workloads"] and set(m["workloads"]) & set(CELLS)
    assert in_workloads_order(b, m)
    moved = [e for e in b["end_to_end"] if e["name"] == m["moves"]][0]
    assert set(m["workloads"]) <= set(moved["workloads"])
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    mod, _, fn = spec["reader"].partition(".")
    assert callable(getattr(
        importlib.import_module("benchmark.readers." + mod), fn))
    for w in b["workloads"]:
        cell = harness.Cell(root, w["name"])
        mine = [s for s in cell.per_layer if s["name"] == name]
        assert bool(mine) == (w["name"] in m["workloads"]), w["name"]
        for s in mine:
            assert callable(cell.reader(s)) and s["reader"] == spec["reader"]
    with open(os.path.join(REPO, "PERF.md")) as f:
        assert f"`{name}`" in f.read()


def shares(cell, scope_s, names=None):
    """{entry: share} of the cell's ``trace.scope_pct`` entries (of
    ``names`` alone, if given) on a reduced trace of these scopes; an
    entry whose scope the trace lacks is left out, as on the line."""
    red = {"scope_s": scope_s, "leaf_s": sum(scope_s.values()),
           "modules": {}}
    ctx = {"raw": {}, "config": cell.config, "traffic": cell.traffic,
           "trace": red}
    got = {s["name"]: cell.reader(s)(ctx, **s["params"])
           for s in cell.per_layer if s["reader"] == "trace.scope_pct"
           and (names is None or s["name"] in names)}
    return {k: v for k, v in got.items() if v is not None}


def partition_rule(root: str, name: str) -> None:
    """In a traced run the eight shares (seven where the configuration
    leaves the telemetry plane off) add up to 100: every scope the
    cell's program has is one of theirs. A scope none of them reads
    shows as what is missing from 100, and is a later entry's to take."""
    cell = harness.Cell(root, name)
    plane = bool(cell.config["sizes"].get("telemetry"))
    scope_s = {k: v for k, v in SCOPE_S.items()
               if plane or k != "raft_telemetry"}
    got = shares(cell, scope_s, SHARES)
    assert set(got) == set(SHARES if plane else SHARES[:-1])
    assert all(0.0 < v < 100.0 for v in got.values())
    assert sum(got.values()) == pytest.approx(100.0)
    assert got["round.unscoped_pct"] == pytest.approx(
        100.0 * scope_s["unscoped"] / sum(scope_s.values()))
    # The fleet summary's scope is on in no live cell; were it, the
    # shares would say so by what they leave.
    got = shares(cell, dict(scope_s, raft_fleet=1.0), SHARES)
    assert sum(got.values()) == pytest.approx(
        100.0 * (1.0 - 1.0 / (sum(scope_s.values()) + 1.0)))


# -- on the repo -----------------------------------------------------------------------


def test_the_23_follow_the_13_and_each_names_its_cells():
    the_23_rule(bench_of(REPO))
    parked = os.listdir(os.path.join(REPO, "benchmark", "parked"))
    assert not [f for f in parked if f.endswith("_layers.json")]
    assert [w["name"] for w in bench_of(REPO)["workloads"]][:5] == CELLS


@pytest.mark.parametrize("name", NEW)
def test_entry_resolves_and_reaches_its_cells_alone(name):
    entry_rule(REPO, name)


@pytest.mark.parametrize("name", CELLS)
def test_the_shares_partition_the_round(name):
    partition_rule(REPO, name)


def test_a_scope_the_trace_lacks_gives_no_share():
    cell = harness.Cell(REPO, CELLS[0])
    got = shares(cell, {"raft_deliver": 1.0, "raft_route": 1.0})
    assert "round.unscoped_pct" not in got and "round.emit_pct" not in got
    assert got["round.route_pct"] == pytest.approx(50.0)


# -- and on a root the next PRs have added to -----------------------------------------

TILES = {"name": "later.tiles_pct", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "round program",
         "moves": "group_rounds_per_s", "workloads": CELLS[3:] + [
             "later-r3.append"]}
SETUP_SPAN = {"name": "later.setup_span_s", "unit": "s", "better": "lower",
              "source": "program_span", "layer": "compile",
              "moves": "setup_s"}


def _metric_file(root, m, reader, params):
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           m["name"] + ".json"), "w") as f:
        json.dump({"name": m["name"], "unit": m["unit"],
                   "layer": m["layer"], "moves": m["moves"],
                   "reader": reader, "params": params}, f)


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    """The benchmark after the PRs it was opened for, each adding files
    and entries at the ends of their lists and editing none: ``tiny_
    root`` brings the served cells back from ``parked/served.json``
    (another driver, readers in ``readers/host.py``); a ``model_config``
    PR's configuration, cell and name under ``group_rounds_per_s``;
    ROADMAP S5's span over ``setup_s`` and ``test_spans.py``'s
    ``ONE_MORE`` (``readers/spans.py``, ``program_span``, with and
    without ``workloads``); S7's share of a scope that splits
    ``unscoped`` (``trace.scope_pct`` once more)."""
    root = tiny_root(str(tmp_path_factory.mktemp("later")))
    base = os.path.join(root, "benchmark")

    def one_more_cell(b):
        b["configs"].append(dict(
            b["configs"][0], name="later-r3",
            file="benchmark/configs/later-r3.json"))
        b["workloads"].append(dict(
            b["workloads"][0], name="later-r3.append",
            config="later-r3"))
        b["end_to_end"][0]["workloads"].append("later-r3.append")
        b["per_layer"].extend([dict(ONE_MORE), dict(SETUP_SPAN),
                               dict(TILES)])

    with open(os.path.join(base, "configs", "engine64k-r3.json")) as f:
        cfg = dict(json.load(f), name="later-r3")
    with open(os.path.join(base, "configs", "later-r3.json"), "w") as f:
        json.dump(cfg, f)
    _metric_file(root, ONE_MORE, "spans.engine_dispatch_ms", {})
    _metric_file(root, SETUP_SPAN, "spans.setup_elect_s", {})
    _metric_file(root, TILES, "trace.scope_pct", {"scope": "raft_tiles"})
    _edit(os.path.join(root, "BENCHMARK.json"), one_more_cell)
    return root


def test_the_later_root_is_what_it_says(later_root):
    b = bench_of(later_root)
    assert len(b["workloads"]) > 6 and len(b["per_layer"]) > 39
    assert b["per_layer"][:36] == bench_of(REPO)["per_layer"][:36]
    # The additions reach their cells through the harness: the span
    # over set-up every cell, the new share the cells it lists.
    for w in b["workloads"]:
        got = {s["name"] for s in harness.Cell(
            later_root, w["name"]).per_layer}
        assert SETUP_SPAN["name"] in got
        assert (TILES["name"] in got) == (w["name"] in TILES["workloads"])
        assert (ONE_MORE["name"] in got) == (
            w["name"] in ONE_MORE["workloads"])


def test_the_rules_of_position_admit_what_was_appended(later_root):
    the_23_rule(bench_of(later_root))
    live_entries_rule(bench_of(later_root))
    test_reconf.gained_rule(bench_of(later_root))


@pytest.mark.parametrize("name", NEW)
def test_entry_rule_admits_what_was_appended(later_root, name):
    entry_rule(later_root, name)


@pytest.mark.parametrize("name", CELLS)
def test_partition_rule_admits_a_share_that_splits_unscoped(later_root,
                                                             name):
    partition_rule(later_root, name)
    # With S7's scope in the trace, taken out of ``unscoped``, the
    # eight leave what the new entry reads, where the cell lists it.
    cell = harness.Cell(later_root, name)
    scope_s = dict(SCOPE_S, unscoped=0.484, raft_tiles=1.0)
    if not cell.config["sizes"].get("telemetry"):
        del scope_s["raft_telemetry"]
    got = shares(cell, scope_s, SHARES + [TILES["name"]])
    tiles = 100.0 / sum(scope_s.values())
    if name in TILES["workloads"]:
        assert got[TILES["name"]] == pytest.approx(tiles)
        assert sum(got.values()) == pytest.approx(100.0)
    else:
        assert TILES["name"] not in got
        assert sum(got.values()) == pytest.approx(100.0 - tiles)


TIGHT = {
    "one of the 23 removed": lambda b: b["per_layer"].pop(20),
    "one renamed": lambda b: b["per_layer"][34].update(
        name="round.rest_pct"),
    "two re-ordered": lambda b: b["per_layer"].insert(
        14, b["per_layer"].pop(13)),
    "an entry put before them": lambda b: b["per_layer"].insert(
        13, dict(ONE_MORE)),
    "one moved to the end": lambda b: b["per_layer"].append(
        b["per_layer"].pop(35)),
    "the lane counter in four cells": lambda b: b["per_layer"][35].update(
        workloads=CELLS[:4]),
    "the lane counter's cells out of order": lambda b: b["per_layer"][
        35].update(workloads=b["per_layer"][35]["workloads"][::-1]),
}


@pytest.mark.parametrize("edit", TIGHT.values(), ids=TIGHT.keys())
def test_the_rule_holds_the_23_where_they_are(later_root, tmp_path, edit):
    root = str(tmp_path)
    os.symlink(os.path.join(later_root, "benchmark"),
               os.path.join(root, "benchmark"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench_of(later_root), f)
    the_23_rule(bench_of(root))
    _edit(os.path.join(root, "BENCHMARK.json"), edit)
    with pytest.raises(AssertionError):
        the_23_rule(bench_of(root))


# -- the lane counter, on a tiny run of each engine driver -----------------------


@pytest.mark.parametrize("name", CELLS)
def test_lanes_run_is_what_lane_rounds_read_round_the_window(
        later_root, name, monkeypatch, capsys):
    """``round.lanes_run`` against the counter itself: every reading of
    ``lane_rounds()`` and every call of the driver in the order they
    happened; the metric is the last reading before the window's first
    call less the first after its last, over the window's rounds, and
    nobody read the counter between two calls of the window."""
    from etcd_tpu.batched import MultiRaftEngine

    cell = harness.Cell(later_root, name)
    driver = cell.module("drivers", cell.config["driver"]).Driver
    events = []
    real_read, real_call = MultiRaftEngine.lane_rounds, driver.call

    def lane_rounds(self):
        got = real_read(self)
        events.append(("read", int(got.sum())))
        return got

    def call(self):
        real_call(self)
        events.append(("call", None))

    monkeypatch.setattr(MultiRaftEngine, "lane_rounds", lane_rounds)
    monkeypatch.setattr(driver, "call", call)
    ctx, checks = harness.measure(cell, 2**31 + 36, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert verdict(checks), [c for c in checks if not c.ok]
    layer = harness.per_layer_metrics(cell, ctx)
    raw = ctx["raw"]
    calls = [i for i, (kind, _v) in enumerate(events) if kind == "call"]
    # Call 0 is the set-up's warm-up; the window's follow it.
    first, last = calls[1], calls[raw["calls"]]
    before = [v for kind, v in events[:first] if kind == "read"][-1]
    after = [v for kind, v in events[last:] if kind == "read"][0]
    assert all(kind == "call" for kind, _v in events[first:last])
    got = layer["round.lanes_run"]
    assert got["unit"] == "lanes"
    assert got["value"] == (after - before) / raw["rounds"]
    # Appends and their responses at the least, six lanes at the most.
    assert 2.0 <= got["value"] <= 6.0
    # The scratch lines the entries replaced are gone.
    out = capsys.readouterr().out
    for tag in ("election", "reconf", "lanes", "replace"):
        assert f"[bench:{tag}] " not in out


def test_a_driver_that_reads_no_lanes_gives_no_metric():
    from benchmark.readers import lanes

    assert lanes.run_a_round({"raw": {"rounds": 64}}) is None
    assert lanes.run_a_round({"raw": {"rounds": 64, "occupancy": {
        "before": {"lanes": [0] * 6}}}}) is None
    assert lanes.run_a_round({"raw": {"rounds": 64, "occupancy": {
        "before": {"lanes": [0, 64, 16, 0, 64, 16]},
        "after": {"lanes": [0, 128, 32, 0, 128, 32]}}}}) == 2.5
