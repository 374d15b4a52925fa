"""The node-placed replacement cell's own pieces on the CPU (ISSUE 40):
its entries in ``BENCHMARK.json`` (the configuration, the cell, its name
under the end-to-end metric and the five ``ici.*`` entries, each
appended after what was there), its two data files against the one-chip
cell's, ``roofline_ici``'s bytes from shapes by hand, the ``ici.*``
readers on made-up counts, on a tiny run over four of the forced devices
and on another driver's run, the reference wrapper, and the driver
against a program whose engine takes no nodes."""

import json
import os
import time

import pytest

from benchmark import harness
from benchmark.compare import verdict
from benchmark.drivers import engine_nodes, engine_replace
from benchmark.readers import nodes as reader
from benchmark.reduce import roofline_ici
from benchmark.reference.shadow_replace import ReplaceCluster
from benchmark.reference.shadow_replace_nodes import NodesCluster

from .test_contract import NAME, SOURCES, UNIT
from .test_replace import CELL as ONE_CHIP_CELL
from .util import (CELLS_AT_36, REPO, UNLISTED, bench, edited_copy,
                   listed_cells, own_entries, reaches, shared_with,
                   tiny_root)

CONFIG = "engine1m-r3of4-x4"
TRAFFIC = "replace-readindex-x4"
CELL = CONFIG + "." + TRAFFIC
FIVE = ["ici.exchange_pct", "ici.agree_pct", "ici.lanes_run",
        "ici.mb_per_round", "ici.roofline_pct"]


def load(kind: str, name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", kind, name + ".json")) as f:
        return json.load(f)


# -- the entries: what the issue names, where they stand -----------------------------


def follows_rule(b: dict) -> None:
    """The configuration, the cell and the cell's name under its
    end-to-end metric each come right after ``engine512k-r3of4``'s, and
    the five entries are consecutive, in their order, after every entry
    that was there (``setup.unspanned_s`` the last of them). What
    follows any of them is a later PR's: the rule says nothing of
    it."""
    names = [c["name"] for c in b["configs"]]
    at = names.index("engine512k-r3of4")
    assert names[:at + 1] == ["engine64k-r3", "engine10k-r5",
                              "engine100k-r3", "engine1m-r3",
                              "engine512k-r3of4"]
    assert names[at + 1] == CONFIG and names.count(CONFIG) == 1
    cells = [w["name"] for w in b["workloads"]]
    assert cells[:6] == CELLS_AT_36 + [CELL] and cells.count(CELL) == 1
    rate = b["end_to_end"][0]
    assert (rate["name"], rate["bound"]) == ("group_rounds_per_s", 0.01)
    assert rate["workloads"][:6] == CELLS_AT_36 + [CELL]
    rows = [m["name"] for m in b["per_layer"]]
    at = rows.index("setup.unspanned_s")
    assert at == 42 and rows[at + 1:at + 6] == FIVE
    assert not set(FIVE) & set(rows[:at + 1] + rows[at + 6:])


def test_the_cell_follows_what_was_there():
    follows_rule(bench())
    assert bench()["run_seconds"] == 30


OPEN = {
    "a cell appended": lambda b: (
        b["workloads"].append(dict(b["workloads"][0], name="later.append")),
        b["end_to_end"][0]["workloads"].append("later.append")),
    "an entry appended": lambda b: b["per_layer"].append(
        dict(b["per_layer"][-1], name="ici.later")),
}
TIGHT = {
    "the cell before the one-chip cell": lambda b: b["workloads"].insert(
        4, b["workloads"].pop(5)),
    "one of the five renamed": lambda b: b["per_layer"][44].update(
        name="ici.agreement_pct"),
    "two of the five re-ordered": lambda b: b["per_layer"].insert(
        43, b["per_layer"].pop(45)),
    "an entry put before the five": lambda b: b["per_layer"].insert(
        43, dict(b["per_layer"][-1], name="ici.later")),
    "the configuration a second time": lambda b: b["configs"].append(
        dict(b["configs"][5])),
}


@pytest.mark.parametrize("edit", OPEN.values(), ids=OPEN.keys())
def test_the_rule_lets_a_later_pr_append(tmp_path, edit):
    follows_rule(edited_copy(tmp_path, edit))


@pytest.mark.parametrize("edit", TIGHT.values(), ids=TIGHT.keys())
def test_the_rule_holds_the_entries_where_they_are(tmp_path, edit):
    with pytest.raises(AssertionError):
        follows_rule(edited_copy(tmp_path, edit))


def test_the_entries_pass_the_contracts_rules():
    """``test_contract.py``'s rules for a configuration, a cell and the
    files they resolve to."""
    b = bench()
    cfg = [c for c in b["configs"] if c["name"] == CONFIG][0]
    w = [x for x in b["workloads"] if x["name"] == CELL][0]
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and NAME.match(w["name"])
    assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [c["file"] for c in b["configs"]].count(cfg["file"]) == 1
    data = load("configs", CONFIG)
    assert (data["name"], data["source"], data["reduced"]) == (
        cfg["name"], cfg["source"], cfg["reduced"])
    assert data["guarantees"] and data["reference"]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["traffic"]) and 1 <= len(w["why"]) <= 200
    pairs = [(x["config"], x["traffic"]) for x in b["workloads"]]
    assert len(set(pairs)) == len(pairs)
    listing = [m["name"] for m in b["end_to_end"]
               if CELL in m.get("workloads", [])]
    assert listing == ["group_rounds_per_s"]
    # One cell of six takes four chips; the contract allows three.
    four = [x["name"] for x in b["workloads"] if x["chips"] == 4]
    assert four == [CELL] and len(four) <= len(b["workloads"]) // 2
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    assert f"`{CELL}`" in perf and f"`{CONFIG}`" in perf


def test_the_entries_are_the_issues():
    b = bench()
    entry = [c for c in b["configs"] if c["name"] == CONFIG][0]
    cell = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=4)
    for word in ("confchange_v2_replace_leader.txt", "raft/confchange",
                 "raft.go:1518-1614", "server.go:80/1446", "rafthttp",
                 "BASELINE configs[4], uncut"):
        assert word in entry["source"], word
    cfg, one = load("configs", CONFIG), load("configs", "engine512k-r3of4")
    assert cfg["reduced"] == [] == entry["reduced"]
    assert "reduced_why" not in cfg
    assert cfg["driver"] == "engine_nodes"
    assert cfg["reference"] == "engine_shadow_replace_nodes"
    # The one-chip cell's deployment with the cut undone, nothing else.
    assert cfg["sizes"] == dict(one["sizes"], num_groups=1_048_576)
    assert cfg["guarantees"] == one["guarantees"]
    assert len(cfg["guarantees"]) == 9
    assert cfg["shadow_groups"] == one["shadow_groups"] == 15
    assert set(cfg["assumed"]) == set(one["assumed"]) | {
        "placement", "interconnect", "agreement"}
    same = set(one["assumed"]) - {"lockstep"}
    assert {k: cfg["assumed"][k] for k in same} == {
        k: one["assumed"][k] for k in same}
    assert "1,048,576" in cfg["assumed"]["lockstep"]
    for word in ("each a chip of one four-chip host", "ICI", "all-to-all",
                 "1,048,576 groups", "4,194,304 instance rows"):
        assert word in cfg["deployment"], word
    assert cfg["deployment"].endswith(one["deployment"].split("; ", 2)[2])
    for word in ("coords", "two hops", "1,600 Gbit/s"):
        assert word in cfg["assumed"]["interconnect"], word


def test_the_traffic_is_the_one_chip_cells_but_for_the_traced_calls():
    """To the key, the name and the traced calls apart: the one-chip
    cell traces a whole period (two calls), this cell one call, the
    period's first half (PERF.md section 4 says which of the schedule's
    events that leaves to the window)."""
    mine, one = load("traffic", TRAFFIC), load("traffic", "replace-readindex")
    assert list(mine) == list(one)
    assert {k: v for k, v in mine.items() if k not in ("name", "trace_calls")
            } == {k: v for k, v in one.items()
                  if k not in ("name", "trace_calls")}
    assert (mine["name"], mine["trace_calls"], one["trace_calls"]) == (
        TRAFFIC, 1, 2)
    # One traced call of a chip's 8 tiles is the two large cells' two
    # calls of 16, in tile-rounds.
    assert 1 * 8 * 4 * mine["rounds_per_call"] == 2 * 16 * one[
        "rounds_per_call"]
    assert one["trace_calls"] * one["rounds_per_call"] == one[
        "period_rounds"]


def entries_rule(b: dict) -> None:
    """The five stand right after the 43 entries PR 38's file had, in
    their order, for this cell alone."""
    own_entries(b, FIVE, 43, CELL)


def test_the_five_are_live_for_this_cell_alone():
    assert listed_cells(FIVE) == {name: [CELL] for name in FIVE}
    b = bench()
    entries_rule(b)
    layers = {m["layer"] for m in b["per_layer"][:43]}
    for m in b["per_layer"]:
        if m["name"] not in FIVE:
            continue
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["layer"] in layers
        assert m["moves"] == "group_rounds_per_s"
        spec = load("layer_metrics", m["name"])
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (m["name"], m["unit"], m["layer"], m["moves"])
        assert spec["reader"].startswith("nodes.") and "workloads" not in spec
    got = {m["name"]: (m["unit"], m["better"], m["source"])
           for m in b["per_layer"] if m["name"] in FIVE}
    assert got == {
        "ici.exchange_pct": ("%", "lower", "device_trace"),
        "ici.agree_pct": ("%", "lower", "device_trace"),
        "ici.lanes_run": ("lanes", "lower", "program_counter"),
        "ici.mb_per_round": ("MB", "lower", "program_counter"),
        "ici.roofline_pct": ("%", "higher", "device_trace")}


def test_route_entries_list_the_cells_whose_program_routes():
    """``route()`` does not run between nodes, so a traced run of this
    cell has no ``raft_route`` scope and the two entries that read it
    find nothing there: each lists every one-chip cell, the five PR 36
    found first, and not this one. The shares of the round its placed
    program does run list it (``test_lists.py`` holds each to what the
    cell's run gives), and every entry without a list reaches it by
    itself: at least the eleven, by name."""
    b = bench()
    rows = {m["name"]: m for m in b["per_layer"]}
    one_chip = [w["name"] for w in b["workloads"] if w["chips"] == 1]
    for name in ("round.route_pct", "route.roofline_pct"):
        assert rows[name]["workloads"][:5] == CELLS_AT_36
        assert rows[name]["workloads"] == one_chip
    cell = harness.Cell(REPO, CELL)
    mine = {s["name"] for s in cell.per_layer}
    assert set(FIVE) <= mine
    assert not {"round.route_pct", "route.roofline_pct"} & mine
    unlisted = {m["name"] for m in b["per_layer"] if "workloads" not in m}
    assert UNLISTED <= unlisted <= mine == reaches(b, CELL)
    assert mine >= unlisted | set(FIVE) | shared_with(b, CELL)
    assert {"round.tick_pct", "round.control_pct", "round.propose_pct",
            "round.emit_pct", "round.unscoped_pct", "round.telemetry_pct",
            "round.lanes_run", "scan.tiles_pct", "scan.watch_pct",
            "scan.carry_pct", "setup.pretrace_s"} <= shared_with(b, CELL)
    one = {s["name"] for s in harness.Cell(REPO, ONE_CHIP_CELL).per_layer}
    assert {"round.route_pct", "route.roofline_pct"} <= one
    assert not set(FIVE) & one


# -- bytes from shapes ------------------------------------------------------------------

E4 = [21, 49, 17, 10, 22, 13]  # step.lane_slot_bytes(4), by kind lane


def test_bytes_a_chip_sends_by_hand():
    """At the cell's size: a tile of 131,072 groups, R=4, E=4. A slot of
    a lane is the fields that lane carries (PR 48: 10 to 49 bytes, the
    append lane's with 4 words of entries); a chip sends three peers
    their slot of every group, once."""
    assert roofline_ici.sent_bytes([1, 0, 0, 0, 0, 0], 131_072, 4, E4) == (
        131_072 * 3 * 21) == 8_257_536
    assert roofline_ici.sent_bytes([0, 1, 0, 0, 0, 0], 131_072, 4, E4) == (
        131_072 * 3 * 49) == 19_267_584
    # A round in which the append, heartbeat and their response lanes
    # cross in all 8 tiles: 101 bytes a slot where the count until PR
    # 52 had 50 + 3 x 34 = 152.
    runs = [0, 8, 8, 0, 8, 8]
    assert roofline_ici.sent_bytes(runs, 131_072, 4, E4) == (
        1_048_576 * 3 * (49 + 17 + 22 + 13)) == 317_718_528
    # The peak is the published 1,600 Gbit/s of the chip, and an
    # unknown device has none.
    assert roofline_ici.ici_peak("TPU v5 lite") == 200e9
    with pytest.raises(KeyError):
        roofline_ici.ici_peak("cpu")
    assert roofline_ici.roofline_pct(317_718_528, 0.01, "TPU v5 lite") == (
        pytest.approx(100 * 317_718_528 / 200e9 / 0.01))
    assert roofline_ici.roofline_pct(1.0, 0.0, "TPU v5 lite") is None
    for gone in ("slot_bytes", "lane_run_bytes", "KIND_APP"):
        assert not hasattr(roofline_ici, gone)


# -- the readers --------------------------------------------------------------------------


def ctx_of(traced=1, scope_s=None):
    """Four window calls of 64 rounds in 8 tiles, then `traced` calls:
    every call crosses the four steady lanes in every tile-round and
    the two vote lanes in 8 tile-rounds."""
    per_call = [8, 512, 512, 8, 512, 512]
    after = [[n * k for k in per_call] for n in range(1, 7 + traced)]
    ctx = {"raw": {"rounds_per_call": 64, "traced_calls": traced, "ici": {
        "after_call": after, "open": 1, "close": 5, "tile_rows": 131_072,
        "tiles": 8, "replicas": 4, "slot_bytes": E4}},
        "device": {"kind": "TPU v5 lite"}}
    if scope_s is not None:
        ctx["trace"] = {"scope_s": scope_s, "leaf_s": sum(scope_s.values()),
                        "modules": {}}
    return ctx


def test_readers_on_counts_made_by_hand(capsys):
    ctx = ctx_of(scope_s={"raft_ici": 0.5, "raft_agree": 0.02,
                          "raft_deliver": 1.48})
    lanes = (4 * 512 + 2 * 8) / 512
    assert reader.lanes_run(ctx) == pytest.approx(lanes)
    sent = (64 * 8 * 131_072 * 3 * (49 + 17 + 22 + 13)
            + 8 * 131_072 * 3 * (21 + 10))
    assert reader.mb_per_round(ctx) == pytest.approx(sent / 64 / 1e6)
    assert reader.exchange_pct(ctx) == pytest.approx(25.0)
    assert reader.agree_pct(ctx) == pytest.approx(1.0)
    # The traced call's own bytes over the chip's seconds under the
    # scope: 20.4 GB in half a second against 200 GB/s.
    assert reader.roofline_pct(ctx) == pytest.approx(
        100 * (sent / 200e9) / 0.5)
    assert 0 < reader.roofline_pct(ctx) < 100
    # The line says what the share was counted with: the bytes, the
    # lanes that crossed in the traced call and a slot's bytes by lane.
    said = [json.loads(ln.split("] ", 1)[1])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench:roofline] ")]
    assert said and all(line == said[0] for line in said)
    assert said[0]["bytes_sent"] == sent
    assert said[0]["lane_runs"] == [8, 512, 512, 8, 512, 512]
    assert said[0]["slot_bytes"] == E4
    assert (said[0]["tile_rows"], said[0]["replicas"]) == (131_072, 4)
    two = ctx_of(traced=2, scope_s={"raft_ici": 1.0})
    assert reader.roofline_pct(two) == pytest.approx(
        100 * (2 * sent / 200e9) / 1.0)


def test_readers_find_nothing_where_nothing_crossed():
    """Another driver's run has no ``ici`` counts and its trace no such
    scope; a run without a trace has neither share; a window of no call
    divides by nothing. The metric is left out and nothing raises."""
    bare = {"raw": {"rounds_per_call": 64,
                    "occupancy": {"before": {"lanes": [0] * 6}}},
            "device": {"kind": "TPU v5 lite"}}
    routed = dict(bare, trace={"scope_s": {"raft_route": 1.0}, "leaf_s": 1.0,
                               "modules": {}})
    for ctx in (bare, routed):
        for name in FIVE:
            fn = getattr(reader, name.split(".")[1])
            assert fn(ctx) is None, name
    untraced = ctx_of()
    assert reader.exchange_pct(untraced) is None
    assert reader.roofline_pct(untraced) is None
    assert reader.lanes_run(untraced) is not None
    empty = ctx_of()
    empty["raw"]["ici"]["close"] = empty["raw"]["ici"]["open"]
    assert reader.lanes_run(empty) is None
    assert reader.mb_per_round(empty) is None
    none_traced = ctx_of(traced=0, scope_s={"raft_ici": 1.0})
    assert reader.roofline_pct(none_traced) is None


# -- the reference -------------------------------------------------------------------------


def test_the_reference_is_the_one_chip_cells_and_knows_no_chip():
    assert issubclass(NodesCluster, ReplaceCluster)
    assert not set(vars(NodesCluster)) - {"__module__", "__doc__",
                                          "__firstlineno__",
                                          "__static_attributes__"}
    with open(os.path.join(REPO, "benchmark", "reference",
                           "shadow_replace_nodes.py")) as f:
        text = f.read()
    assert "import jax" not in text and "etcd_tpu" not in text
    assert engine_nodes.CONTROLS == engine_replace.CONTROLS
    assert issubclass(engine_nodes.Driver, engine_replace.Driver)


# -- the cell driven tiny, over four of the forced devices --------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("nodes")))


def test_the_cell_resolves_to_files_that_exist(root):
    c = harness.Cell(root, CELL)
    assert c.chips == 4
    assert c.module("drivers", c.config["driver"]).Driver is (
        engine_nodes.Driver)
    g = c.module("generators", c.traffic["generator"])
    assert g.make and g.run and g.preload
    assert {m["name"] for m in c.end_to_end} == {"group_rounds_per_s",
                                                 "setup_s"}
    for spec in c.per_layer:
        assert callable(c.reader(spec))


@pytest.fixture(scope="module")
def tiny_run(root):
    cell = harness.Cell(root, CELL)
    ctx, checks = harness.measure(cell, 2**31 + 40, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    return cell, ctx, checks


def test_the_tiny_run_is_correct_by_the_one_chip_cells_checks(tiny_run):
    _cell, ctx, checks = tiny_run
    assert verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks) and len(checks) == 32
    assert ctx["device"]["count"] >= 4
    raw = ctx["raw"]
    assert raw["calls"] % 2 == 0 and raw["failed"] == 0
    assert raw["group_rounds_per_s"] > 0 and raw["setup_s"] > 0


def test_each_reader_on_a_tiny_run(tiny_run):
    cell, ctx, _checks = tiny_run
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values(layer)
    # No trace on the CPU: the two counters read, the three that need
    # the device's seconds are left out.
    assert {n for n in layer if n.startswith("ici.")} == {
        "ici.lanes_run", "ici.mb_per_round"}
    ici = ctx["raw"]["ici"]
    assert (ici["tiles"], ici["tile_rows"], ici["replicas"]) == (1, 8, 4)
    assert ici["slot_bytes"] == E4
    assert len(ici["after_call"]) == ici["close"] + 1 + ctx["raw"][
        "traced_calls"]
    assert ici["close"] - ici["open"] == ctx["raw"]["calls"]
    # What crossed is what the window's lane counter saw occupied, a
    # round a lane a call apart (the call's last outbox waits).
    occ = ctx["raw"]["occupancy"]
    occupied = sum(occ["after"]["lanes"]) - sum(occ["before"]["lanes"])
    rounds = ctx["raw"]["rounds"]
    assert layer["ici.lanes_run"]["value"] == pytest.approx(
        occupied / rounds, abs=6 * ctx["raw"]["calls"] / rounds)
    assert 3.0 < layer["ici.lanes_run"]["value"] < 4.5
    # 8 rows a tile: 8 x 3 slots a lane run.
    runs = [b - a for a, b in zip(ici["after_call"][ici["open"]],
                                  ici["after_call"][ici["close"]])]
    assert layer["ici.mb_per_round"]["value"] == pytest.approx(
        sum(n * 8 * 3 * E4[k] for k, n in enumerate(runs)) / rounds / 1e6)
    with_trace = dict(ctx, trace={
        "scope_s": {"raft_ici": 1.0, "raft_agree": 1.0}, "leaf_s": 4.0,
        "modules": {}}, device={"kind": "TPU v5 lite"})
    with_trace["raw"] = dict(ctx["raw"], traced_calls=1)
    traced = harness.per_layer_metrics(cell, with_trace)
    assert set(FIVE) <= set(traced)
    assert traced["ici.exchange_pct"]["value"] == 25.0
    assert 0 < traced["ici.roofline_pct"]["value"] < 100
    harness.refuse_bad_values(traced)


def test_a_program_whose_engine_takes_no_nodes_fails_at_once(root,
                                                            monkeypatch):
    """The parent: its ``MultiRaftEngine`` knows no ``nodes``. The
    driver says so before it builds anything, in seconds."""
    from etcd_tpu.batched import MultiRaftEngine

    def init(self, cfg, start_index=0, spare=None):
        raise AssertionError("not reached")

    monkeypatch.setattr(MultiRaftEngine, "__init__", init)
    cell = harness.Cell(root, CELL)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="takes no nodes"):
        harness.measure(cell, 4, 0.3, False, time.perf_counter(),
                        require_tpu=False)
    assert time.perf_counter() - t0 < 5
