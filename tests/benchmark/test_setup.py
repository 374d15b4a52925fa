"""ISSUE 38's side of the benchmark: the seven per-layer entries that
read the closed-loop engine's new scopes (``scan.*_pct``, the reader
that is there) and JAX's compile phases as spans (``setup.*_s``,
``readers/setup.py``): each entry against ``test_layers.py``'s rules,
the shares of a cell adding up to 100 again with what was taken out of
``unscoped``, the new reader on hand-made spans and on tiny runs, and
``readers/spans.py`` reading what it read with ``engine.pretrace``
among the engine's spans."""

import json
import os
import time

import pytest

from benchmark import harness
from benchmark.readers import setup as setup_reader
from benchmark.readers import spans as span_reader
from etcd_tpu.obs.spans import SpanRec

from . import test_layers
from .test_layers import SCOPE_S, SHARES, bench_of, entry_rule, shares
from .util import CELLS_AT_36 as CELLS
from .util import REPO, in_workloads_order, tiny_root

# The cells each of the seven listed when PR 38 wrote it: it lists them
# still, first; what cell came after is listed by the rule
# ``test_lists.py`` holds (the cell's program runs the scope, its
# set-up has the span).
LARGE = CELLS[3:]
SCAN = {"scan.tiles_pct": ("raft_tiles", LARGE),
        "scan.watch_pct": ("raft_watch", LARGE),
        "scan.carry_pct": ("raft_carry", CELLS)}
SETUP = {"setup.jax_trace_s": CELLS, "setup.jax_compile_s": CELLS,
         "setup.pretrace_s": LARGE, "setup.unspanned_s": CELLS}
NEW = [*SCAN, *SETUP]


# -- the entries -------------------------------------------------------------------


def test_the_seven_are_appended_after_the_36_and_nothing_else_moved():
    b = bench_of(REPO)
    rows = b["per_layer"]
    assert [m["name"] for m in rows[36:36 + len(NEW)]] == NEW
    for m in rows[36:36 + len(NEW)]:
        scan = m["name"] in SCAN
        were = SCAN[m["name"]][1] if scan else SETUP[m["name"]]
        assert m == {
            "name": m["name"], "unit": "%" if scan else "s",
            "better": "lower",
            "source": "device_trace" if scan else "program_span",
            "layer": "closed-loop engine" if scan else "compile",
            "moves": "group_rounds_per_s" if scan else "setup_s",
            "workloads": m["workloads"]}
        assert [c for c in m["workloads"] if c in CELLS] == were
        assert m["workloads"][:len(were)] == were
        assert in_workloads_order(b, m)


@pytest.mark.parametrize("name", NEW)
def test_entry_resolves_and_reaches_its_cells_alone(name, monkeypatch):
    if name in SETUP:
        # `setup_s` lists no cells (every cell reports it): the rule's
        # one line that reads the moved metric's list is handed the
        # list that absence stands for.
        real = test_layers.bench_of

        def bench_of(root):
            b = real(root)
            for e in b["end_to_end"]:
                if e["name"] == "setup_s":
                    assert "workloads" not in e
                    e["workloads"] = [w["name"] for w in b["workloads"]]
            return b

        monkeypatch.setattr(test_layers, "bench_of", bench_of)
    entry_rule(REPO, name)
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    if name in SCAN:
        assert spec["reader"] == "trace.scope_pct"
        assert spec["params"] == {"scope": SCAN[name][0]}
    else:
        assert spec["reader"] == name and spec["params"] == {}


@pytest.mark.parametrize("name", CELLS)
def test_the_shares_add_up_to_100_with_what_left_unscoped(name):
    """The engine's scopes taken out of ``unscoped``: the round's eight
    and the cell's ``scan.*`` leave nothing, and ``round.unscoped_pct``
    goes on reading ``reduce/trace.py``'s ``unscoped``, now lower."""
    cell = harness.Cell(REPO, name)
    split = {"unscoped": 1.0, "raft_carry": 0.484}
    if name in LARGE:
        split.update(unscoped=0.8, raft_tiles=0.1, raft_watch=0.1)
    scope_s = dict(SCOPE_S, **split)
    assert sum(scope_s.values()) == pytest.approx(sum(SCOPE_S.values()))
    if not cell.config["sizes"].get("telemetry"):
        del scope_s["raft_telemetry"]
    got = shares(cell, scope_s)
    assert set(got) >= {n for n, (_s, cells) in SCAN.items()
                        if name in cells}
    assert not {n for n, (_s, cells) in SCAN.items()
                if name not in cells} & set(got)
    assert sum(got.values()) == pytest.approx(100.0)
    total = sum(scope_s.values())
    assert got["round.unscoped_pct"] == pytest.approx(
        100.0 * split["unscoped"] / total)
    assert got["scan.carry_pct"] == pytest.approx(100.0 * 0.484 / total)
    # On the parent's program the trace holds none of the three: the
    # entries are left out and the eight read what they read.
    old = {k: v for k, v in SCOPE_S.items() if k in scope_s}
    assert set(shares(cell, old)) <= set(SHARES)


# -- the reader, on hand-made spans ----------------------------------------------

S = 1_000_000_000  # ns


def rec(name, t0, t1, seq, parent=-1, round=-1, **stats):
    return SpanRec(name, seq, parent, 0, round, int(t0 * S), int(t1 * S),
                   -1, stats or None, 0)


def engine_call(name, t0, t1, call, **stats):
    return rec(name, t0, t1, 100 + call, round=call, engine=7, **stats)


# One tiled engine's set-up and a window of two calls, in seconds: the
# init (a small program compiled in it), the campaign (its program
# traced round a pre-trace, lowered, fetched), the settle scan (traced
# round a pre-trace, lowered, compiled), the warm-up scan, the window.
HAND = [
    rec("compile.trace", 0.5, 0.6, 1, fun_name="zeros"),          # bare
    rec("compile.backend", 0.6, 0.9, 2, fun_name="jit(zeros)", hit=1),
    engine_call("engine.init", 1.0, 2.0, 0),
    rec("compile.trace", 2.6, 5.0, 5, 103, 1, fun_name="step_round"),
    rec("engine.pretrace", 2.5, 5.1, 4, 101, 1, engine=7),
    rec("compile.trace", 2.2, 5.5, 3, 101, 1, fun_name="step_round"),
    rec("compile.lower", 5.5, 6.0, 6, 101, 1, fun_name="jit(step_round)"),
    rec("compile.backend", 6.0, 6.5, 7, 101, 1,
        fun_name="jit(step_round)", hit=1),
    engine_call("engine.step_round", 2.0, 7.0, 1),
    rec("engine.pretrace", 8.1, 8.2, 9, 102, 2, engine=7),
    rec("compile.trace", 8.0, 9.0, 8, 102, 2, fun_name="closed_loop"),
    rec("compile.lower", 9.0, 10.0, 10, 102, 2,
        fun_name="jit(closed_loop)"),
    rec("compile.backend", 10.0, 14.0, 11, 102, 2,
        fun_name="jit(closed_loop)", hit=0),
    engine_call("engine.run_rounds", 8.0, 14.5, 2, rounds=64),
    engine_call("engine.run_rounds", 16.0, 16.1, 3, rounds=64),
    # The window: its first call opens at 18.0.
    engine_call("engine.run_rounds", 18.0, 18.1, 4, rounds=64),
    rec("compile.backend", 18.5, 18.6, 12, fun_name="jit(late)", hit=0),
    engine_call("engine.run_rounds", 19.0, 19.1, 5, rounds=64),
    # Ended after the window's first call opened, though begun before.
    rec("compile.trace", 17.9, 18.05, 13, fun_name="straddles"),
]


@pytest.fixture
def hand(monkeypatch):
    monkeypatch.setattr(span_reader, "_snapshot", lambda: list(HAND))
    return {"raw": {"calls": 2, "traced_calls": 0, "setup_s": 17.5}}


def test_set_up_ends_where_the_windows_first_call_opens(hand):
    got = setup_reader._setup(hand)
    names = {s.stats["fun_name"] for v in got.values() for s in v
             if s.name.startswith("compile.")}
    assert names == {"zeros", "jit(zeros)", "step_round",
                     "jit(step_round)", "closed_loop", "jit(closed_loop)"}
    assert len(got["engine.run_rounds"]) == 2  # settle and warm-up


def test_a_trace_inside_a_trace_counts_once(hand):
    # 0.1 (zeros) + 3.3 (the eager round, its callee's 2.4 inside it)
    # + 1.0 (the scan).
    assert setup_reader.jax_trace_s(hand) == pytest.approx(4.4)


def test_compile_seconds_and_the_dearest_programs_by_name(hand, capsys):
    # lower 0.5 + 1.0, backend 0.3 + 0.5 + 4.0.
    assert setup_reader.jax_compile_s(hand) == pytest.approx(6.3)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("[bench:setup_programs] ")]
    assert len(line) == 1
    said = json.loads(line[0].split(" ", 1)[1])
    assert (said["programs"], said["compiled"], said["fetched"]) == (3, 1, 2)
    assert said["trace_events_s"] == pytest.approx(0.1 + 2.4 + 3.3 + 1.0)
    assert [p["name"] for p in said["dearest"]] == [
        "closed_loop", "step_round", "zeros"]
    scan = said["dearest"][0]
    assert scan["hit"] is False and scan["backend_s"] == pytest.approx(4.0)
    assert scan["lower_s"] == pytest.approx(1.0)
    assert scan["trace_s"] == pytest.approx(1.0)
    assert said["dearest"][1]["hit"] is True


def test_pretrace_seconds(hand):
    assert setup_reader.pretrace_s(hand) == pytest.approx(2.7)


def test_unspanned_is_setup_less_the_union_of_its_spans(hand):
    # Covered: 0.5-0.9, 1.0-7.0, 8.0-14.5, 16.0-16.1 = 13.0 of 17.5;
    # nothing nested is counted twice, nothing after 18.0 at all.
    assert setup_reader.unspanned_s(hand) == pytest.approx(4.5)


def test_readers_spans_reads_what_it_read_with_the_pretrace_among_them(
        hand, monkeypatch):
    with_it = span_reader._engine(dict(hand))
    monkeypatch.setattr(
        span_reader, "_snapshot",
        lambda: [s for s in HAND if s.name != "engine.pretrace"])
    without = span_reader._engine(dict(hand))
    assert with_it == without
    assert [s.round for s in with_it["init"]] == [0]
    assert [s.round for s in with_it["elect"]] == [1]
    assert [s.round for s in with_it["scans"]] == [2, 3, 4, 5]
    assert [s.round for s in with_it["window"]] == [4, 5]
    for fn in (span_reader.setup_engine_init_s, span_reader.setup_elect_s,
               span_reader.setup_first_scan_s,
               span_reader.engine_dispatch_ms):
        assert fn(dict(hand)) is not None


def test_a_program_without_the_spans_leaves_the_metrics_out(monkeypatch):
    """The parent commit records ``engine.*`` alone: the three that
    read what this PR adds return None; what no span covers is still
    ``setup_s`` less the engine's spans."""
    monkeypatch.setattr(
        span_reader, "_snapshot",
        lambda: [s for s in HAND if s.name.startswith("engine.")
                 and s.name != "engine.pretrace"])
    ctx = {"raw": {"calls": 2, "traced_calls": 0, "setup_s": 17.5}}
    assert setup_reader.jax_trace_s(ctx) is None
    assert setup_reader.jax_compile_s(ctx) is None
    assert setup_reader.pretrace_s(ctx) is None
    assert setup_reader.unspanned_s(ctx) == pytest.approx(17.5 - 12.6)
    # No recorder at all, or no engine's window: nothing to read.
    monkeypatch.setattr(span_reader, "_snapshot", lambda: None)
    for fn in (setup_reader.jax_trace_s, setup_reader.jax_compile_s,
               setup_reader.pretrace_s, setup_reader.unspanned_s):
        assert fn({"raw": {"calls": 2, "setup_s": 1.0}}) is None
        assert fn({"raw": {}}) is None


# -- and on tiny runs ----------------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("setup")))


@pytest.mark.parametrize("name, tiles", [(CELLS[0], 1), (CELLS[4], 2)])
def test_the_new_readers_on_a_tiny_run(root, name, tiles, monkeypatch):
    """One cell in one scan and one in tiles (the tile constants are
    the test's to patch, as in ``tests/batched/test_scan_tiles.py``):
    the four entries through the harness, beside the five that were
    there, which read the same spans in the same order."""
    from etcd_tpu.batched import engine as engine_mod

    cell = harness.Cell(root, name)
    rows = 8 * int(cell.config["sizes"]["num_replicas"])
    monkeypatch.setattr(engine_mod, "TILE_ALIGN", 1)
    monkeypatch.setattr(engine_mod, "TILE_ROWS",
                        rows // tiles if tiles > 1 else 1 << 40)
    # This process has run other tests: set-up is what this run did.
    mark, real = time.monotonic_ns(), span_reader._snapshot
    monkeypatch.setattr(
        span_reader, "_snapshot",
        lambda: [s for s in real() if s.t0 >= mark])
    ctx, checks = harness.measure(cell, 2**31 + 38, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    assert harness.verdict(checks), [c for c in checks if not c.ok]
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values(layer)
    e = span_reader._engine(ctx)
    assert [s.stats["tiles"] for s in e["scans"]] == [tiles] * len(e["scans"])
    want = {n for n, cells in SETUP.items() if name in cells}
    assert want == {n for n in SETUP if n in layer}
    assert all(layer[n]["unit"] == "s" for n in want)
    setup_s = ctx["raw"]["setup_s"]
    trace, compiled, bare = (layer[n]["value"] for n in (
        "setup.jax_trace_s", "setup.jax_compile_s", "setup.unspanned_s"))
    assert 0.0 < trace < setup_s and 0.0 < compiled < setup_s
    assert 0.0 <= bare < setup_s
    if tiles > 1:
        assert 0.0 < layer["setup.pretrace_s"]["value"] <= trace
    # The three spans over set-up that were there lie inside it, and
    # the window's calls are the driver's.
    old = sum(layer[n]["value"] for n in (
        "setup.engine_init_s", "setup.elect_s", "setup.first_scan_s"))
    assert 0.0 < old < setup_s
    assert len(e["window"]) == ctx["raw"]["calls"]
    assert len(e["init"]) == 1 and e["elect"]
    assert not [s for s in e["scans"] + e["elect"] + e["init"]
                if s.name == "engine.pretrace"]
