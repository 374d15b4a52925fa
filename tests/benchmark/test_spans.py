"""The span layer's side of the benchmark (ISSUE 24): the sharing-out of
device idle gaps among host spans on hand-made intervals and on the
recorded chip trace, the rule that holds PR 24's five live entries where
they are and lets a later PR append (shown open and shown tight on a
temporary copy of ``BENCHMARK.json``), the parked span metrics against
the contract, and each new reader on a tiny run."""

import gzip
import json
import os
import shutil
import time

import pytest

from benchmark import harness
from benchmark.readers import spans as span_readers
from benchmark.reduce.gaps import (
    UNSPANNED,
    host_clock_lead,
    idle_gaps,
    innermost,
    read_xplane,
    reduce_gaps,
    share_out,
    span_rows,
    table,
)
from benchmark.reduce.trace import MIN_GAP_NS, _union, reduce_trace

from .test_contract import NAME, SOURCES, UNIT, WITH_PARKED
from .util import (REPO, XPROF, _edit, bench, edited_copy, swap,
                   tiny_root)

# 0.3 s of a G=8 served cluster under 16 putting clients on the chip
# (TPU v5 lite): the trace ``tools/round_gaps.py`` kept of
# ``served1k-r3.put``, seed 24, 3 s, with the cell's files cut to 8
# groups, 16 clients and a 0.3 s trace (my chip run, PR 24), gzipped.
SPAN_XPLANE = os.path.join(REPO, "artifacts", "tpu_r24_spans",
                           "g8_served_put.xplane.pb.gz")
# PR 24's five live entries, as ``BENCHMARK.json`` has to hold them.
LIVE_ENTRIES = [
    {"name": name, "unit": unit, "better": "lower",
     "source": "program_span", "layer": layer, "moves": moves}
    for name, unit, layer, moves in (
        ("engine.dispatch_ms", "ms", "closed-loop engine",
         "group_rounds_per_s"),
        ("engine.late_ms", "ms", "closed-loop engine",
         "group_rounds_per_s"),
        ("setup.engine_init_s", "s", "compile", "setup_s"),
        ("setup.elect_s", "s", "compile", "setup_s"),
        ("setup.first_scan_s", "s", "compile", "setup_s"))]
LIVE = [m["name"] for m in LIVE_ENTRIES]


def served_spans() -> dict:
    with open(os.path.join(REPO, "benchmark", "parked",
                           "served_spans.json")) as f:
        return json.load(f)


PARKED = served_spans()["per_layer"]

# -- the sharing-out, on hand-made intervals -------------------------------------

ROUND = [(0, 100, "member.round"), (10, 30, "rawnode.stage"),
         (30, 50, "rawnode.h2d"), (60, 90, "rawnode.extract"),
         (120, 130, "member.idle_wait")]
DRAIN = [(0, 40, "member.wal"), (20, 40, "member.fsync")]

CASES = {
    "nested spans, one thread": (
        [(20, 70)], {1: ROUND},
        {"rawnode.stage": 10, "rawnode.h2d": 20, "member.round": 10,
         "rawnode.extract": 10}),
    "two threads share each instant": (
        [(20, 70)], {1: ROUND, 2: DRAIN},
        {"rawnode.stage": 5, "member.fsync": 10, "rawnode.h2d": 15,
         "member.round": 10, "rawnode.extract": 10}),
    "a gap under no span": (
        [(200, 210)], {1: ROUND, 2: DRAIN}, {UNSPANNED: 10}),
    "a gap half under a span": (
        [(95, 125)], {1: ROUND},
        {"member.round": 5, UNSPANNED: 20, "member.idle_wait": 5}),
    "no host thread at all": ([(0, 7)], {}, {UNSPANNED: 7}),
}


@pytest.mark.parametrize("case", CASES, ids=list(CASES))
def test_share_out(case):
    gaps, threads, want = CASES[case]
    total, per_gap = share_out(gaps, threads)
    assert total == pytest.approx(want)
    assert len(per_gap) == len(gaps)
    for (a, b), cover in zip(gaps, per_gap):
        assert sum(cover.values()) == pytest.approx(b - a)


def test_share_out_adds_gaps_up():
    gaps = [(20, 70), (95, 125), (200, 210)]
    total, per_gap = share_out(gaps, {1: ROUND, 2: DRAIN})
    assert sum(total.values()) == pytest.approx(90)
    for name in total:
        assert total[name] == pytest.approx(
            sum(c.get(name, 0) for c in per_gap))


def test_innermost_flattens_a_call_stack():
    assert innermost(ROUND) == [
        (0, 10, "member.round"), (10, 30, "rawnode.stage"),
        (30, 50, "rawnode.h2d"), (50, 60, "member.round"),
        (60, 90, "rawnode.extract"), (90, 100, "member.round"),
        (120, 130, "member.idle_wait")]
    # A child that closes a tick after its parent (two clock reads)
    # still gives stretches that do not overlap.
    flat = innermost([(0, 10, "a"), (5, 11, "b")])
    assert flat == [(0, 5, "a"), (5, 11, "b")]


def test_idle_gaps_are_reduce_traces_gaps():
    ops = [(0.0, 100.0, "%fusion.1 = f32[] fusion()"),
           (100.5, 50.0, "%copy.2 = f32[] copy()"),  # under MIN_GAP_NS
           (5000.0, 10.0, "%copy.3 = f32[] copy()")]
    assert MIN_GAP_NS > 0.5
    assert idle_gaps(ops) == [(150.5, 5000.0, "copy")]


def test_host_clock_lead_is_the_largest_enqueue_minus_start():
    # Three programs: the second waited 40 for the first to end, so it
    # reads 30 lower; the lead is what the promptest program shows.
    starts, enqueues = [100.0, 300.0, 900.0], [170.0, 330.0, 968.0]
    assert host_clock_lead(starts, enqueues) == 70.0
    assert host_clock_lead(starts[::-1], enqueues) == 70.0
    assert host_clock_lead(starts, enqueues[:2]) is None
    assert host_clock_lead([], []) is None


# -- the extraction, on the recorded chip trace ------------------------------------


@pytest.fixture(scope="module")
def chip(tmp_path_factory):
    if not os.path.exists(SPAN_XPLANE):
        pytest.skip("artifacts/tpu_r24_spans is not in this checkout")
    path = str(tmp_path_factory.mktemp("xplane") / "g8.xplane.pb")
    with gzip.open(SPAN_XPLANE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return reduce_gaps(path, top=5), reduce_trace(path)


def test_chip_trace_holds_the_spans_on_the_devices_clock(chip):
    devices, threads, lead = read_xplane(chip[0]["xplane"])
    assert lead == pytest.approx(372441.0)  # ns: the host's clock ahead
    assert len(devices) == 1 and devices[0]
    names = {n for evs in threads.values() for _s, _e, n in evs}
    assert {"member.round", "rawnode.h2d", "rawnode.dispatch",
            "rawnode.fence", "rawnode.d2h", "member.wal",
            "member.apply"} <= names
    # The two planes share one clock: the device is busy for 1.08 ms of
    # these 300, and all of it but the round the trace began in lies
    # between some member's dispatch and the end of its fence.
    _busy, merged = _union([(s, s + d) for s, d, _n in devices[0]])
    fed = []
    for evs in threads.values():
        fences = sorted(e for e in evs if e[2] == "rawnode.fence")
        for d in (e for e in evs if e[2] == "rawnode.dispatch"):
            after = [f for f in fences if f[0] >= d[1] - 1]
            if after:
                fed.append((d[0], after[0][1]))
    inside = sum(1 for a, b in merged
                 if any(s <= a and b <= e for s, e in fed))
    assert len(fed) == 15 and len(merged) == 4644
    assert inside / len(merged) > 0.95


def test_every_gap_second_of_the_chip_trace_is_attributed(chip):
    gaps, red = chip
    assert gaps["devices"] == red["devices"] == 1
    assert (gaps["gaps"], gaps["host_threads"], gaps["host_spans"]) == (
        31, 6, 210)
    assert gaps["gap_s"] == pytest.approx(0.244485816)
    assert gaps["gap_s"] == pytest.approx(red["gap_s"], rel=1e-9)
    assert sum(gaps["by_span_s"].values()) == pytest.approx(gaps["gap_s"])
    assert 0.0 <= gaps["unspanned_pct"] < 5.0
    for row in gaps["longest"]:
        assert sum(row["spans_ms"].values()) == pytest.approx(row["ms"])
    assert "| `" in table(gaps)


# -- the result line's ``idle_gaps``: rows named by host span -----------------------

# The G=8 served trace's spans with most device idle seconds, in order.
SERVED_ROWS = ["rawnode.dispatch", "rawnode.d2h", "rawnode.stage",
               "member.round", "member.send"]


def test_rows_of_the_served_chip_trace_are_named_by_host_span(chip):
    gaps, _red = chip
    rows = span_rows(gaps)
    names = [n for n, _v in rows]
    assert len(rows) == 10 < len(gaps["by_span_s"])
    assert names[:5] == SERVED_ROWS
    assert names[-1] == UNSPANNED, "kept though ten spans read longer"
    assert all(n == UNSPANNED or n.startswith(("member.", "rawnode."))
               for n in names)
    assert [v for _n, v in rows[:-1]] == sorted(
        (v for _n, v in rows[:-1]), reverse=True)
    assert all(v == gaps["by_span_s"][n] for n, v in rows)


def test_rows_of_a_trace_without_spans_are_all_unspanned():
    """``artifacts/tpu_r05/xprof`` was taken before the program had the
    span layer: every idle second falls under no span."""
    if not os.path.isdir(XPROF):
        pytest.skip("artifacts/tpu_r05/xprof is not in this checkout")
    gaps, red = reduce_gaps(XPROF), reduce_trace(XPROF)
    rows = span_rows(gaps)
    assert [n for n, _v in rows] == [UNSPANNED]
    assert rows[0][1] == pytest.approx(red["gap_s"], rel=1e-9)


def test_the_gaps_of_a_reduced_trace_are_those_read_anew(chip):
    """A traced run walks the device's ops once: ``reduce_gaps`` handed
    ``reduce_trace``'s result takes its gaps as they stand and reads
    the host's plane alone, and says what it says reading all anew."""
    gaps, red = chip
    again = reduce_gaps(red["xplane"], top=5, reduced=red)
    assert again == gaps
    assert sum(b - a for dev in red["idle_gaps"] for a, b, _n in dev) \
        == pytest.approx(red["gap_s"] * 1e9 * red["devices"])
    assert red["first_op_ns"] is not None
    if os.path.isdir(XPROF):
        old = reduce_trace(XPROF)
        assert reduce_gaps(XPROF, reduced=old) == reduce_gaps(XPROF)


def test_result_line_of_a_traced_run(chip, root):
    """``run_cell``'s second half on a tiny engine run, with the served
    chip trace put where a traced run's reduction would be (a CPU trace
    has no device plane): ``idle_gaps`` by host span, ``device_ops`` as
    they were, the numbers compared last in the line."""
    gaps, red = chip
    cell = harness.Cell(root, "engine64k-r3.append")
    ctx, checks = harness.measure(cell, 27, 0.3, False,
                                  time.perf_counter(), require_tpu=False)
    ctx.update(trace=red, gaps=gaps)
    line = harness._result(cell, ctx, checks, True)
    assert line["breakdown"]["idle_gaps"] == span_rows(gaps)
    assert line["breakdown"]["device_ops"] == red["device_ops"]
    assert list(line)[-1] == "checks" and line["correct"] is True
    assert line["checks"] == {c.name: {"value": c.value, "limit": c.limit}
                              for c in checks}
    assert line["device"]["busy_s"] == red["busy_s"]
    assert json.loads(json.dumps(line)) == line


# -- the parked entries against the contract ---------------------------------------


# What ``per_layer`` held before PR 24's five: they stand right after.
BEFORE_LIVE = ["round.device_ms", "round.route_pct", "round.deliver_pct",
               "route.roofline_pct", "engine.call_gap_ms",
               "device.hbm_peak_gb", "compile.in_window",
               "compile.cache_misses"]


def live_entries_rule(b: dict) -> None:
    """PR 24's five are present, unchanged (``program_span``, no
    ``workloads``), consecutive and in their order, right after the
    eight entries that were there before them. What follows them is
    any later PR's to append: the rule says nothing of it."""
    rows = b["per_layer"]
    at = len(BEFORE_LIVE)
    assert [m["name"] for m in rows[:at]] == BEFORE_LIVE
    assert rows[at:at + len(LIVE)] == LIVE_ENTRIES
    assert not set(LIVE) & {m["name"] for m in rows[at + len(LIVE):]}


def test_live_entries_are_present_unchanged_and_in_their_order():
    live_entries_rule(bench())


ONE_MORE = {"name": "engine.one_more_ms", "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "closed-loop engine",
            "moves": "group_rounds_per_s",
            "workloads": ["engine64k-r3.append"]}


# What the rule is for, and what it still refuses: index 8 is the
# first of the five, 12 the last.
OPEN = {
    "an entry appended": lambda b: b["per_layer"].append(dict(ONE_MORE)),
    "three entries appended": lambda b: b["per_layer"].extend(
        dict(ONE_MORE, name=f"engine.one_more_{i}_ms") for i in range(3)),
    "an appended entry removed again": lambda b: b["per_layer"].pop(),
}
TIGHT = {
    "one of the five removed": lambda b: b["per_layer"].pop(9),
    "one renamed": lambda b: b["per_layer"][10].update(
        name="setup.engine_build_s"),
    "two re-ordered": lambda b: swap(b["per_layer"], 11, 12),
    "a source changed": lambda b: b["per_layer"][8].update(
        source="host_clock"),
    "one given a list of cells": lambda b: b["per_layer"][12].update(
        workloads=["engine64k-r3.append"]),
    "one moved to the end": lambda b: b["per_layer"].append(
        b["per_layer"].pop(8)),
    "an entry put before them": lambda b: b["per_layer"].insert(
        3, dict(ONE_MORE)),
    "one of the five a second time": lambda b: b["per_layer"].append(
        dict(LIVE_ENTRIES[0])),
}


@pytest.mark.parametrize("edit", OPEN.values(), ids=OPEN.keys())
def test_the_rule_lets_a_later_pr_append(tmp_path, edit):
    live_entries_rule(edited_copy(tmp_path, edit))


@pytest.mark.parametrize("edit", TIGHT.values(), ids=TIGHT.keys())
def test_the_rule_holds_the_five_where_they_are(tmp_path, edit):
    with pytest.raises(AssertionError):
        live_entries_rule(edited_copy(tmp_path, edit))


@pytest.mark.parametrize("m", PARKED, ids=lambda m: m["name"])
def test_parked_span_metric_entry(m):
    assert set(served_spans()) == {"note", "per_layer"}
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert m["source"] in ("program_span", "program_counter")
    assert m["moves"] in {e["name"] for e in WITH_PARKED["end_to_end"]}
    assert m["name"] not in {x["name"] for x in WITH_PARKED["per_layer"]}
    known = {x["layer"] for x in WITH_PARKED["per_layer"]} | {"ReadIndex"}
    assert m["layer"] in known
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           m["name"] + ".json")) as f:
        spec = json.load(f)
    assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
        m["name"], m["unit"], m["layer"], m["moves"])
    mod, _, fn = spec["reader"].partition(".")
    assert mod == "spans" and callable(getattr(span_readers, fn))


# -- each new reader on a tiny run ---------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """``tiny_root`` with the span entries added the way ``add_parked``
    adds ``served.json``."""
    dst = tiny_root(str(tmp_path_factory.mktemp("spans")))
    _edit(os.path.join(dst, "BENCHMARK.json"),
          lambda b: b["per_layer"].extend(PARKED))
    return dst


def drive(root, workload, seed, seconds=0.8):
    cell = harness.Cell(root, workload)
    ctx, checks = harness.measure(cell, seed, seconds, False,
                                  time.perf_counter(), require_tpu=False)
    assert harness.verdict(checks), [c for c in checks if not c.ok]
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values(layer)
    return cell, ctx, layer


@pytest.fixture(scope="module")
def put_run(root):
    return drive(root, "served1k-r3.put", 24)


@pytest.mark.parametrize("m", PARKED, ids=lambda m: m["name"])
def test_parked_reader_on_a_tiny_put_run(put_run, m):
    _cell, _ctx, layer = put_run
    got = layer[m["name"]]
    assert got["unit"] == m["unit"] and got["value"] >= 0.0
    if m["unit"] == "%":
        assert 0.0 <= got["value"] <= 100.0


def test_span_metrics_agree_with_the_counters_they_sit_beside(put_run):
    _cell, ctx, layer = put_run
    w = span_readers._served(ctx)
    c = ctx["raw"]["counters"]
    for m, a, b in zip(w["members"], c["before"]["members"],
                       c["after"]["members"]):
        # Exactly the window's rounds, by the counter's own numbers.
        assert [s.round for s in m["member.round"]] == list(
            range(a["rounds"], b["rounds"]))
        secs = sum(s.t1 - s.t0 for s in m["member.round"]) / 1e9
        assert secs == pytest.approx(b["round_s"] - a["round_s"])
    step = sum(layer[k]["value"] for k in (
        "round.h2d_ms", "round.dispatch_ms", "round.fence_ms",
        "round.d2h_ms"))
    assert 0.0 < step < layer["member.round_ms"]["value"] * 3
    assert layer["read.unconfirmed"]["value"] == 0.0
    assert layer["member.leader_losses"]["value"] == 0.0
    assert layer["read.timeouts"]["value"] == 0.0
    assert 1.0 <= layer["member.ready_q_depth_max"]["value"] <= 4.0


def test_lread_run_counts_its_read_batches(root):
    _cell, ctx, layer = drive(root, "served1k-r3.lread", 25)
    opened = span_readers._counter(ctx, "read_opened")
    assert sum(b - a for a, b in opened) > 0
    assert layer["read.unconfirmed"]["value"] >= 0.0
    assert 0.0 <= layer["member.idle_wait_pct"]["value"] <= 100.0


def test_live_readers_on_a_tiny_engine_run(root):
    _cell, ctx, layer = drive(root, "engine64k-r3.append", 26, 0.4)
    assert set(LIVE) <= set(layer)
    e = span_readers._engine(ctx)
    assert len(e["window"]) == ctx["raw"]["calls"]
    assert len(e["scans"]) == ctx["raw"]["calls"] + 2  # settle, warm-up
    assert [s.stats["rounds"] for s in e["scans"]] == (
        [ctx["raw"]["rounds_per_call"]] * len(e["scans"]))
    assert len(e["init"]) == 1 and len(e["elect"]) == 1
    assert layer["engine.dispatch_ms"]["value"] <= (
        ctx["raw"]["call_s_median"] * 1e3)
    assert layer["engine.late_ms"]["value"] >= 0.0
    setup = sum(layer[k]["value"] for k in LIVE[2:])
    assert 0.0 < setup < ctx["raw"]["setup_s"]


def test_a_program_without_the_recorder_gives_no_span_metric(
        put_run, monkeypatch):
    """The parent commit has no ``etcd_tpu.obs.spans``: every reader
    then returns None and the line leaves the metric out."""
    import builtins

    real = builtins.__import__

    def no_spans(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "etcd_tpu.obs" and "spans" in (fromlist or ()):
            raise ImportError("No module named 'etcd_tpu.obs.spans'")
        return real(name, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_spans)
    cell, ctx, _layer = put_run
    bare = {k: v for k, v in ctx.items() if not k.startswith("_")}
    layer = harness.per_layer_metrics(cell, bare)
    assert "member.round_ms" in layer
    assert not {m["name"] for m in PARKED} & set(layer)
    eng = dict(bare, raw=dict(bare["raw"], calls=5, traced_calls=2))
    for fn in (span_readers.engine_dispatch_ms, span_readers.engine_late_ms,
               span_readers.setup_engine_init_s,
               span_readers.setup_elect_s,
               span_readers.setup_first_scan_s):
        assert fn(dict(eng)) is None
