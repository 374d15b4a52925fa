"""Per-layer metrics from the program's round-span recorder
(``etcd_tpu/obs/spans.py``), read in this process after the window.

The recorder is always on, so the rings hold set-up, the window and the
traced tail. The window's spans are picked out by counts the run
already has: for the engine, the last ``traced_calls``
``engine.run_rounds`` spans are the traced tail and the ``calls``
before them the window; for the served path, a member's window rounds
are those numbered from its ``rounds`` counter as the window opened up
to (not including) its value as the window closed — a span's ``round``
*is* that counter as the round started.

A program without the recorder (a parent commit), or a ring that holds
nothing a reader looks for, gives ``None``: the metric is left out.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from ..harness import say
from ..reduce.gaps import share_out
from ..reduce.trace import _union


def _snapshot() -> Optional[list]:
    try:
        from etcd_tpu.obs import spans
    except ImportError:
        return None
    return spans.snapshot()


def _ms(spans: Sequence) -> List[float]:
    return [(s.t1 - s.t0) / 1e6 for s in spans]


# -- the engine -------------------------------------------------------------------


def _engine(ctx) -> Optional[dict]:
    """The spans of this run's engine (the newest one), cached on the
    context: ``init``, ``elect`` (step_round spans before the first
    scan), ``scans`` (every run_rounds span, in order) and the window's
    slice of them."""
    if "_engine_spans" in ctx:
        return ctx["_engine_spans"]
    out = None
    snap = _snapshot()
    calls = ctx["raw"].get("calls")
    if snap and calls:
        # (A span of the prefix that names no engine is not one of an
        # engine's own: a driver's, or a later PR's.)
        mine = [s for s in snap if s.name.startswith("engine.")
                and s.stats and "engine" in s.stats]
        if mine:
            serial = max(s.stats["engine"] for s in mine)
            mine = sorted((s for s in mine if s.stats["engine"] == serial),
                          key=lambda s: s.round)
            scans = [s for s in mine if s.name == "engine.run_rounds"]
            traced = int(ctx["raw"].get("traced_calls", 0))
            end = len(scans) - traced
            if end - calls >= 0:
                first = scans[0].round if scans else 1 << 62
                out = {
                    "init": [s for s in mine if s.name == "engine.init"],
                    "elect": [s for s in mine
                              if s.name == "engine.step_round"
                              and s.round < first],
                    "scans": scans,
                    "window": scans[end - calls:end],
                }
    ctx["_engine_spans"] = out
    return out


def engine_dispatch_ms(ctx) -> Optional[float]:
    """Median host time to enqueue one scan of the window."""
    e = _engine(ctx)
    if not e or not e["window"]:
        return None
    return statistics.median(_ms(e["window"]))


def engine_late_ms(ctx) -> Optional[float]:
    """Over the window's calls, the sum of each start-to-start period's
    excess over the median period: what late calls cost the window.
    (The last call has no period: what follows it is the profiler
    session opening.)"""
    e = _engine(ctx)
    if not e or len(e["window"]) < 3:
        return None
    starts = [s.t0 for s in e["window"]]
    periods = [(b - a) / 1e6 for a, b in zip(starts, starts[1:])]
    med = statistics.median(periods)
    say("spans", engine_periods=len(periods), period_ms_median=med,
        period_ms_max=max(periods))
    return sum(max(0.0, p - med) for p in periods)


def setup_engine_init_s(ctx) -> Optional[float]:
    e = _engine(ctx)
    if not e or not e["init"]:
        return None
    return sum(_ms(e["init"])) / 1e3


def setup_elect_s(ctx) -> Optional[float]:
    e = _engine(ctx)
    if not e or not e["elect"]:
        return None
    return sum(_ms(e["elect"])) / 1e3


def setup_first_scan_s(ctx) -> Optional[float]:
    """The first scan's span plus the wait to the next span's start:
    tracing and compiling (or fetching) the scan, then its first run."""
    e = _engine(ctx)
    if not e or len(e["scans"]) < 2:
        return None
    return (e["scans"][1].t0 - e["scans"][0].t0) / 1e9


# -- the served path --------------------------------------------------------------


def _served(ctx) -> Optional[dict]:
    """Per member (id 1..n) the window's spans by name, and the window
    itself [first round's start, last round's end], cached.

    A process can have had other members of the same id (the restart
    check opens the cluster again; a test process runs many), one at a
    time and each with threads, hence rings, of its own. The window's
    member is the one whose ``member.round`` spans of the window's
    round numbers add up to the window's ``round_s`` delta; its other
    spans are those of the id and the round numbers that begin inside
    the window's time."""
    if "_served_spans" in ctx:
        return ctx["_served_spans"]
    out = None
    c = ctx["raw"].get("counters")
    snap = _snapshot()
    if snap and c and c.get("before") and c.get("after"):
        members = []
        for i, (a, b) in enumerate(zip(c["before"]["members"],
                                       c["after"]["members"])):
            lo, hi = a["rounds"], b["rounds"]
            mine = [s for s in snap
                    if s.member == i + 1 and lo <= s.round < hi]
            rings: Dict[int, list] = {}
            for s in mine:
                if s.name == "member.round":
                    rings.setdefault(s.thread, []).append(s)
            if not rings:
                continue
            want = b["round_s"] - a["round_s"]
            rounds = min(rings.values(), key=lambda v: abs(
                sum(s.t1 - s.t0 for s in v) / 1e9 - want))
            t0 = min(s.t0 for s in rounds)
            # A Ready is queued as its round closes: the last round's
            # queue wait begins just past the window's last instant.
            t1 = max(s.t1 for s in rounds) + 10_000_000
            by_name: Dict[str, list] = {}
            for s in sorted(mine, key=lambda s: s.t0):
                if t0 <= s.t0 <= t1:
                    by_name.setdefault(s.name, []).append(s)
            members.append(by_name)
        if members:
            out = {
                "members": members,
                "t0": min(m["member.round"][0].t0 for m in members),
                "t1": max(m["member.round"][-1].t1 for m in members),
            }
    ctx["_served_spans"] = out
    return out


def _thirds(spans: Sequence) -> List[Optional[float]]:
    """Median ms of the first, middle and last third (in time)."""
    n = len(spans)
    parts = [spans[:n // 3], spans[n // 3:2 * n // 3], spans[2 * n // 3:]]
    return [statistics.median(_ms(p)) if p else None for p in parts]


def round_span_ms(ctx, span: str, thirds: bool = False) -> Optional[float]:
    """Median over the members of the median ms of one span a round;
    with ``thirds`` the first, middle and last third of the window are
    printed too, so a phase that grows through a run reads off one."""
    w = _served(ctx)
    if w is None:
        return None
    vals = [statistics.median(_ms(m[span])) for m in w["members"]
            if m.get(span)]
    if not vals:
        return None
    if thirds:
        say("spans", span=span, ms_by_third_per_member=[
            _thirds(m.get(span, ())) for m in w["members"]])
    return statistics.median(vals)


def offcpu_pct(ctx, spans: Sequence[str]) -> Optional[float]:
    """100 x (1 - thread CPU / wall) summed over the named spans of the
    window: the share of pure-Python phases spent off the processor
    (the interpreter lock, a mutex)."""
    w = _served(ctx)
    if w is None:
        return None
    wall = cpu = 0
    for m in w["members"]:
        for name in spans:
            for s in m.get(name, ()):
                wall += s.t1 - s.t0
                cpu += s.cpu_ns
    if wall <= 0:
        return None
    # The two clocks tick apart: a span all on the processor can read a
    # few microseconds more CPU than wall.
    return max(0.0, 100.0 * (1.0 - cpu / wall))


def idle_wait_pct(ctx) -> Optional[float]:
    """``member.idle_wait`` over the round thread's wall, median over
    the members."""
    w = _served(ctx)
    if w is None:
        return None
    vals = []
    for m in w["members"]:
        rounds = m["member.round"]
        wall = rounds[-1].t1 - rounds[0].t0
        waits = [s for s in m.get("member.idle_wait", ())
                 if s.t0 >= rounds[0].t0]
        if wall > 0:
            vals.append(100.0 * sum(s.t1 - s.t0 for s in waits) / wall)
    return statistics.median(vals) if vals else None


def device_unfed_pct(ctx) -> Optional[float]:
    """Share of the window in which no member has a program in flight:
    the complement of the union, over members, of [``rawnode.h2d``
    start, ``rawnode.fence`` end]. The unfed time is also split by the
    round-thread span each member had open meanwhile (each instant
    shared equally among the members), on the ``[bench:spans]`` line."""
    w = _served(ctx)
    if w is None:
        return None
    fed = []
    for m in w["members"]:
        ends = {s.round: s.t1 for s in m.get("rawnode.fence", ())}
        fed.extend((s.t0, ends[s.round]) for s in m.get("rawnode.h2d", ())
                   if s.round in ends)
    t0, t1 = w["t0"], w["t1"]
    if t1 <= t0 or not fed:
        return None
    _fed_ns, merged = _union([(max(s, t0), min(e, t1)) for s, e in fed
                              if e > t0 and s < t1])
    unfed = [(a, b) for a, b in zip(
        [t0] + [e for _s, e in merged], [s for s, _e in merged] + [t1])
        if b > a]
    threads = {
        i: [(s.t0, s.t1, s.name) for name, v in m.items()
            if name.startswith("rawnode.") or name in (
                "member.round", "member.idle_wait") for s in v]
        for i, m in enumerate(w["members"])}
    by_span, _per_gap = share_out(unfed, threads)
    total = sum(b - a for a, b in unfed)
    say("spans", unfed_ms_by_span={
        n: v / 1e6 for n, v in sorted(by_span.items(),
                                      key=lambda r: -r[1])},
        unfed_ms=total / 1e6, window_ms=(t1 - t0) / 1e6)
    return 100.0 * total / (t1 - t0)


def _counter(ctx, key: str) -> Optional[List[Tuple[int, int]]]:
    """(value as the window's first round closed, as its last closed)
    of one cumulative counter carried on the member.round spans."""
    w = _served(ctx)
    if w is None:
        return None
    out = []
    for m in w["members"]:
        rounds = m["member.round"]
        if not rounds[0].stats or key not in rounds[0].stats:
            return None
        out.append((rounds[0].stats[key], rounds[-1].stats[key]))
    return out


def read_unconfirmed(ctx) -> Optional[float]:
    """ReadIndex batches opened and not confirmed as the window closes,
    summed over the members."""
    opened = _counter(ctx, "read_opened")
    confirmed = _counter(ctx, "read_confirmed")
    if opened is None or confirmed is None:
        return None
    return float(sum(o[1] - c[1] for o, c in zip(opened, confirmed)))


def counter_delta(ctx, key: str) -> Optional[float]:
    vals = _counter(ctx, key)
    if vals is None:
        return None
    return float(sum(b - a for a, b in vals))


def counter_high(ctx, key: str) -> Optional[float]:
    """A high-water mark (a counter that only ever takes its maximum)
    as the window closes, the highest over the members. It stands since
    the member started: a mark the window did not raise was set before
    it."""
    vals = _counter(ctx, key)
    if vals is None:
        return None
    return float(max(b for _a, b in vals))
