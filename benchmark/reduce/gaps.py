"""Each device idle gap of a profiler trace, put down to the host spans
that were open during it.

The program's round-span recorder (``etcd_tpu/obs/spans.py``) opens a
``jax.profiler.TraceAnnotation`` for every span, so a trace taken while
the program runs holds them as events of the host plane, one line a
thread, on the clock of the device planes. From one xplane this gives:

* the idle gaps of the device planes, as ``reduce/trace.py`` defines
  them (between the merged intervals of the ``XLA Ops`` line, at least
  ``MIN_GAP_NS`` long; its ``_union`` and constants are imported);
* the seconds of device idle per span name: at every instant of a gap
  each host thread that has a span open counts with its *innermost*
  one, the instant is shared equally among those threads, and an
  instant under no span is ``unspanned``;
* the longest gaps, each with the spans that covered it.

``share_out`` and ``innermost`` are pure functions of interval lists.

    python3 -m benchmark.reduce.gaps <trace dir or .xplane.pb>
"""

from __future__ import annotations

import bisect
import json
import sys
from typing import Dict, Hashable, List, Sequence, Tuple

from .trace import MIN_GAP_NS, OPS_LINE, _union, find_xplane, op_kind

HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("engine.", "member.", "rawnode.")
UNSPANNED = "unspanned"

Interval = Tuple[float, float]
Named = Tuple[float, float, str]


def innermost(spans: Sequence[Named]) -> List[Named]:
    """One thread's spans nest like a call stack. Returns the stretches
    of time with the name of the innermost span open in each: sorted,
    not overlapping, and covering exactly what the spans cover."""
    out: List[Named] = []
    stack: List[Named] = []  # open spans, outermost first
    cursor = 0.0  # stretches are emitted up to here while a span is open

    def close(upto: float) -> None:
        """Pop what ended by ``upto``, each popped span named from the
        cursor to its end."""
        nonlocal cursor
        while stack and stack[-1][1] <= upto:
            _s, end, name = stack.pop()
            if end > cursor:
                out.append((cursor, end, name))
                cursor = end

    for sp in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(sp[0])
        if stack and sp[0] > cursor:
            out.append((cursor, sp[0], stack[-1][2]))
        cursor = max(cursor, sp[0]) if stack else sp[0]
        stack.append(sp)
    close(float("inf"))
    return out


def share_out(gaps: Sequence[Interval],
              threads: Dict[Hashable, Sequence[Named]]
              ) -> Tuple[Dict[str, float], List[Dict[str, float]]]:
    """(length of all gaps by span name, the same for each gap).
    ``threads``: per host thread its spans (start, end, name). Lengths
    are in the unit of the intervals; every gap's parts sum to its
    length."""
    flat = {k: innermost(v) for k, v in threads.items()}
    starts = {k: [s for s, _e, _n in v] for k, v in flat.items()}
    total: Dict[str, float] = {}
    per_gap: List[Dict[str, float]] = []
    for a, b in gaps:
        pieces: List[Named] = []
        for k, segs in flat.items():
            i = max(bisect.bisect_right(starts[k], a) - 1, 0)
            while i < len(segs) and segs[i][0] < b:
                s, e, name = segs[i]
                if e > a:
                    pieces.append((max(s, a), min(e, b), name))
                i += 1
        cuts = sorted({a, b, *(p[0] for p in pieces),
                       *(p[1] for p in pieces)})
        cover: Dict[str, float] = {}
        for lo, hi in zip(cuts, cuts[1:]):
            names = [n for s, e, n in pieces if s <= lo and e >= hi]
            for n in names or [UNSPANNED]:
                cover[n] = cover.get(n, 0.0) + (hi - lo) / max(len(names), 1)
        per_gap.append(cover)
        for n, v in cover.items():
            total[n] = total.get(n, 0.0) + v
    return total, per_gap


def idle_gaps(ops: Sequence[Tuple[float, float, str]]
              ) -> List[Tuple[float, float, str]]:
    """(start, end, kind of the op that ended before it) of every idle
    gap of one device's ops (start, duration, name)."""
    _busy, merged = _union([(s, s + d) for s, d, _n in ops])
    ends = sorted((s + d, n) for s, d, n in ops)
    end_times = [e for e, _n in ends]
    out = []
    for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
        if s1 - e0 < MIN_GAP_NS:
            continue
        i = bisect.bisect_right(end_times, e0 + 1e-3) - 1
        out.append((e0, s1, op_kind(ends[max(i, 0)][1])))
    return out


def read_xplane(path: str):
    """(per device plane its ops, per host thread its span events
    (start, end, name)) of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[List[Tuple[float, float, str]]] = []
    threads: Dict[Hashable, List[Named]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append([(e.start_ns, e.duration_ns, e.name)
                                    for e in line.events])
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIXES)]
                if evs:
                    threads[(i, line.name)] = evs
    return devices, threads


def reduce_gaps(trace_dir_or_file: str, top: int = 10) -> dict:
    """Device idle seconds by host span, and the ``top`` longest gaps
    with what covered each. Seconds are averaged over the device planes
    (as ``reduce_trace`` averages its own)."""
    path = (trace_dir_or_file if trace_dir_or_file.endswith(".pb")
            else find_xplane(trace_dir_or_file))
    devices, threads = read_xplane(path)
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:* plane with ops")
    k = len(devices)
    by_span: Dict[str, float] = {}
    rows = []
    for ops in devices:
        gaps = idle_gaps(ops)
        total, per_gap = share_out([(a, b) for a, b, _n in gaps], threads)
        for n, v in total.items():
            by_span[n] = by_span.get(n, 0.0) + v / 1e9 / k
        rows.extend((b - a, a, after, cover)
                    for (a, b, after), cover in zip(gaps, per_gap))
    gap_s = sum(by_span.values())
    t_first = min(s for ops in devices for s, _d, _n in ops)
    rows.sort(key=lambda r: -r[0])
    return {
        "xplane": path,
        "devices": k,
        "host_threads": len(threads),
        "host_spans": sum(len(v) for v in threads.values()),
        "gaps": len(rows),
        "gap_s": gap_s,
        "by_span_s": dict(sorted(by_span.items(), key=lambda r: -r[1])),
        "unspanned_pct": (100.0 * by_span.get(UNSPANNED, 0.0) / gap_s
                          if gap_s > 0 else 0.0),
        "longest": [
            {"ms": length / 1e6, "at_ms": (a - t_first) / 1e6,
             "after": after,
             "spans_ms": {n: v / 1e6 for n, v in sorted(
                 cover.items(), key=lambda r: -r[1])}}
            for length, a, after, cover in rows[:top]],
    }


def table(red: dict) -> str:
    """The reduction as the markdown PERF.md keeps."""
    lines = ["| host span | device idle s | share |", "|---|---|---|"]
    for name, s in red["by_span_s"].items():
        lines.append(f"| `{name}` | {s:.4f} | "
                     f"{100.0 * s / red['gap_s']:.1f}% |")
    lines.append(f"| all {red['gaps']} gaps | {red['gap_s']:.4f} | 100% |")
    return "\n".join(lines)


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print(__doc__.strip().splitlines()[-1].strip(), file=sys.stderr)
        return 2
    red = reduce_gaps(argv[0])
    print(table(red))
    print(json.dumps(red))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
