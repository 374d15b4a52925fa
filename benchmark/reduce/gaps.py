"""Each device idle gap of a profiler trace, put down to the host spans
that were open during it.

The program's round-span recorder (``etcd_tpu/obs/spans.py``) opens a
``jax.profiler.TraceAnnotation`` for every span, so a trace taken while
the program runs holds them as events of the host plane, one line a
thread, on the clock of the device planes. From one xplane this gives:

* the idle gaps of the device planes, as ``reduce/trace.py`` lists
  them (``trace.idle_gaps``: between the merged intervals of the
  ``XLA Ops`` line, at least ``MIN_GAP_NS`` long);
* the seconds of device idle per span name: at every instant of a gap
  each host thread that has a span open counts with its *innermost*
  one, the instant is shared equally among those threads, and an
  instant under no span is ``unspanned``;
* the longest gaps, each with the spans that covered it.

The host plane's clock leads the device planes' by a constant that
differs from one profiler session to the next (0.37 ms in the kept
served trace, 1.44 ms in an engine trace: a scan seemed to start on the
device before the host had opened the span that enqueues it). No
program starts before the host enqueues it, so the lead is the largest
(host ``DoEnqueueProgram`` start - device ``XLA Modules`` start) over
the programs of the trace, paired in order (``host_clock_lead``); the
spans are moved back by it before the gaps are shared out. Where the
two counts differ nothing can be paired, and the spans stay as they
are (``clock_lead_ms`` null).

``share_out``, ``innermost`` and ``host_clock_lead`` are pure functions
of interval lists.

    python3 -m benchmark.reduce.gaps <trace dir or .xplane.pb>
"""

from __future__ import annotations

import bisect
import json
import sys
from typing import Dict, Hashable, List, Optional, Sequence, Tuple

from .trace import MODULES_LINE, OPS_LINE, find_xplane, idle_gaps

HOST_PLANE = "/host:CPU"
ENQUEUE_EVENT = "DoEnqueueProgram"
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


def host_clock_lead(module_starts: Sequence[float],
                    enqueue_starts: Sequence[float]) -> Optional[float]:
    """By how much the host plane's clock leads the device's: the
    largest (enqueue - start) over the programs, each device program
    paired with the host's enqueue of the same rank in time (a program
    that waited for the one before it reads lower, never higher).
    ``None`` where the counts differ or there is nothing to pair."""
    if not module_starts or len(module_starts) != len(enqueue_starts):
        return None
    return max(h - d for d, h in zip(sorted(module_starts),
                                     sorted(enqueue_starts)))


def read_xplane(path: str, device_ops: bool = True):
    """(per device plane its ops, per host thread its span events
    (start, end, name) on the device's clock, the host clock's lead in
    ns or ``None``) of one trace file. Without ``device_ops`` the ops
    are not walked and the first is empty: for a caller that has
    ``reduce_trace``'s gaps already."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[List[Tuple[float, float, str]]] = []
    threads: Dict[Hashable, List[Named]] = {}
    modules: List[float] = []
    enqueues: List[float] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE and device_ops:
                    devices.append([(e.start_ns, e.duration_ns, e.name)
                                    for e in line.events])
                elif line.name == MODULES_LINE:
                    modules.extend(e.start_ns for e in line.events)
        elif plane.name == HOST_PLANE:
            for i, line in enumerate(plane.lines):
                evs = []
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name))
                    elif e.name == ENQUEUE_EVENT:
                        enqueues.append(e.start_ns)
                if evs:
                    threads[(i, line.name)] = evs
    lead = host_clock_lead(modules, enqueues)
    if lead:
        threads = {k: [(a - lead, b - lead, n) for a, b, n in v]
                   for k, v in threads.items()}
    return devices, threads, lead


def reduce_gaps(trace_dir_or_file: str, top: int = 10,
                reduced: Optional[dict] = None) -> dict:
    """Device idle seconds by host span, and the ``top`` longest gaps
    with what covered each. Seconds are averaged over the device planes
    (as ``reduce_trace`` averages its own). ``reduced``:
    ``reduce_trace``'s result of the same file, whose gaps are then
    taken as they stand and the device's ops not read again."""
    path = (trace_dir_or_file if trace_dir_or_file.endswith(".pb")
            else find_xplane(trace_dir_or_file))
    devices, threads, lead = read_xplane(path, device_ops=reduced is None)
    if reduced is not None:
        device_gaps, t_first = reduced["idle_gaps"], reduced["first_op_ns"]
    else:
        device_gaps = [idle_gaps(ops) for ops in devices]
        t_first = min((s for ops in devices for s, _d, _n in ops),
                      default=None)
    if not device_gaps or t_first is None:
        raise ValueError(f"{path}: no /device:TPU:* plane with ops")
    k = len(device_gaps)
    by_span: Dict[str, float] = {}
    rows = []
    for gaps in device_gaps:
        total, per_gap = share_out([(a, b) for a, b, _n in gaps], threads)
        for n, v in total.items():
            by_span[n] = by_span.get(n, 0.0) + v / 1e9 / k
        rows.extend((b - a, a, after, cover)
                    for (a, b, after), cover in zip(gaps, per_gap))
    gap_s = sum(by_span.values())
    rows.sort(key=lambda r: -r[0])
    return {
        "xplane": path,
        "devices": k,
        "host_threads": len(threads),
        "host_spans": sum(len(v) for v in threads.values()),
        "clock_lead_ms": None if lead is None else lead / 1e6,
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


def span_rows(red: dict, top: int = 10) -> List[List]:
    """The result line's ``idle_gaps``: [host span, device idle
    seconds], longest first, at most ``top``, ``unspanned`` always
    among them where any second fell under no span."""
    rows = [[n, v] for n, v in red["by_span_s"].items()]
    kept = rows[:top]
    if UNSPANNED in red["by_span_s"] and UNSPANNED not in dict(kept):
        kept[-1] = [UNSPANNED, red["by_span_s"][UNSPANNED]]
    return kept


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
