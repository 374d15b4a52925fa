"""Profiler trace (``*.xplane.pb``) -> numbers, as code.

What this gives, for the device planes of one trace:

* device seconds per ``named_scope`` (``raft_route``, ``raft_deliver``,
  ...) and per ``scope/op`` (``raft_route/copy``), over *leaf* ops only —
  an op that encloses others (a ``while``) is counted through what it
  encloses, so the parts sum to the whole;
* the busy union of every op interval, the traced window, the idle share;
* each executed program (``XLA Modules`` line) with its count and seconds;
* the idle gaps (``idle_gaps``: one list, which ``reduce/gaps.py`` shares
  out among the host spans) and their seconds.

Times come from ``jax.profiler.ProfileData`` (the events). ProfileData
does not expose the per-op *metadata* stats, and the ``named_scope`` of
an op lives exactly there (``tf_op``), so a few dozen lines of protobuf
wire decoding read that one map out of the same file; nothing but JAX,
numpy (which JAX brings) and the standard library is imported.

A traced call of a large cell is millions of events. ``reduce_trace``
walks them once: the sorts, the leaf test and the merge of the busy
intervals run in numpy (``_plane``), every sum still runs in the order
and the precision it always did, so the numbers are the same to the
last bit as the plain functions beside it give (``_leaf_seconds``,
``_union``, ``idle_gaps``: kept, for small inputs and as the statement
of what ``_plane`` computes; ``tests/benchmark/test_reduce.py`` holds
the two equal on the recorded traces).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# ``.../raft_route/reshape`` and ``.../vmap(raft_deliver)/while``.
SCOPE_RE = re.compile(r"(?:^|[/(])(raft_[a-z_]+)(?=[/):]|$)")
# A pause shorter than this between two ops is the device's own issue
# latency, not the host holding it back.
MIN_GAP_NS = 1000.0
UNSCOPED = "unscoped"


# -- protobuf wire format, only as far as XSpace needs ------------------------


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes) -> Iterable[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message; value is an int
    for varint/fixed fields and a memoryview for length-delimited ones."""
    i, n = 0, len(buf)
    mv = memoryview(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = mv[i:i + ln], i + ln
        elif wt == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield num, wt, v


def _stat_names(plane: bytes) -> Dict[int, str]:
    """XPlane.stat_metadata: id -> name."""
    out = {}
    for num, _wt, v in _fields(plane):
        if num != 5:
            continue
        for n2, _w2, entry in _fields(bytes(v)):
            if n2 != 2:
                continue
            sid, name = 0, ""
            for n3, _w3, x in _fields(bytes(entry)):
                if n3 == 1:
                    sid = x
                elif n3 == 2:
                    name = bytes(x).decode("utf-8", "replace")
            out[sid] = name
    return out


def op_scopes(xplane_path: str) -> Dict[str, str]:
    """{event metadata name (the HLO text ProfileData calls the event's
    name): its ``tf_op`` string} over every plane of the file."""
    with open(xplane_path, "rb") as f:
        space = f.read()
    out: Dict[str, str] = {}
    for num, _wt, plane in _fields(space):
        if num != 1:
            continue
        plane = bytes(plane)
        names = _stat_names(plane)
        tf_ids = {i for i, n in names.items() if n == "tf_op"}
        if not tf_ids:
            continue
        for n1, _w1, v in _fields(plane):
            if n1 != 4:  # event_metadata map entry
                continue
            for n2, _w2, meta in _fields(bytes(v)):
                if n2 != 2:
                    continue
                ev_name, tf_op = "", None
                for n3, _w3, x in _fields(bytes(meta)):
                    if n3 == 2:
                        ev_name = bytes(x).decode("utf-8", "replace")
                    elif n3 == 5:  # XStat
                        sid, sval = 0, None
                        for n4, _w4, y in _fields(bytes(x)):
                            if n4 == 1:
                                sid = y
                            elif n4 == 5:
                                sval = bytes(y).decode("utf-8", "replace")
                            elif n4 == 7:  # ref_value -> stat_metadata
                                sval = names.get(y, "")
                        if sid in tf_ids:
                            tf_op = sval
                if ev_name and tf_op:
                    out[ev_name] = tf_op
    return out


# -- the reduction -------------------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return hits[-1]


def op_kind(event_name: str) -> str:
    """``%copy.288 = s32[...] copy(...)`` -> ``copy``; a plain
    ``fusion.12`` -> ``fusion``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def scope_of(tf_op: Optional[str]) -> str:
    if not tf_op:
        return UNSCOPED
    hits = SCOPE_RE.findall(tf_op)
    return hits[-1] if hits else UNSCOPED


def _leaf_seconds(events: List[Tuple[float, float, str]]
                  ) -> List[Tuple[float, float, str]]:
    """Events of one line nest like a call stack. Keep the leaves: an
    event with another inside it is dropped, its time is its children's
    (plus gaps, which the busy union below still sees)."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    leaves: List[Tuple[float, float, str]] = []
    stack: List[List] = []  # [end, has_child, event]
    for ev in events:
        start, dur, _name = ev
        while stack and stack[-1][0] <= start + 1e-3:
            end, has_child, old = stack.pop()
            if not has_child:
                leaves.append(old)
        if stack:
            stack[-1][1] = True
        stack.append([start + dur, False, ev])
    for _end, has_child, old in stack:
        if not has_child:
            leaves.append(old)
    return leaves


def _union(intervals: List[Tuple[float, float]]) -> Tuple[float, List]:
    """Total covered length and the merged intervals (ns)."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def idle_gaps(ops: Sequence[Tuple[float, float, str]],
              merged: Optional[List] = None
              ) -> List[Tuple[float, float, str]]:
    """(start, end, kind of the op that ended before it) of every idle
    gap of one device's ops (start, duration, name): the pauses of at
    least ``MIN_GAP_NS`` between the merged op intervals (``merged``:
    ``_union``'s of these ops, where the caller has it already)."""
    if merged is None:
        _busy, merged = _union([(s, s + d) for s, d, _n in ops])
    gaps = [(e0, s1) for (_s0, e0), (s1, _e1) in zip(merged, merged[1:])
            if s1 - e0 >= MIN_GAP_NS]
    if not gaps:
        return []
    ends = sorted((s + d, n) for s, d, n in ops)
    end_times = [e for e, _n in ends]
    out = []
    for e0, s1 in gaps:
        i = bisect.bisect_right(end_times, e0 + 1e-3) - 1
        out.append((e0, s1, op_kind(ends[max(i, 0)][1])))
    return out


def _plane(ops: Sequence[Tuple[float, float, str]]):
    """One device's ops (start, duration, name) -> (the leaves in
    ``_leaf_seconds``'s order, ``_union``'s total, the first merged
    interval's start, the last one's end, ``idle_gaps``' list), by
    sorting and merging in numpy. What makes it the same as the plain
    functions: sorted by (start, longest first) an event has a child
    exactly when the next one starts inside it, and a leaf is popped
    before anything is pushed over it, so the leaves come in sorted
    order; sorted by (start, end) a new busy interval begins where a
    start passes the running maximum of the ends."""
    n = len(ops)
    start = np.fromiter((o[0] for o in ops), np.float64, n)
    dur = np.fromiter((o[1] for o in ops), np.float64, n)
    end = start + dur
    order = np.lexsort((-dur, start))
    s1, e1 = start[order], end[order]
    leaf = np.ones(n, bool)
    leaf[:-1] = e1[:-1] <= s1[1:] + 1e-3
    leaves = [ops[i] for i in order[leaf].tolist()]

    by_start = np.lexsort((end, start))
    s2, e2 = start[by_start], end[by_start]
    reach = np.maximum.accumulate(e2)
    first = np.ones(n, bool)
    first[1:] = s2[1:] > reach[:-1]
    at = np.flatnonzero(first)
    m_start = s2[at]
    m_end = reach[np.append(at[1:] - 1, n - 1)]
    total = sum((m_end - m_start).tolist())

    gaps: List[Tuple[float, float, str]] = []
    wide = np.flatnonzero(m_start[1:] - m_end[:-1] >= MIN_GAP_NS)
    if len(wide):
        by_end = np.argsort(end, kind="stable")
        ends = end[by_end]
        for g in wide.tolist():
            e0 = float(m_end[g])
            i = int(np.searchsorted(ends, e0 + 1e-3, "right")) - 1
            # Of the ops that ended together, the last by name: where
            # ``idle_gaps``' sort of (end, name) puts the one it takes
            # (the first of all, if none ended by then).
            if i < 0:
                hi = int(np.searchsorted(ends, ends[0], "right"))
                name = min(ops[j][2] for j in by_end[:hi].tolist())
            else:
                lo = int(np.searchsorted(ends, ends[i], "left"))
                name = max(ops[j][2] for j in by_end[lo:i + 1].tolist())
            gaps.append((e0, float(m_start[g + 1]), op_kind(name)))
    return leaves, total, float(m_start[0]), float(m_end[-1]), gaps


def reduce_trace(trace_dir_or_file: str, window_s: Optional[float] = None,
                 top: int = 10) -> dict:
    """Reduce one trace. ``window_s`` is the traced wall window as the
    caller clocked it; without it the window is first op start to last
    op end. Device numbers are averaged over the device planes found."""
    from jax.profiler import ProfileData

    path = (trace_dir_or_file if trace_dir_or_file.endswith(".pb")
            else find_xplane(trace_dir_or_file))
    scopes = op_scopes(path)
    data = ProfileData.from_file(path)
    planes = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    if not planes:
        raise ValueError(
            f"{path}: no /device:TPU:* plane, found "
            f"{[p.name for p in data.planes]}")

    by_scope: Dict[str, float] = {}
    by_op: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    busy_ns = span_ns = gap_ns = 0.0
    n_ops = 0
    device_gaps: List[List[Tuple[float, float, str]]] = []
    first_op_ns: Optional[float] = None
    for plane in planes:
        ops: List[Tuple[float, float, str]] = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(e.start_ns, e.duration_ns, e.name)
                       for e in line.events]
            elif line.name == MODULES_LINE:
                for e in line.events:
                    name = re.sub(r"\(\d+\)$", "", e.name)
                    modules.setdefault(name, []).append(e.duration_ns / 1e9)
        if not ops:
            continue
        n_ops += len(ops)
        # A traced call of a large cell is millions of events of a few
        # thousand distinct ops: the two regular expressions run once
        # an op, not once an event; the sums run in the events' order
        # as they always did.
        keys: Dict[str, Tuple[str, str]] = {}
        leaves, total, first, last, gaps = _plane(ops)
        for _s, dur, name in leaves:
            if name not in keys:
                scope = scope_of(scopes.get(name))
                keys[name] = scope, f"{scope}/{op_kind(name)}"
            scope, key = keys[name]
            by_scope[scope] = by_scope.get(scope, 0.0) + dur / 1e9
            by_op[key] = by_op.get(key, 0.0) + dur / 1e9
        busy_ns += total
        span_ns += last - first
        device_gaps.append(gaps)
        gap_ns += sum(b - a for a, b, _after in gaps)
        first_op_ns = first if first_op_ns is None else min(first_op_ns,
                                                            first)

    k = len(planes)
    busy_s = busy_ns / 1e9 / k
    span_s = span_ns / 1e9 / k
    window = window_s if window_s else span_s
    leaf_total = sum(by_scope.values()) / k
    return {
        "xplane": path,
        "devices": k,
        "ops": n_ops,
        "busy_s": busy_s,
        "span_s": span_s,
        "window_s": window,
        "idle_share_pct": max(0.0, 100.0 * (1.0 - busy_s / window))
        if window > 0 else None,
        "leaf_s": leaf_total,
        "scope_s": {s: v / k for s, v in sorted(
            by_scope.items(), key=lambda r: -r[1])},
        "op_s": {s: v / k for s, v in by_op.items()},
        "modules": {n: {"count": len(v), "seconds": sum(v)}
                    for n, v in modules.items()},
        "device_ops": [[n, v / k] for n, v in sorted(
            by_op.items(), key=lambda r: -r[1])[:top]],
        "gap_s": gap_ns / 1e9 / k,
        # For ``reduce/gaps.reduce_gaps``, which then reads the host's
        # plane alone: the ops, millions in a large cell, are walked
        # once a traced run.
        "idle_gaps": device_gaps,
        "first_op_ns": first_op_ns,
    }


def scope_share_pct(reduced: dict, scope: str) -> Optional[float]:
    """Share of leaf device time under one ``named_scope``, 0..100."""
    total = reduced.get("leaf_s") or 0.0
    if total <= 0 or scope not in reduced["scope_s"]:
        return None
    return 100.0 * reduced["scope_s"][scope] / total
