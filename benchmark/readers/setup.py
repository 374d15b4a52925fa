"""``setup_s`` by what the program was doing, from the round-span
recorder (``etcd_tpu/obs/spans.py``): JAX's own compile phases, which
the program records as spans by program (``compile.trace``,
``compile.lower``, ``compile.backend``: ``etcd_tpu/batched/
compile_cache.py``), the abstract pre-trace of a tiled engine
(``engine.pretrace``), and what no program span covers.

Set-up is everything that ended before the window's first
``engine.run_rounds`` opened (``readers/spans.py`` says which call that
is). Only durations are used: ``setup_s`` is the harness's, on
``perf_counter`` from the start of the process, the spans are on
``monotonic_ns``, and no reading of one clock is compared with one of
the other.

A program without these spans (a parent commit) gives ``None`` for the
three that read them, and the metric is left out.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from ..harness import say
from ..reduce.trace import _union
from . import spans as span_reader

TRACE, LOWER, BACKEND = "compile.trace", "compile.lower", "compile.backend"
PRETRACE = "engine.pretrace"


def _setup(ctx) -> Optional[Dict[str, list]]:
    """Every span that ended before the window opened, by name, cached;
    ``None`` without an engine's window to end set-up at."""
    if "_setup_spans" in ctx:
        return ctx["_setup_spans"]
    out = None
    e = span_reader._engine(ctx)
    if e and e["window"]:
        opens = e["window"][0].t0
        out = {}
        for s in span_reader._snapshot() or ():
            if s.t1 <= opens:
                out.setdefault(s.name, []).append(s)
    ctx["_setup_spans"] = out
    return out


def _seconds(spans) -> float:
    return sum(s.t1 - s.t0 for s in spans) / 1e9


def _covered_s(spans) -> float:
    """Seconds under at least one of the spans: what nests counts
    once."""
    return _union([(s.t0, s.t1) for s in spans])[0] / 1e9


def jax_trace_s(ctx) -> Optional[float]:
    """Seconds of set-up JAX spent tracing functions to jaxprs. A
    jitted function traced inside another's trace (the round inside
    the scan) sends an event of its own, inside the outer one's time:
    counted once."""
    got = _setup(ctx)
    if not got or TRACE not in got:
        return None
    return _covered_s(got[TRACE])


def _programs(got: Dict[str, list]) -> List[Tuple[str, dict]]:
    """Per program (``closed_loop`` for ``jit(closed_loop)``): seconds
    traced, lowered and in the backend, how often it was compiled or
    fetched, and how many of those were hits; dearest in lowering and
    backend first."""
    rows: Dict[str, dict] = {}
    for name, key in ((TRACE, "trace_s"), (LOWER, "lower_s"),
                      (BACKEND, "backend_s")):
        for s in got.get(name, ()):
            fun = (s.stats or {}).get("fun_name", "?")
            if fun.startswith("jit(") and fun.endswith(")"):
                fun = fun[4:-1]
            row = rows.setdefault(fun, {"trace_s": 0.0, "lower_s": 0.0,
                                        "backend_s": 0.0, "built": 0,
                                        "hits": 0})
            row[key] += (s.t1 - s.t0) / 1e9
            if name == BACKEND:
                row["built"] += 1
                row["hits"] += int((s.stats or {}).get("hit", 0))
    return sorted(rows.items(),
                  key=lambda r: -(r[1]["lower_s"] + r[1]["backend_s"]))


def jax_compile_s(ctx) -> Optional[float]:
    """Seconds of set-up JAX spent lowering jaxprs and in the backend
    (compiling, or fetching from the persistent cache); the five
    dearest programs by name on the ``[bench:setup_programs]`` line."""
    got = _setup(ctx)
    if not got or not (LOWER in got or BACKEND in got):
        return None
    rows = _programs(got)
    say("setup_programs", programs=len(rows),
        compiled=sum(r["built"] - r["hits"] for _n, r in rows),
        fetched=sum(r["hits"] for _n, r in rows),
        trace_events_s=_seconds(got.get(TRACE, ())),
        dearest=[dict(row, name=name,
                      hit=row["built"] > 0 and row["hits"] == row["built"])
                 for name, row in rows[:5]])
    return _seconds(got.get(LOWER, ())) + _seconds(got.get(BACKEND, ()))


def pretrace_s(ctx) -> Optional[float]:
    """Seconds of set-up in the abstract pre-traces of a tiled engine
    (one in the eager round's program, one in each scan program)."""
    got = _setup(ctx)
    if not got or PRETRACE not in got:
        return None
    return _seconds(got[PRETRACE])


def unspanned_s(ctx) -> Optional[float]:
    """``setup_s`` less the seconds under any program span of set-up
    (the union, so a span nested in another counts once): what only
    the harness can name, the process's start, imports, the backend's
    start, the generator's schedule, the drivers' fences."""
    got = _setup(ctx)
    total = ctx["raw"].get("setup_s")
    if not got or total is None:
        return None
    covered = _covered_s([s for v in got.values() for s in v])
    return max(0.0, float(total) - covered)
