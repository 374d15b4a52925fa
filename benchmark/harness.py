"""One run of one cell, driven by data.

``BENCHMARK.json`` names a cell's configuration and traffic; the files
under ``configs/``, ``traffic/`` and ``layer_metrics/`` name the modules
that serve them (``drivers/<driver>.py``, ``generators/<generator>.py``,
``readers/<module>.py``). Nothing here knows a cell, a configuration, a
mix or a metric by name, so a later PR adds files and entries and edits
none.

Order of a run: device check -> compile cache -> driver set-up (build,
load, warm-up; all of it ``setup_s``) -> the generator's window ->
the driver's comparisons, outside the window -> the result line, and
each number compared beside its limit as the last lines of stderr.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from .compare import Check, verdict

PACKAGE = "benchmark"
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    # Includes the persistent-cache fetch when the program was a hit.
    "/jax/core/compile/backend_compile_duration",
)


class BenchmarkError(RuntimeError):
    """The run cannot give a result (wrong device, bad data file, a
    share over 100%). The command exits non-zero and prints no line."""


def say(tag: str, **kw) -> None:
    print(f"[bench:{tag}] " + json.dumps(kw, default=str), flush=True)


class CompileMeter:
    """Sums JAX's own compile events (trace + lower + backend compile or
    cache fetch), keeps each program's backend seconds by name, and
    counts persistent-cache hits and misses. (Copied from
    ``chip_smoke.CompileMeter``.)"""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.programs: list = []  # [name, backend seconds], in order
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **kw) -> None:
        if event in _COMPILE_EVENTS:
            self.compile_s += secs
            if event == _COMPILE_EVENTS[-1]:
                self.programs.append([kw.get("fun_name", "?"), secs])

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "programs": len(self.programs),
                "hits": self.hits, "misses": self.misses}


# -- resolving a cell from the data files --------------------------------------


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything its names resolve to."""

    def __init__(self, root: str, workload: str) -> None:
        self.root = root
        self.bench = _load_json(os.path.join(root, "BENCHMARK.json"))
        rows = [w for w in self.bench["workloads"] if w["name"] == workload]
        if not rows:
            raise BenchmarkError(
                f"no workload {workload!r} in BENCHMARK.json; known: "
                f"{[w['name'] for w in self.bench['workloads']]}")
        self.entry = rows[0]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfgs = [c for c in self.bench["configs"]
                if c["name"] == self.entry["config"]]
        if not cfgs:
            raise BenchmarkError(
                f"{workload}: config {self.entry['config']!r} is not in "
                "BENCHMARK.json configs")
        self.config = _load_json(os.path.join(root, cfgs[0]["file"]))
        self.base = os.path.join(root, self.bench["paths"][0])
        self.traffic = _load_json(os.path.join(
            self.base, "traffic", self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if self._listed(m)]
        moved = {m["name"] for m in self.end_to_end}
        # A per-layer metric belongs to the cells that report the
        # end-to-end metric it moves, unless it names its cells.
        self.per_layer = []
        for m in self.bench["per_layer"]:
            if m["moves"] in moved and self._listed(m):
                spec = _load_json(os.path.join(
                    self.base, "layer_metrics", m["name"] + ".json"))
                self.per_layer.append({**spec, **m})

    def _listed(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def module(self, kind: str, name: str):
        """``drivers/<name>.py`` etc. A module that only the benchmark
        under ``root`` has (another checkout's new file) is loaded from
        there, into the same package."""
        modname = f"{PACKAGE}.{kind}.{name}"
        try:
            return importlib.import_module(modname)
        except ModuleNotFoundError:
            path = os.path.join(self.base, kind, name + ".py")
            if not os.path.exists(path):
                raise
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        return mod

    def reader(self, spec: dict):
        mod, _, fn = spec["reader"].partition(".")
        return getattr(self.module("readers", mod), fn)


# -- the device ---------------------------------------------------------------


def check_device(chips: int, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if require_tpu and d.platform != "tpu":
        raise BenchmarkError(
            f"the benchmark measures a TPU; JAX found platform="
            f"{d.platform!r} device_kind={d.device_kind!r}")
    if len(devs) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chips; JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def place_compile_cache(root: str) -> str:
    """Called before JAX is imported. The program keeps its compile
    cache where ``JAX_COMPILATION_CACHE_DIR`` says and sets no other in
    code; where the variable is not set the benchmark gives it the
    checkout's own fixed path (which is also what the program would
    choose), so that only a checkout's first run compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(root), ".jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.makedirs(path, exist_ok=True)
    return path


class Probe:
    """The profiler trace of a ``--trace 1`` run, opened and closed by
    the generator's loop: a few seconds of the same load, right after
    the measured window (closing a trace stalls the thread that does
    it, and the window's counters must not see that)."""

    def __init__(self, trace: bool, length_s: float, workdir: str) -> None:
        self.want = trace
        self.length_s = length_s
        self.dir = os.path.join(workdir, "trace")
        self.t_on: Optional[float] = None
        self.traced_s: Optional[float] = None

    def start(self) -> None:
        if not self.want or self.t_on is not None:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_on = time.perf_counter()

    def stop(self) -> None:
        if self.t_on is None or self.traced_s is not None:
            return
        import jax

        self.traced_s = time.perf_counter() - self.t_on
        jax.profiler.stop_trace()
        # Where a traced run's wall goes, beside ``[bench:reduced]``:
        # the profiler's own collecting and writing of the trace.
        say("trace_stopped",
            seconds=time.perf_counter() - self.t_on - self.traced_s)


# -- one run --------------------------------------------------------------------


def refuse_bad_values(metrics: Dict[str, dict]) -> None:
    for name, m in metrics.items():
        v = m["value"]
        if v != v or v in (float("inf"), float("-inf")):
            raise BenchmarkError(f"metric {name} is {v}")
        if m["unit"] == "%" and not 0.0 <= v <= 100.0:
            raise BenchmarkError(
                f"metric {name} is a share and reads {v} %: the bytes or "
                "operations are counted too high, or the time leaves out "
                "part of the work")


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            t_start: float, require_tpu: bool = True,
            workdir: Optional[str] = None):
    """Set-up, window and comparisons of one run: (context, checks).
    A caller that gives the ``workdir`` keeps it, and the trace in it,
    until it has read what it wants (``run_cell``: the readers)."""
    device = check_device(cell.chips, require_tpu)
    import jax

    cache_dir = jax.config.jax_compilation_cache_dir
    meter = CompileMeter()
    say("cell", workload=cell.name, config=cell.config["name"],
        traffic=cell.traffic["name"], seed=seed, seconds=seconds,
        trace=int(trace), device=device, compile_cache=cache_dir)

    driver_mod = cell.module("drivers", cell.config["driver"])
    gen = cell.module("generators", cell.traffic["generator"])
    own_workdir = workdir is None
    if own_workdir:
        workdir = tempfile.mkdtemp(prefix="bench_")
    probe = Probe(trace, float(cell.traffic.get("trace_s", 3.0)), workdir)
    driver = driver_mod.Driver(cell.config, cell.traffic, seed, workdir)
    try:
        load = gen.make(cell.traffic, cell.config["sizes"], seed)
        driver.setup(load, gen)
        c0 = meter.snapshot()
        setup_s = time.perf_counter() - t_start
        say("setup", setup_s=setup_s, **c0)

        raw = gen.run(driver, load, cell.traffic, seconds, probe)
        probe.stop()
        # What the driver read at the window's two marks, for the
        # readers; a generator that asks for it itself gets the same.
        raw.update(getattr(driver, "window_counters", dict)())
        c1 = meter.snapshot()
        peak = memory_peak_bytes()
        say("window", **{k: v for k, v in raw.items()
                         if isinstance(v, (int, float, str))})

        t0 = time.perf_counter()
        checks: List[Check] = driver.check(load, raw)
        for c in checks:
            say("check", name=c.name, value=c.value, limit=c.limit,
                ok=c.ok)
        say("checked", seconds=time.perf_counter() - t0)
        raw["setup_s"] = setup_s
        ctx = {
            "raw": raw, "config": cell.config, "traffic": cell.traffic,
            "device": device,
            "compile": {"in_window": c1["programs"] - c0["programs"],
                        "cache_misses": c1["misses"],
                        "cache_hits": c1["hits"],
                        "compile_s": c1["compile_s"]},
            "memory_peak_bytes": peak, "trace": None, "gaps": None,
        }
        if trace:
            from .reduce.gaps import reduce_gaps
            from .reduce.trace import reduce_trace

            t0 = time.perf_counter()
            ctx["trace"] = reduce_trace(probe.dir, window_s=probe.traced_s)
            ctx["gaps"] = reduce_gaps(ctx["trace"]["xplane"],
                                      reduced=ctx["trace"])
            say("reduced", seconds=time.perf_counter() - t0,
                ops=ctx["trace"]["ops"])
        return ctx, checks
    finally:
        probe.stop()
        driver.close()
        if own_workdir:
            shutil.rmtree(workdir, ignore_errors=True)


def per_layer_metrics(cell: Cell, ctx: dict) -> Dict[str, dict]:
    """Each of the cell's per-layer metrics whose reader finds
    something to read."""
    out: Dict[str, dict] = {}
    for spec in cell.per_layer:
        value = cell.reader(spec)(ctx, **spec.get("params", {}))
        if value is not None:
            out[spec["name"]] = {"value": float(value),
                                 "unit": spec["unit"]}
    return out


def end_to_end_metrics(cell: Cell, ctx: dict) -> Dict[str, dict]:
    raw, out = ctx["raw"], {}
    for m in cell.end_to_end:
        if m["name"] not in raw:
            raise BenchmarkError(
                f"{cell.name}: generator {cell.traffic['generator']} "
                f"gave no {m['name']}")
        out[m["name"]] = {"value": float(raw[m["name"]]),
                          "unit": m["unit"]}
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: Optional[float] = None,
             require_tpu: bool = True) -> dict:
    """Run one cell once; returns the result object (the last line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(root, workload)
    with tempfile.TemporaryDirectory(
            prefix="bench_", ignore_cleanup_errors=True) as workdir:
        ctx, checks = measure(cell, seed, seconds, trace, t_start,
                              require_tpu, workdir)
        return _result(cell, ctx, checks, trace)


def _result(cell: Cell, ctx: dict, checks: List[Check],
            trace: bool) -> dict:
    raw = ctx["raw"]
    device = dict(ctx["device"], memory_peak_bytes=ctx["memory_peak_bytes"])
    result = {"correct": verdict(checks),
              "attempted": int(raw["attempted"]),
              "failed": int(raw["failed"])}
    if trace:
        red = ctx["trace"]
        if red["busy_s"] <= 0:
            raise BenchmarkError(
                "the traced window shows no operation on the device")
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        say("trace", devices=red["devices"], ops=red["ops"],
            busy_s=red["busy_s"], window_s=red["window_s"],
            idle_share_pct=red["idle_share_pct"],
            scope_s=red["scope_s"], modules=red["modules"])
        from .reduce.gaps import span_rows

        gaps = ctx["gaps"]
        say("gaps", gaps=gaps["gaps"], gap_s=gaps["gap_s"],
            by_span_s=gaps["by_span_s"], host_spans=gaps["host_spans"],
            clock_lead_ms=gaps["clock_lead_ms"],
            longest=gaps["longest"][:3])
        metrics = per_layer_metrics(cell, ctx)
        # Device idle seconds by the host span open meanwhile.
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": span_rows(gaps)}
    else:
        metrics = end_to_end_metrics(cell, ctx)
    refuse_bad_values(metrics)
    result["metrics"] = metrics
    result["device"] = device
    # Each number compared beside its limit; last in the line.
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def main(argv: List[str], t_start: float, root: str) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    place_compile_cache(root)
    try:
        result = run_cell(root, a.workload, a.seed, a.seconds,
                          bool(a.trace), t_start)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["checks"].items():
        print(f"benchmark: check {name} = {c['value']} (limit "
              f"{c['limit']})", file=sys.stderr)
    print(f"benchmark: correct = {result['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(result), flush=True)
    return 0
