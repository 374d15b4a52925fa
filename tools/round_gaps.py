#!/usr/bin/env python
"""One traced run of a benchmark cell with the trace kept, and its
device idle gaps put down to host spans (``benchmark/reduce/gaps.py``).

The harness deletes its trace before anything else can read it
(``benchmark/harness.py``: ``shutil.rmtree(workdir)``), so until a
``benchmark`` PR wires ``reduce/gaps.py`` into the result line
(``ROADMAP.md`` S2) this is how the tables of PERF.md §5 are made; then
this tool goes. The cell runs as its files say, through the harness's
own ``measure``, a served cell with the parked entries
(``benchmark/parked/*.json``) added to a temporary ``BENCHMARK.json``.
Needs the TPU. Prints the run's metrics, then the table:

    python tools/round_gaps.py --workload served1k-r3.put --seed 7 \\
        --seconds 30 --out chiprun_out/served_put
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def root_with_parked() -> str:
    dst = tempfile.mkdtemp(prefix="round_gaps_")
    os.symlink(os.path.join(ROOT, "benchmark"),
               os.path.join(dst, "benchmark"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for path in sorted(glob.glob(
            os.path.join(ROOT, "benchmark", "parked", "*.json"))):
        with open(path) as f:
            for key, entries in json.load(f).items():
                if key != "note":
                    bench[key].extend(entries)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


def main() -> int:
    ap = argparse.ArgumentParser(prog="tools/round_gaps.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()

    sys.path.insert(0, ROOT)
    from benchmark import harness
    from benchmark.reduce.gaps import reduce_gaps, table

    kept = os.path.join(a.out, "trace")

    class KeepingProbe(harness.Probe):
        def stop(self) -> None:
            closing = self.t_on is not None and self.traced_s is None
            super().stop()
            if closing:
                shutil.rmtree(kept, ignore_errors=True)
                shutil.copytree(self.dir, kept)

    harness.Probe = KeepingProbe
    harness.place_compile_cache(ROOT)
    root = root_with_parked()
    try:
        cell = harness.Cell(root, a.workload)
        ctx, checks = harness.measure(cell, a.seed, a.seconds, True,
                                      T_START)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    metrics = {**harness.end_to_end_metrics(cell, ctx),
               **harness.per_layer_metrics(cell, ctx)}
    print(json.dumps({
        "metrics": {k: v["value"] for k, v in metrics.items()},
        "correct": harness.verdict(checks), "device": ctx["device"],
        "failed": ctx["raw"]["failed"],
        "idle_share_pct": ctx["trace"]["idle_share_pct"]}))
    red = reduce_gaps(kept)
    with open(os.path.join(a.out, "gaps.json"), "w") as f:
        json.dump(red, f, indent=1)
    print(table(red))
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)  # daemon threads of the members must not outlive it
