#!/usr/bin/env python
"""Fleet-summary overhead measurement (ISSUE 10 bench honesty).

Measures the closed-loop kernel rate with the fleet observatory
compiled OFF and ON, **interleaved in one process on one box** (the
box drifts tens of percent day to day — BENCH_NOTES discipline: never
compare across runs, always A/B within one), at G=512 and G=1024 on
the canonical bench config (tools/benchlib), and writes
``artifacts/fleet_overhead.json`` — the row BENCH_NOTES quotes.

    JAX_PLATFORMS=cpu python tools/fleet_overhead.py [--reps 3]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _note(msg: str) -> None:
    print(f"[fleet_overhead {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def measure_cell(groups: int, reps: int) -> Dict:
    """One A/B cell: build both engines, then alternate off/on rate
    measurements so box drift hits both arms equally."""
    from etcd_tpu.tools.benchlib import make_bench_engine, measure_rate

    t0 = time.perf_counter()
    eng_off, props_off = make_bench_engine(groups, lanes_minor=False,
                                           fleet=False)
    eng_on, props_on = make_bench_engine(groups, lanes_minor=False,
                                         fleet=True)
    _note(f"G={groups}: engines built+compiled in "
          f"{time.perf_counter() - t0:.1f}s")
    off: List[float] = []
    on: List[float] = []
    for i in range(reps):
        off.append(measure_rate(eng_off, props_off, 8, 2))
        on.append(measure_rate(eng_on, props_on, 8, 2))
        _note(f"G={groups} rep {i + 1}/{reps}: off {off[-1]:.0f} "
              f"on {on[-1]:.0f} group-rounds/s")
    off_med = statistics.median(off)
    on_med = statistics.median(on)
    # Overhead = MEDIAN OF THE PER-REP PAIRWISE RATIOS, not the ratio
    # of medians: this 2-core box load-flakes by tens of percent, and
    # a spike landing on one arm of one rep would otherwise dominate
    # the cross-arm medians (each rep's off/on pair runs back to back,
    # so within a pair the load is as equal as it gets).
    pair_pct = [(o - n) / o * 100 for o, n in zip(off, on)]
    return {
        "groups": groups,
        "reps": reps,
        "off_rates": [round(x, 1) for x in off],
        "on_rates": [round(x, 1) for x in on],
        "off_median": round(off_med, 1),
        "on_median": round(on_med, 1),
        "pairwise_pct": [round(x, 2) for x in pair_pct],
        # Positive = fleet summary costs throughput.
        "overhead_pct": round(statistics.median(pair_pct), 2),
    }


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="fleet-overhead",
                                description=__doc__)
    p.add_argument("--reps", type=int, default=3,
                   help="interleaved A/B repetitions per cell")
    p.add_argument("--groups", default="512,1024",
                   help="comma-separated G cells")
    p.add_argument("--out", default="artifacts/fleet_overhead.json")
    args = p.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("ETCD_TPU_TRANSFER_GUARD", "disallow")
    import jax

    platform = jax.devices()[0].platform
    cells = [measure_cell(int(g), args.reps)
             for g in args.groups.split(",")]
    payload = {
        "metric": "fleet_summary_overhead",
        "platform": platform,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "method": ("interleaved on/off measure_rate(8x2) in one "
                   "process (benchlib canonical config, layout=major); "
                   "medians of the A/B pairs — same-box same-minute, "
                   "so day-to-day box drift cancels"),
        "cells": cells,
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    for c in cells:
        print(f"G={c['groups']}: off {c['off_median']:.0f} vs on "
              f"{c['on_median']:.0f} group-rounds/s -> overhead "
              f"{c['overhead_pct']:+.2f}%")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
