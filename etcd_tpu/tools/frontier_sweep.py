"""Latency/throughput frontier sweep over group count G.

Maps the (throughput, commit-latency) frontier of the batched engine:
for each G it measures steady-state group-rounds/s through the
double-buffered pipelined round loop (engine.run_rounds_pipelined —
chunk k+1 enqueued while chunk k's scan runs, donated state buffers)
AND the device commit p50 — wall-clock from a quiet-point proposal to
quorum commit across every group, the bench.py methodology. One sweep
answers the VERDICT r05 top-two items together: how much throughput
each latency point buys, and where the knee is.

Every engine build routes XLA compilation through the persistent
on-disk cache (batched/compile_cache.py), so re-running the sweep pays
disk hits instead of recompiles. The sweep records per-point build
times. It starts no child process: this process owns the chip, and a
child that needed it could not start. The warm-start compile time is
the second ``chip_smoke.py`` run's compile seconds.

Before measuring, the pipelined loop is differentially gated against
single-round stepping (same program as the shadow-verified step_round
path) on a small config: commits/terms/leaders must match exactly, or
the sweep aborts. The full oracle check lives in
tests/batched/test_pipelined.py; this inline gate just refuses to
publish numbers from a loop that diverged.

Writes ``artifacts/frontier.json``:

    {"platform", "captured_at", "loop": "pipelined",
     "points": [{"groups", "rate_group_rounds_per_s", "commit_p50_ms",
                 "commit_p50_rounds", "build_s"}, ...]}

and prints a markdown table for BENCH_NOTES.md (``--append-notes``
appends it under a dated heading).

    python -m etcd_tpu.tools.frontier_sweep            # platform defaults
    python -m etcd_tpu.tools.frontier_sweep --groups 1024,4096,16384
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

# The TPU sweep of the north-star plan (ISSUE 1); 131072 probes past
# the headline G for the throughput knee. CPU defaults stay small
# enough that the whole sweep (builds included) fits a CI-scale box.
TPU_GROUPS = [1024, 4096, 16384, 65536, 131072]
CPU_GROUPS = [256, 512, 1024, 4096]


def _log(msg: str) -> None:
    print(f"[frontier {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def _make_engine(groups: int, telemetry: bool = False):
    # The bench.py config and setup (BENCH_r05 methodology), from the
    # shared module so the sweep cannot desynchronize from bench.py.
    from .benchlib import make_bench_engine

    return make_bench_engine(groups, lanes_minor=True,
                             telemetry=telemetry)


def _pipeline_gate() -> None:
    """Refuse to measure a pipelined loop that diverges from
    single-round stepping (the shadow-verified path)."""
    import numpy as np

    a, props = _make_engine(64)
    b, _ = _make_engine(64)
    a.run_rounds_pipelined(48, chunk=8, tick=True, propose_n=props)
    for _ in range(48):
        b.step_round(tick=True, propose_n=props)
    for f in ("term", "role", "lead", "commit", "last"):
        # jitlint: waive(sync-in-loop) -- differential gate, not a hot path: one bulk gather per state field (5 total) to compare pipelined vs serial stepping
        av, bv = np.asarray(getattr(a.state, f)), np.asarray(
            getattr(b.state, f))
        assert (av == bv).all(), (
            f"pipelined loop diverged from single-round stepping on "
            f"{f}; refusing to record frontier numbers")
    _log("pipeline gate: pipelined == single-round stepping over 48 "
         "rounds at G=64")


def _measure_point(groups: int, rounds_per_call: int,
                   calls: int, telemetry: bool = False) -> dict:
    from .benchlib import measure_commit_p50, measure_rate

    t0 = time.perf_counter()
    eng, props = _make_engine(groups, telemetry)
    build_s = time.perf_counter() - t0
    _log(f"G={groups}: built+compiled in {build_s:.1f}s")

    # Throughput through the pipelined loop (bench.py's measurement,
    # shared via benchlib so the numbers stay comparable).
    rate = measure_rate(eng, props, rounds_per_call, calls,
                        pipelined=True)
    commits = eng.commits()
    assert commits.min() > 0
    _log(f"G={groups}: {rate:,.0f} group-rounds/s")

    p50_ms, rounds = measure_commit_p50(eng)
    _log(f"G={groups}: commit p50 {p50_ms:.2f}ms over {rounds} rounds")

    del eng, props
    gc.collect()
    return {
        "groups": groups,
        "rate_group_rounds_per_s": round(rate, 1),
        "commit_p50_ms": round(p50_ms, 2),
        "commit_p50_rounds": rounds,
        "build_s": round(build_s, 2),
    }


def _markdown(result: dict) -> str:
    lines = [
        "| G | group-rounds/s | commit p50 (ms) | rounds "
        "| build (s) |",
        "|---|---|---|---|---|",
    ]
    for p in result["points"]:
        lines.append(
            "| {groups} | {rate_group_rounds_per_s:,.0f} | "
            "{commit_p50_ms} | {commit_p50_rounds} | {build_s} |"
            .format(**p))
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", default="",
                    help="comma-separated G list (default per platform)")
    ap.add_argument("--out", default="artifacts/frontier.json")
    ap.add_argument("--rounds-per-call", type=int, default=16)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--telemetry", action="store_true",
                    help="compile the kernel telemetry plane into the "
                         "measured round (overhead sweep; ISSUE 4)")
    ap.add_argument("--skip-gate", action="store_true")
    ap.add_argument("--append-notes", default="",
                    help="append the markdown table to this file")
    args = ap.parse_args()

    from etcd_tpu.batched.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    _log(f"compile cache: {cache_dir}")

    import jax

    platform = jax.devices()[0].platform
    accelerated = platform == "tpu"
    if args.groups:
        group_list = [int(g) for g in args.groups.split(",")]
    else:
        group_list = TPU_GROUPS if accelerated else CPU_GROUPS
    _log(f"platform={platform} sweep G={group_list}")

    if not args.skip_gate:
        _pipeline_gate()

    result: dict = {
        "platform": platform,
        "device": str(jax.devices()[0]),
        "loop": "pipelined (run_rounds_pipelined chunk=%d depth=2)"
                % args.rounds_per_call,
        "telemetry": bool(args.telemetry),
        "compile_cache": cache_dir,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "captured_by": "tools/frontier_sweep.py",
        "points": [],
    }
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    def flush() -> None:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")

    for g in group_list:
        try:
            result["points"].append(
                _measure_point(g, args.rounds_per_call, args.calls,
                               args.telemetry))
        except Exception as e:  # noqa: BLE001 — partial frontier
            _log(f"G={g} failed: {e!r}; frontier stays partial")
            result.setdefault("failed", []).append(
                {"groups": g, "error": repr(e)})
        flush()

    table = _markdown(result)
    print(table)
    if args.append_notes:
        with open(args.append_notes, "a") as f:
            f.write(
                f"\n### Frontier sweep ({platform}, "
                f"{time.strftime('%Y-%m-%d')}, tools/frontier_sweep.py)"
                f"\n\n{table}\n")


if __name__ == "__main__":
    main()
