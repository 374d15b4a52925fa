"""Benchmark: Raft groups stepped per second on one TPU chip.

Runs the batched multi-Raft engine closed-loop (deliver → tick →
propose → emit → route, all on device) with every group leader-elected
and a steady proposal load, and measures group-rounds per wall-second
at G=65536, R=3.

One group-step = one group of R replicas processing a full message round
(every inbox lane, commit-quorum reduction included). The north-star
target (BASELINE.md) is ≥1M groups stepped/sec/chip; `vs_baseline` is
value / 1e6 against that target. For calibration, the reference's
headline single-group figure is 10k writes/sec (ref: README.md:21).

The number is a device metric, so the bench needs the device: this
process initializes JAX itself, and with any backend but ``tpu`` it
exits non-zero before building anything and prints no rate. It starts
no child process.

It runs the one configuration BENCHMARK.json's ``engine64k-r3`` source
line names: the lane-filling [R, N] instance-minor layout, sequential
``run_rounds`` calls, telemetry and fleet planes off.

Persistent compile cache: every engine build routes XLA compilations
through the on-disk cache (batched/compile_cache.py:
JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), so the second
bench of an identical config pays a disk hit instead of the full
compile. Build time is logged so warm/cold is visible in the stderr
trace.

Prints exactly one JSON line: {"metric", "value", "unit", "vs_baseline"}
with commit-p50 detail inside "unit".
"""

import json
import os
import sys
import time

GROUPS = 65536


def _note(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


def main() -> None:
    # Transfer sentinel (ISSUE 7): every warm round dispatch runs under
    # jax.transfer_guard("disallow") — an implicit transfer in the
    # measured loop is a hard error, not a silent per-round sync that
    # ships a fake record (the r4 675M/s artifact class).
    # Opt out: ETCD_TPU_TRANSFER_GUARD=.
    os.environ.setdefault("ETCD_TPU_TRANSFER_GUARD", "disallow")
    import jax

    from etcd_tpu.batched.compile_cache import enable_compile_cache
    from etcd_tpu.tools.benchlib import (
        make_bench_engine,
        measure_commit_p50,
        measure_rate,
    )

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU metric and found platform "
            f"{platform!r} ({jax.devices()[0].device_kind}); "
            "run it on the chip")
    _note(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    eng, props = make_bench_engine(GROUPS)
    _note(f"main G={GROUPS} built+compiled in {time.perf_counter()-t0:.1f}s")
    rate = measure_rate(eng, props, 16, 8)
    _note(f"main rate: {rate:.0f} group-rounds/s")
    commits = eng.commits()
    assert commits.min() > 0

    commit_p50_ms, rounds = measure_commit_p50(eng)

    print(
        json.dumps(
            {
                "metric": "raft_groups_stepped_per_sec",
                "value": round(rate, 1),
                "unit": (
                    f"group-rounds/s ({platform}, G={GROUPS}, R=3, "
                    f"layout=minor, deliver={eng.cfg.deliver_shape}, "
                    "loop=serial, telemetry=off, fleet=off, "
                    f"commit_p50={commit_p50_ms:.2f}ms/{rounds}r)"
                ),
                "vs_baseline": round(rate / 1e6, 4),
            }
        )
    )


if __name__ == "__main__":
    main()
