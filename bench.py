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

Kernel layout ([N, R] instance-major vs [R, N] instance-minor): the
lane-filling minor layout runs unless BENCH_LAYOUT=major|minor pins
one. A layout that fails to build fails the bench.

Persistent compile cache: every engine build routes XLA compilations
through the on-disk cache (batched/compile_cache.py:
JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache), so the second
bench of an identical config pays a disk hit instead of the full
compile. Build time is logged so warm/cold is visible in the stderr
trace.

Round pipelining: BENCH_PIPELINE=1 drives the measured loop through
`run_rounds_pipelined` (double-buffered chunks, donated state; chunk
k+1 enqueued while chunk k runs) instead of sequential `run_rounds`
calls — the dispatch-gap experiment knob. Default off.

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


def _make_engine(groups: int, lanes_minor: bool,
                 telemetry: bool = False,
                 fleet: bool = False):
    # Canonical config + setup shared with tools/frontier_sweep.py so
    # the two tools' numbers stay methodologically comparable.
    from etcd_tpu.tools.benchlib import make_bench_engine

    return make_bench_engine(groups, lanes_minor,
                             telemetry=telemetry, fleet=fleet)


def _rate(eng, props, rounds_per_call: int, calls: int,
          pipelined: bool = False) -> float:
    from etcd_tpu.tools.benchlib import measure_rate

    return measure_rate(eng, props, rounds_per_call, calls,
                        pipelined=pipelined)


def main() -> None:
    # Transfer sentinel (ISSUE 7): every warm round dispatch runs under
    # jax.transfer_guard("disallow") — an implicit transfer in the
    # measured loop is a hard error, not a silent per-round sync that
    # ships a fake record (the r4 675M/s artifact class). Overhead is
    # below box noise (BENCH_NOTES r7). Opt out: ETCD_TPU_TRANSFER_GUARD=.
    os.environ.setdefault("ETCD_TPU_TRANSFER_GUARD", "disallow")
    import jax

    from etcd_tpu.batched.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU metric and found platform "
            f"{platform!r} ({jax.devices()[0].device_kind}); "
            "run it on the chip")
    _note(f"compile cache: {enable_compile_cache()}")

    layout_env = os.environ.get("BENCH_LAYOUT", "")
    if layout_env and layout_env not in ("major", "minor"):
        raise SystemExit(f"BENCH_LAYOUT must be major|minor, got {layout_env!r}")
    pipe_env = os.environ.get("BENCH_PIPELINE", "")
    if pipe_env and pipe_env not in ("0", "1"):
        raise SystemExit(f"BENCH_PIPELINE must be 0|1, got {pipe_env!r}")
    pipelined = pipe_env == "1"
    # BENCH_TELEMETRY=1 compiles the kernel telemetry plane (ISSUE 4)
    # into the measured round — the overhead-measurement knob backing
    # the BENCH_NOTES telemetry-off/on row. Headline default: off.
    tel_env = os.environ.get("BENCH_TELEMETRY", "")
    if tel_env and tel_env not in ("0", "1"):
        raise SystemExit(
            f"BENCH_TELEMETRY must be 0|1, got {tel_env!r}")
    telemetry = tel_env == "1"
    # BENCH_FLEET=1 compiles the fleet-summary plane (ISSUE 10) into
    # the measured round — the overhead knob backing the BENCH_NOTES
    # fleet row (tools/fleet_overhead.py interleaves on/off runs).
    flt_env = os.environ.get("BENCH_FLEET", "")
    if flt_env and flt_env not in ("0", "1"):
        raise SystemExit(f"BENCH_FLEET must be 0|1, got {flt_env!r}")
    fleet = flt_env == "1"
    # The lane-filling layout ([R*K, N]: the group axis fills the
    # 128-wide vector lanes) unless pinned.
    lanes_minor = layout_env != "major"
    t0 = time.perf_counter()
    eng, props = _make_engine(GROUPS, lanes_minor, telemetry, fleet)
    _note(f"main G={GROUPS} built+compiled in {time.perf_counter()-t0:.1f}s")
    rate = _rate(eng, props, 16, 8, pipelined=pipelined)
    _note(f"main rate: {rate:.0f} group-rounds/s")
    commits = eng.commits()
    assert commits.min() > 0

    from etcd_tpu.tools.benchlib import measure_commit_p50

    commit_p50_ms, rounds = measure_commit_p50(eng)

    print(
        json.dumps(
            {
                "metric": "raft_groups_stepped_per_sec",
                "value": round(rate, 1),
                "unit": (
                    f"group-rounds/s ({platform}, G={GROUPS}, R=3, "
                    f"layout={'minor' if lanes_minor else 'major'}, "
                    f"deliver={eng.cfg.deliver_shape}, "
                    f"loop={'pipelined' if pipelined else 'serial'}, "
                    f"telemetry={'on' if telemetry else 'off'}, "
                    f"fleet={'on' if fleet else 'off'}, "
                    f"commit_p50={commit_p50_ms:.2f}ms/{rounds}r)"
                ),
                "vs_baseline": round(rate / 1e6, 4),
            }
        )
    )


if __name__ == "__main__":
    main()
