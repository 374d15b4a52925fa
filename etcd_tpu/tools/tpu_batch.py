"""One-command TPU measurement batch (BENCH_NOTES round-5 task).

Fires EVERY open TPU perf question in one run, so one chip call (and
one compile cache) covers them all:

  (a) re-capture the G=65536 headline rate (six-lane deliver, the
      bench.py default config) so the driver record can be confirmed;
  (b) deliver-shape A/B ON TPU — merged scans and the ISSUE 14
      vectorized fold vs the six-lane baseline (--deliver-shape; CPU
      has not predicted TPU for this kernel before, so the accelerator
      default only ever moves on numbers from this section);
  (c) the Pallas fused quorum/ring kernels vs their XLA forms
      (integration gate, pallas_kernels.py docstring);
  (d) device-side commit p50 — rounds-to-commit counted by stepping
      single rounds (correctness only), priced at the per-round wall
      time of the async multi-round scans, NOT at the host round trip
      of a single dispatch (the round-4 number was dispatch-dominated);
  (e) an xprof trace of the steady-state round (best effort).

Writes <--out>/batch.json with every number + provenance and appends
nothing anywhere else.

    python -m etcd_tpu.tools.tpu_batch [--groups 65536]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _log(msg: str) -> None:
    print(f"[tpu_batch {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def _make_engine(groups: int, shape: str):
    import jax.numpy as jnp

    from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

    cfg = BatchedConfig(
        num_groups=groups,
        num_replicas=3,
        window=32,
        max_ents_per_msg=4,
        max_props_per_round=2,
        election_timeout=1 << 20,
        heartbeat_timeout=4,
        auto_compact=True,
        lanes_minor=True,  # pinned lane-filling layout (bench.py on TPU)
        deliver_shape=shape,
    )
    eng = MultiRaftEngine(cfg)
    eng.campaign([g * cfg.num_replicas for g in range(groups)])
    eng.run_rounds(4, tick=False)
    assert (eng.leaders() == 0).all(), "election failed in batch setup"
    props = jnp.zeros((cfg.num_instances,), jnp.int32)
    props = props.at[jnp.arange(groups) * cfg.num_replicas].set(2)
    return eng, props


def _rate(eng, props, rounds_per_call: int = 16, calls: int = 8) -> float:
    import jax

    eng.run_rounds(rounds_per_call, tick=True, propose_n=props)  # warmup
    jax.block_until_ready(eng.state.commit)
    t0 = time.perf_counter()
    for _ in range(calls):
        eng.run_rounds(rounds_per_call, tick=True, propose_n=props)
    jax.block_until_ready(eng.state.commit)
    dt = time.perf_counter() - t0
    return eng.cfg.num_groups * rounds_per_call * calls / dt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=65536)
    ap.add_argument("--out", default="chiprun_out/tpu_batch")
    ap.add_argument("--deliver-shape", dest="deliver_shapes",
                    default="merged,vectorized",
                    help="comma-separated deliver shapes to A/B "
                         "against the six-lane baseline (section b)")
    args = ap.parse_args()
    args.deliver_shapes = [s.strip() for s in
                           args.deliver_shapes.split(",") if s.strip()]

    import jax
    import jax.numpy as jnp

    from ..batched.compile_cache import enable_compile_cache

    # Persistent XLA cache: a re-fired batch pays disk hits instead of
    # recompiles.
    _log(f"compile cache: {enable_compile_cache()}")

    platform = jax.devices()[0].platform
    _log(f"platform={platform} devices={jax.devices()}")
    os.makedirs(args.out, exist_ok=True)
    result: dict = {
        "platform": platform,
        "device": str(jax.devices()[0]),
        "groups": args.groups,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "captured_by": "builder (tools/tpu_batch.py)",
    }

    def flush() -> None:
        with open(os.path.join(args.out, "batch.json"), "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")

    # ---- (a) headline capture: six-lane deliver, bench.py config ----
    t0 = time.perf_counter()
    eng, props = _make_engine(args.groups, "lanes")
    compile_s = time.perf_counter() - t0
    _log(f"(a) six-lane G={args.groups} built+compiled in {compile_s:.0f}s")
    rate_six = _rate(eng, props)
    commits = eng.commits()
    assert commits.min() > 0
    _log(f"(a) six-lane rate: {rate_six:,.0f} group-rounds/s")
    result["a_six_lane"] = {
        "rate_group_rounds_per_s": round(rate_six, 1),
        "compile_s": round(compile_s, 1),
        "config": "G=%d R=3 W=32 layout=minor deliver=lanes"
                  % args.groups,
        "commits_min": int(commits.min()),
    }
    flush()

    # ---- (d) device-side commit p50 ----
    # rounds-to-commit: counted with single-round steps (each pays a
    # host dispatch but only the ROUND COUNT is used, never the wall time);
    # priced at the per-round wall time of the pipelined scan above.
    one = jnp.zeros((eng.cfg.num_instances,), jnp.int32)
    one = one.at[jnp.arange(args.groups) * eng.cfg.num_replicas].set(1)
    eng.run_rounds(1, tick=False, propose_n=one)  # warm 1-round program
    for _ in range(4):
        eng.run_rounds(1, tick=False)
    jax.block_until_ready(eng.state.commit)
    base = int(eng.commits()[:, 0].min())
    eng.run_rounds(1, tick=False, propose_n=one)
    rounds_to_commit = 1
    while int(eng.commits()[:, 0].min()) <= base and rounds_to_commit < 10:
        eng.run_rounds(1, tick=False)
        rounds_to_commit += 1
    timed_out = int(eng.commits()[:, 0].min()) <= base
    per_round_s = args.groups / rate_six  # seconds per round at steady state
    p50_us = rounds_to_commit * per_round_s * 1e6
    _log(f"(d) rounds_to_commit={rounds_to_commit}, per-round "
         f"{per_round_s*1e6:.1f}us -> device-side commit p50 "
         f"{p50_us:.1f}us timed_out={timed_out}")
    result["d_commit_p50"] = {
        "rounds_to_commit": rounds_to_commit,
        "timed_out": timed_out,
        "per_round_us": round(per_round_s * 1e6, 2),
        "commit_p50_us_device_side": round(p50_us, 2),
        "note": "round count from single-round stepping (count only); "
                "priced at steady-state per-round wall time, not one "
                "dispatch's",
    }
    flush()

    # ---- (e) xprof trace (best effort) ----
    trace_dir = os.path.join(args.out, "xprof")
    try:
        with jax.profiler.trace(trace_dir):
            eng.run_rounds(16, tick=True, propose_n=props)
            jax.block_until_ready(eng.state.commit)
        has_files = any(files for _, _, files in os.walk(trace_dir))
        result["e_xprof"] = {"ok": has_files, "dir": trace_dir}
        _log(f"(e) xprof trace saved={has_files} -> {trace_dir}")
    except Exception as e:  # noqa: BLE001 — profiling is best-effort
        result["e_xprof"] = {"ok": False, "error": repr(e)}
        _log(f"(e) xprof failed: {e!r}")
    flush()
    del eng, props

    # ---- (c) Pallas kernels vs XLA forms ----
    try:
        from etcd_tpu.tools import pallas_bench

        import contextlib
        import io

        saved_argv = sys.argv
        buf = io.StringIO()
        try:
            sys.argv = ["pallas_bench"]
            with contextlib.redirect_stdout(buf):
                pallas_bench.main()
        finally:
            sys.argv = saved_argv
        result["c_pallas"] = {"ok": True, "report": buf.getvalue()}
        _log("(c) pallas_bench:\n" + buf.getvalue())
    except Exception as e:  # noqa: BLE001 — keep the batch going
        result["c_pallas"] = {"ok": False, "error": repr(e)}
        _log(f"(c) pallas_bench failed: {e!r}")
    flush()

    # ---- (b) deliver-shape A/B ON TPU (--deliver-shape picks the
    # comparison set; default covers merged + the ISSUE 14 vectorized
    # fold — the on-device tuning the r5 notes demanded, in one
    # command) ----
    for shape in args.deliver_shapes:
        key = f"b_deliver_{shape}"
        try:
            t0 = time.perf_counter()
            eng2, props2 = _make_engine(args.groups, shape)
            compile2_s = time.perf_counter() - t0
            _log(f"(b) {shape} G={args.groups} built+compiled in "
                 f"{compile2_s:.0f}s")
            rate_shape = _rate(eng2, props2)
            assert eng2.commits().min() > 0
            _log(f"(b) {shape} rate: {rate_shape:,.0f} group-rounds/s "
                 f"({rate_shape / rate_six:.2f}x six-lane)")
            result[key] = {
                "rate_group_rounds_per_s": round(rate_shape, 1),
                "compile_s": round(compile2_s, 1),
                "vs_six_lane": round(rate_shape / rate_six, 3),
            }
            del eng2, props2
        except Exception as e:  # noqa: BLE001
            result[key] = {"ok": False, "error": repr(e)}
            _log(f"(b) {shape} deliver failed: {e!r}")
        flush()

    _log("batch complete")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
