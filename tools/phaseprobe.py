#!/usr/bin/env python
"""Device round-segment attribution: time each named_scope phase of the
batched round as its own jitted program and write the per-segment table
to ``artifacts/`` — which phase dominates the round as a tracked
artifact instead of one ad-hoc probe's folklore.

Method: a warmed ``MultiRaftEngine`` supplies a realistic steady-state
(leaders elected, proposals staged, inbox populated); each phase
function (``step._deliver_vectorized`` / ``_tick`` / ``_control`` /
``_propose`` / ``_emit``, vmapped over instances, plus ``route`` and
``pack_outbox``) is jitted in isolation, warmed once, then timed over
K dispatches with the result fenced — every timed call runs inside the
PR 7 transfer guard (``warm_guard``), so a smuggled host sync can't
fake a fast segment the way the r4 bench artifact did. Caveat recorded
in the artifact: the fused full round lets XLA overlap phases, so
isolated segments are an attribution of *relative* cost; their sum can
differ from the fused round time (both are reported).

Usage:
    python tools/phaseprobe.py [--groups 512] [--layout minor|major]
        [--rounds 32] [--out-dir artifacts] [--xprof DIR]

``--xprof DIR`` additionally captures a JAX profiler trace of the
fused-round timing loop (the named_scope annotations attribute device
time per phase in xprof — the capture that produced
artifacts/tpu_r05/xprof). This absorbs the old ad-hoc
tests/batched/phaseprobe.py probe.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from etcd_tpu.analysis.sentinels import warm_guard  # noqa: E402
from etcd_tpu.batched import step as step_mod  # noqa: E402
from etcd_tpu.batched.engine import MultiRaftEngine  # noqa: E402
from etcd_tpu.batched.state import BatchedConfig, I32  # noqa: E402


def _time_calls(name: str, fn, args, rounds: int) -> float:
    """Per-call seconds over `rounds` dispatches, first call unwarmed
    (compile, unguarded), the timed loop fenced + transfer-guarded."""
    key = f"phaseprobe/{name}"
    with warm_guard(key):
        jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    with warm_guard(key):
        for _ in range(rounds):
            out = fn(*args)
        jax.block_until_ready(out)  # the timing fence IS the measurement
    return (time.perf_counter() - t0) / rounds


def main() -> int:
    ap = argparse.ArgumentParser(
        description="per-phase device round attribution")
    ap.add_argument("--groups", type=int, default=512)
    ap.add_argument("--layout", choices=("minor", "major"),
                    default="minor")
    ap.add_argument("--rounds", type=int, default=32)
    ap.add_argument("--out-dir", default="artifacts")
    ap.add_argument("--xprof", default="", metavar="DIR",
                    help="capture a JAX profiler trace of the fused-"
                         "round loop into DIR (xprof attributes device "
                         "time per named_scope phase)")
    args = ap.parse_args()

    g = args.groups
    cfg = BatchedConfig(
        num_groups=g, num_replicas=3, window=32, max_ents_per_msg=4,
        max_props_per_round=2, election_timeout=1 << 20,
        heartbeat_timeout=4, auto_compact=True,
        lanes_minor=args.layout == "minor",
    )
    eng = MultiRaftEngine(cfg)
    eng.campaign([i * 3 for i in range(g)])
    eng.run_rounds(4, tick=False)
    assert (eng.leaders() == 0).all(), "warmup did not elect leaders"
    n = cfg.num_instances
    props = jnp.zeros((n,), I32).at[jnp.arange(g) * 3].set(2)
    ticks = jnp.ones((n,), bool)
    zb = jnp.zeros((n,), bool)
    zi = jnp.zeros((n,), I32)
    iids = jnp.arange(n, dtype=I32)
    slots = iids % 3
    st, inbox = eng.state, eng.inbox

    # Per-phase jitted programs over the SAME live state/inbox. The
    # per-instance phase functions vmap exactly as the round does
    # (major layout — segment ratios are what the probe tracks; the
    # lanes_minor transpose belongs to the fused round, measured via
    # the full-round reference below).
    phase_fns = {
        # deliver takes the inbox by kind lane and the batch-level
        # lane-occupancy vector exactly as the production round does
        # (computed outside the vmap → the lane skips stay real
        # branches).
        "deliver": (
            jax.jit(lambda _iids, _slots, _st, _inbox: jax.vmap(
                lambda iid, slot, sti, inb, la:
                step_mod._deliver_vectorized(
                    cfg, iid, slot, sti, inb, la),
                in_axes=(0, 0, 0, 0, None))(
                _iids, _slots, _st, step_mod.split_lanes(_inbox),
                jnp.any(_inbox.valid, axis=(0, 1)))),
            (iids, slots, st, inbox)),
        "tick": (
            jax.jit(jax.vmap(
                lambda iid, slot, sti, dt, dc:
                step_mod._tick(cfg, iid, slot, sti, dt, dc))),
            (iids, slots, st, ticks, zb)),
        "control": (
            jax.jit(jax.vmap(
                lambda slot, sti, tr, rr:
                step_mod._control(cfg, slot, sti, tr, rr))),
            (slots, st, zi, zb)),
        "propose": (
            jax.jit(jax.vmap(
                lambda slot, sti, nn:
                step_mod._propose(cfg, slot, sti, nn))),
            (slots, st, props)),
        "emit": (
            jax.jit(jax.vmap(
                lambda slot, sti: step_mod._emit(cfg, slot, sti))),
            (slots, st)),
    }
    scopes = {name: scope for layer, name, scope in step_mod.DEVICE_SCOPES
              if layer == "round program"}
    order = list(scopes)
    seg_s = {}
    for name in order:
        if name in phase_fns:
            fn, fargs = phase_fns[name]
            seg_s[name] = _time_calls(name, fn, fargs, args.rounds)
            print(f"{name}: {seg_s[name] * 1e3:.3f} ms", flush=True)
    # route runs on a real outbox (emit's request lanes and deliver's
    # response lanes, stacked as the eager round stacks them), like the
    # round does: the program engine.step_round dispatches (row shifts
    # and selects along N). Inside a scan it is inlined, by lane, and
    # fuses with its neighbours, so this segment is an upper bound there.
    _st1, resps = phase_fns["deliver"][0](iids, slots, st, inbox)
    _st2, reqs = phase_fns["emit"][0](slots, st)
    outbox = step_mod.stack_lanes(reqs + resps)
    seg_s["route"] = _time_calls(
        "route", lambda ob: step_mod.route(cfg, ob), (outbox,),
        args.rounds)
    print(f"route: {seg_s['route'] * 1e3:.3f} ms", flush=True)
    # pack_outbox: the hosted collect's on-device half (PR 6).
    seg_s["pack_outbox"] = _time_calls(
        "pack_outbox", step_mod.pack_outbox, (outbox, slots),
        args.rounds)
    print(f"pack_outbox: {seg_s['pack_outbox'] * 1e3:.3f} ms",
          flush=True)
    # Fused full-round reference (the program production actually runs).
    if args.xprof:
        with jax.profiler.trace(args.xprof):
            full_s = _time_calls(
                "full_round", eng._step,
                (st, inbox, ticks, zb, props, zb), args.rounds)
        print(f"xprof trace captured in {args.xprof}", flush=True)
    else:
        full_s = _time_calls(
            "full_round", eng._step, (st, inbox, ticks, zb, props, zb),
            args.rounds)
    print(f"full_round (fused): {full_s * 1e3:.3f} ms", flush=True)

    total = sum(seg_s.values())
    segments = [
        {
            "segment": name,
            "scope": scopes.get(name, name),
            "ms": round(seg_s[name] * 1e3, 4),
            "pct_of_segments": round(100 * seg_s[name] / total, 1),
        }
        for name in order + ["pack_outbox"] if name in seg_s
    ]
    backend = jax.devices()[0]
    result = {
        "metric": "round_segment_attribution",
        "config": (f"G={g} R=3 W=32 E=4 layout={args.layout} "
                   f"platform={backend.platform}"),
        "device": str(backend),
        "rounds_per_segment": args.rounds,
        "segments": segments,
        "segments_sum_ms": round(total * 1e3, 4),
        "full_round_fused_ms": round(full_s * 1e3, 4),
        "note": ("segments timed as isolated jitted programs under the "
                 "transfer guard; the fused round overlaps phases, so "
                 "the sum is an attribution baseline, not a wall-time "
                 "identity"),
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "captured_by": "tools/phaseprobe.py",
    }
    os.makedirs(args.out_dir, exist_ok=True)
    out_json = os.path.join(args.out_dir, "phaseprobe.json")
    with open(out_json, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    lines = [
        "# Device round-segment attribution (tools/phaseprobe.py)",
        "",
        f"Config: `{result['config']}`, {args.rounds} timed rounds per "
        f"segment; fused full round {result['full_round_fused_ms']} ms.",
        "",
        "| segment | named_scope | ms | % of segments |",
        "|---|---|---|---|",
    ]
    for s in segments:
        lines.append(f"| {s['segment']} | {s['scope']} | {s['ms']} "
                     f"| {s['pct_of_segments']} |")
    lines.append("")
    lines.append(result["note"] + ".")
    out_md = os.path.join(args.out_dir, "PHASEPROBE.md")
    with open(out_md, "w") as f:
        f.write("\n".join(lines) + "\n")
    print(f"wrote {out_json} and {out_md}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
