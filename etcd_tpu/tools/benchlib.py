"""Shared bench methodology: the canonical engine config and the
commit-p50 measurement.

``bench.py`` (the headline number) and ``chip_smoke.py`` (the engine
phase of the chip gate) must stay directly comparable to each other
and to the BENCH_r05 captures (2026-07-31) — same R/W/E config, same
election setup, same proposal load, same quiet-point commit-latency
loop — and ``benchmark/drivers/engine.py`` copies this set-up for the
``engine64k-r3`` configuration. A methodology tweak lands in one place.
"""

from __future__ import annotations

import time
from typing import Tuple


def make_bench_engine(groups: int):
    """Build the canonical bench engine (BENCH_r05 methodology: R=3,
    W=32, E=4, steady state with no timer elections, auto-compacting
    ring, the lane-filling minor layout, telemetry and fleet planes
    off), elect every group's slot-0 replica, and return the engine
    plus the steady 2-entries-per-group-per-round proposal vector."""
    import jax.numpy as jnp

    from ..batched import BatchedConfig, MultiRaftEngine

    cfg = BatchedConfig(
        num_groups=groups,
        num_replicas=3,
        window=32,
        max_ents_per_msg=4,
        max_props_per_round=2,
        election_timeout=1 << 20,  # steady state: no timer elections
        heartbeat_timeout=4,
        auto_compact=True,  # sustained load: ring chases the applied mark
        lanes_minor=True,  # the group axis fills the 128-wide lanes
    )
    eng = MultiRaftEngine(cfg)
    eng.campaign([g * cfg.num_replicas for g in range(groups)])
    eng.run_rounds(4, tick=False)
    assert (eng.leaders() == 0).all(), "election failed in bench setup"
    props = jnp.zeros((cfg.num_instances,), jnp.int32)
    props = props.at[jnp.arange(groups) * cfg.num_replicas].set(2)
    return eng, props


def measure_rate(eng, props, rounds_per_call: int, calls: int) -> float:
    """Steady-state group-rounds/s. The warmup compiles the
    chunk-sized scan program (rounds is a static arg); the timed
    region then drives sequential ``run_rounds`` calls (the BENCH_r05
    headline methodology)."""
    import jax

    eng.run_rounds(rounds_per_call, tick=True, propose_n=props)  # warmup
    jax.block_until_ready(eng.state.commit)
    t0 = time.perf_counter()
    for _ in range(calls):
        eng.run_rounds(rounds_per_call, tick=True, propose_n=props)
    jax.block_until_ready(eng.state.commit)
    dt = time.perf_counter() - t0
    return eng.cfg.num_groups * rounds_per_call * calls / dt


def measure_commit_p50(eng, max_rounds: int = 10) -> Tuple[float, int]:
    """Device commit p50: propose one entry per group at a quiet point,
    then step single rounds until every group's commit covers it — the
    wall-clock from propose to quorum-commit. All groups move in
    lockstep, so p50 == the common latency. Returns (ms, rounds)."""
    import jax
    import jax.numpy as jnp

    groups = eng.cfg.num_groups
    one = jnp.zeros((eng.cfg.num_instances,), jnp.int32)
    one = one.at[jnp.arange(groups) * eng.cfg.num_replicas].set(1)
    # Warm the single-round program (rounds is a static arg) and drain
    # the in-flight pipeline so the measurement starts quiesced.
    eng.run_rounds(1, tick=False, propose_n=one)
    for _ in range(4):
        eng.run_rounds(1, tick=False)
    jax.block_until_ready(eng.state.commit)
    base = eng.commits()[:, 0].min()
    t0 = time.perf_counter()
    eng.run_rounds(1, tick=False, propose_n=one)
    jax.block_until_ready(eng.state.commit)
    rounds = 1
    while eng.commits()[:, 0].min() <= base and rounds < max_rounds:
        eng.run_rounds(1, tick=False)
        # jitlint: waive(sync-in-loop) -- the sync IS the measurement: commit p50 is wall-clock from propose to observed quorum commit, one fence per round by definition
        jax.block_until_ready(eng.state.commit)
        rounds += 1
    return (time.perf_counter() - t0) * 1000, rounds
