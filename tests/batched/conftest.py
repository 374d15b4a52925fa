"""Batched-suite configuration: runtime sentinels (ISSUE 7).

Two session-wide guards ride every test in this directory:

* **Transfer guard** — ETCD_TPU_TRANSFER_GUARD=disallow makes every
  warm engine/rawnode round dispatch run under
  ``jax.transfer_guard("disallow")`` (see analysis/sentinels.round_guard
  and the warm_guard call sites in engine.py/rawnode.py): an implicit
  transfer smuggled into the steady-state loop — an eager scalar op, a
  concretized tracer — fails the test instead of shipping as a silent
  per-round sync (the BENCH r4 675M/s artifact class).

* **Compile-shape budget** — the declared number of distinct
  round-step programs (config x aux variants, counted by
  step._step_round_jit via analysis.sentinels) a full batched-suite
  session may build. Tier-1 runs within ~15s of its 870s timeout
  (ROADMAP), and every additional config is a fresh trace+compile, so
  a PR that adds one must bump this number CONSCIOUSLY — with the
  tier-1 margin re-checked — rather than discover the truncation line
  moved. Sharing an existing module's config is free; a novel config
  costs budget.
"""

import os

import pytest

# Must be set before any engine dispatches; harmless for processes that
# never read it. Member subprocesses (hosting_proc / e2e tests) inherit
# it, so the guard also covers the multi-process hosting path.
os.environ.setdefault("ETCD_TPU_TRANSFER_GUARD", "disallow")

# The declared tier-1 compile-shape budget for the round-step program.
# RE-MEASURED at ISSUE 13: a full `pytest tests/ -m 'not slow'`
# session builds 39 distinct (config, aux) round programs — 36 from
# tests/batched plus 3 single-group configs from the raft-node/
# raftexample suites (the session fixture counts process-wide). The
# old declaration (18+2) had drifted stale over several PRs WITHOUT
# the sentinel firing, because tier-1 used to truncate at its 870s
# timeout before this file's tests ran; a faster box reached them and
# exposed the gap (34 of the 36 batched shapes are built before
# test_sentinels; ISSUE 13's test_wal_pipeline adds zero — it shares
# the chaos CFG). Headroom of 2 absorbs parametrization drift without
# hiding a real regression class (one accidental config fork per PR
# compounds into minutes of compile). If you bump this, list WHICH
# config you added, and prefer sharing an existing module's config —
# `sentinels.compile_keys("round_step")` names every key.
#
# ISSUE 17 AUDIT: 43 (ISSUE 14 had made it so: deliver_shape on every
# key, a third lockstep program and test_deliver_shapes' hosted
# narrow-lanes rawnode; ISSUE 30 below took the shapes away again).
# test_lifecycle reuses test_chaos.CFG
# VALUES verbatim (every lifecycle knob — snap_cadence, snap_keep,
# wal_rotate_bytes, wal_pinned_segments — is a host-side member arg,
# not a BatchedConfig field, so it never enters the compile key), and
# the invariant-sweep ring_over_window bit + fleet-frame ring fields
# changed layout VALUES inside existing programs, not program COUNT.
# The G=1024 lifecycle soak config is slow-marked (outside tier-1).
#
# ISSUE 19 AUDIT: still 43. The device apply plane is a SEPARATE
# jitted program with its own compile-key kind ("apply_plane": the
# dispatch per (C, WS, A, n) plus the snapshot gather per batch
# width — counted there, never here), and make_step_round keys
# step._step_round_jit on cfg.apply_plane_key(), which strips every
# apply_* knob to defaults BEFORE keying: apply_plane=True therefore
# shares the plane-off round program STRUCTURALLY, not by luck
# (test_applyplane asserts zero new round-step keys across a full
# plane-on drive). The unconditional lease tick lane + the
# lease_on_nonleader invariant bit changed program CONTENT inside
# every existing key, not key COUNT; test_applyplane's engine pair
# reuses test_fleet's CFG_OFF values and its hosted/chaos cells
# reuse test_chaos.CFG values verbatim.
#
# ISSUE 25 AUDIT: 44 used of 45 (PR 21's tests/test_chip_smoke.py had
# made it 42 of 43). test_route adds two programs: the n-minor twin of
# test_pipelined.make_engine(4) (lanes_minor=True had no engine test
# of its own; the n-major half of that parity test reuses
# make_engine(4)'s values) and one config nobody else may build
# (G=3, n-minor, narrow), because the compile-count test has to see
# the round and route() compile. route() itself is keyed by R alone
# and counted nowhere here.
#
# ISSUE 28 AUDIT: 48 used of 48, counted (a whole `pytest tests/ -m 'not
# slow'` session in ONE process, 1,518 passed in 759 s, the sentinel's
# own message naming 48 keys), not reckoned: the parent stood at 46 of
# 45 (PR 26's `engine10k-r5` at the benchmark tests' 8 groups was never
# audited; the driver's run is split over six xdist workers, each a
# session of its own, so the sentinel never saw it). test_scan_faults
# adds two programs: the benchmark's `engine100k-r3` at the CPU tests'
# 8 groups (etcd's raft defaults: election 10, heartbeat 1, pre_vote,
# check_quorum; n-minor, telemetry on), which
# tests/benchmark builds too for the cell's tiny run, its controls and
# its broken-path tests, and that config's `merged` twin (gone with
# the shape in ISSUE 30). The scan-
# against-single-rounds test's other two engines reuse values that are
# built already (`engine10k-r5` at 8 groups, R=5 n-minor, from
# tests/benchmark; test_pipelined.make_engine(4), R=3 n-major), and the
# hash tests build no round at all. The scan's per-round fault schedule
# is an input of the closed-loop program, not of the round step: no key
# there either
# (test_without_a_schedule_the_scan_gains_no_input_and_no_key). Of the
# raise by three, one unit repairs PR 26's count and two are this PR's.
# ISSUE 29 AUDIT: still 48. test_deliver_default builds engines on
# test_scan_faults' CELL and R5 (the same values, so the same keys)
# and lowers without compiling.
# ISSUE 30 AUDIT: 44 used of 46, counted (a whole `pytest tests/ -m 'not
# slow'` session in ONE process, 1,531 passed in 905 s, the keys dumped
# at session end; the parent's tree counted the same way stood at 48 of
# 48). deliver_shape rides every config key and reads 'vectorized' in
# all 44: deliver has one shape (ISSUE 14 had made it three, and the
# differential config a trio of programs). Five keys went: the `lanes`
# and `merged` members of that trio at G=2 and at G=1
# (test_differential's partition test and test_features' make_pair(1)
# ran those; at G=1 the one shape was a key already), and
# test_scan_faults' CELL in `merged`. One came: the differential config
# with laneskip=0 (test_deliver_shapes' lane_skip twin: the one fork
# left in deliver had no direct test). test_deliver_shapes' hosted
# narrow-lanes rawnode (aux=True, ISSUE 14) stays. Budget 48 -> 46
# keeps the headroom of 2.
# ISSUE 31 AUDIT: still 44 of 46. test_route's new cases build engines
# on values that are keys already: its own cfg_of(4, 3) pair, the
# n-minor one again for the scan's jaxpr, test_scan_faults' CELL and
# test_differential_wide.make_pair(2, 10, auto_compact=True) for the
# election schedule. route() by lane is keyed by R alone, like route();
# the inbox handed to the round as six lanes is a second trace of the
# same `jit(step_round)`, inside the scan, and no key.
# ISSUE 32 AUDIT: 46 used of 48. test_scan_reconf adds two programs,
# both with `conf_entries` (a configuration change as an entry of the
# device's log: new state lanes, so new programs): RC3, the values of
# the benchmark's `engine1m-r3` at the CPU tests' 8 groups (R=3,
# n-minor, telemetry on), which tests/benchmark builds too for the
# cell's tiny runs, its controls and its broken-path tests; and RC5
# (R=5, n-major, telemetry off), so that both R, both layouts and the
# plane on and off stand against the oracle with two programs and not
# eight. The control schedule, like the fault schedule, is an input of
# the closed-loop program and no key of the round step
# (test_without_a_schedule_the_scan_is_the_parents), and every other
# engine of that file is built on CELL, a key since ISSUE 28. Budget
# 46 -> 48: raised by exactly the two, the headroom of 2 kept.
# ISSUE 33 AUDIT: 47 used of 48, no raise. test_route's cases for the
# outbox's two forms build on keys that are there (test_scan_faults'
# CELL, R5 and R3_MAJOR, test_scan_reconf's RC3 and RC5, this file's
# own n-minor pair and its narrow G=3, test_deliver_shapes' lane_skip
# twin and its hosted narrow-lanes rawnode) and add ONE: R=5, n-minor,
# narrow lanes, laneskip=0 (cfg_of(4, 5, lanes_minor=True,
# narrow_lanes=True)): R=5 had no narrow and no lane_skip=False
# program to hold the two forms against each other. The round handed
# lanes or slots is two traces of one `jit(step_round)`, no key.
# ISSUE 34 AUDIT: 49 used of 50. test_scan_replace adds two programs,
# both with `replace_replicas` (replicas born and retired on the
# device, a snapshot that states the configuration: new round text, so
# new programs): RP4, the values of
# the benchmark's `engine512k-r3of4` at the CPU tests' 8 groups (R=4,
# n-minor, telemetry on), which tests/benchmark builds too for the
# cell's tiny runs, its controls and its broken-path tests (one of
# which clears step._step_round_jit's cache to swap the snapshot
# handler: the same key string, counted once); and RP4_MAJOR (n-major,
# telemetry off, 4 groups), so that both layouts and the plane on and
# off stand against the oracle. The wipe and the two control columns
# are inputs of the round and of the closed loop, no key; the four
# text-for-text cases lower the live configurations at 8 groups, which
# are keys since ISSUE 28 and 32 (CELL, R5, RC3) but for `engine64k-r3`
# at 8 groups, which tests/benchmark builds. Budget 48 -> 50: raised by
# exactly the two, the headroom of 1 that ISSUE 33 left kept.
# ISSUE 35 AUDIT: still 49 of 50. test_scan_tiles builds the five live
# configurations at 8 groups, values that are keys already (CELL, R5,
# RC3, RP4 and `engine64k-r3` at 8 groups). A tile of the closed loop
# calls the configuration's own `jit(step_round)` on fewer rows
# (make_step_round with the tile's iids): another trace of one key.
# ISSUE 40 AUDIT: still 49 of 50, raised by exactly the programs the
# configuration brings, which is none. `engine1m-r3of4-x4` is
# `engine512k-r3of4`'s BatchedConfig at another num_groups, and at the
# CPU tests' 8 groups the same key (RP4: tests/benchmark/test_nodes.py's
# tiny run, test_scopes' node-placed case). An engine placed over nodes
# (`MultiRaftEngine(nodes=...)`) hands the configuration's own
# `jit(step_round)` a node's rows with the logical iids, inside
# `shard_map`: another trace of one key, as a tile is. test_scan_nodes
# runs its node-placed engines in child processes (RP4 there too), which
# this session's sentinel does not see.
# ISSUE 42 AUDIT: still 49 of 50, raised by exactly the programs the
# phased schedule's tests bring, which is none. test_scan_phased builds
# every engine on RP4 (one case on RC3), keys since ISSUE 34 and 32, and
# tests/benchmark/test_trickle.py drives `engine768k-r3of4-rebalance`,
# which is `engine512k-r3of4`'s BatchedConfig to the digit, at the CPU
# tests' 8 groups: RP4 again. A phased schedule, like the lockstep one,
# is an input of the closed-loop program and no key of the round step.
# ISSUE 45 AUDIT: still 49 of 50. test_own_term runs the schedules of
# test_differential, test_scan_faults, test_scan_reconf and
# test_scan_replace again and builds its own engines on RC3, RC5 and
# RP4: keys all. `own_from` is a field of the state and no field of the
# configuration; the cases that force emit's bit clear
# step._step_round_jit's cache to trace a key's round anew (the same key
# string, counted once, as tests/benchmark/test_replace.py does).
# ISSUE 47 AUDIT: still 49 of 50. test_scan_load builds every engine on
# RC3 (the benchmark's `engine1m-r3` values at 8 groups; one of them
# over three of the forced devices, for the refusal: another trace of
# the same key), and tests/benchmark/test_load.py drives the cell
# `engine1m-r3-zipf.ycsb-a`, whose sizes are `engine1m-r3`'s to the
# digit: RC3 again. A load plane, like the schedules, is an input of the
# closed-loop program and no key of the round step.
# ISSUE 49 AUDIT: 50 used of 51. test_tick_campaign builds its engines
# on test_scan_faults' CELL and R3_MAJOR and on test_scopes' five live
# configurations at 8 groups: keys all (the P-column spelling it holds
# the round against re-traces a key's round, the same key string).
# test_ring_layout adds ONE: `engine64k-r3` at 256 groups (768 rows:
# the smallest batch at which the TPU compiler sinks tick's ring
# broadcast into the last deliver cond; at 8 groups it lays a 24-row
# ring out ring-minor everywhere and sinks nothing), traced, lowered
# and compiled for a described v5e twice (16 s) and never built for
# the CPU. Budget 50 -> 51: raised by exactly the one, the headroom of
# 1 kept.
# ISSUE 50 AUDIT: 52 used of 53. The log as term runs
# (`BatchedConfig.log_runs`) is a field of the compile key, and two
# configurations carry it: test_deep_log's DEEP (window 512, K=8, E=16
# at 8 groups: the differential against the oracle wants a small deep
# window, so that a node away for 96 rounds returns twelve appends
# behind) and tests/benchmark/test_catchup.py's tiny root of the cell
# `engine100k-r3-deeplog.reboot-catchup` (window 10,240, K=32, E=64 at
# 8 groups: the cell's own sizes, as every tests/benchmark cell runs
# its own). Budget 51 -> 53: raised by exactly the two, the headroom of
# 1 kept.
# ISSUE 51 AUDIT: 53 used of 54. test_bulk_lane builds its engines on
# two keys that are there (the cell `engine100k-r3-deeplog`'s sizes at 8
# groups, tests/benchmark/test_catchup.py's; the values of
# test_differential_wide.make_pair(2, 10, auto_compact=True)), and the
# append lane in one piece beside the split one is another trace of the
# same key's round (the round answers in the form it is handed: no
# field, no flag, no key). test_ring_layout adds ONE: that cell's sizes
# at 256 groups (768 rows, as ISSUE 49's case and for its reason),
# traced, lowered and compiled for a described v5e once (11 s) and
# never built for the CPU. Budget 53 -> 54: raised by exactly the one,
# the headroom of 1 kept.
ROUND_STEP_SHAPE_BUDGET = 54


@pytest.fixture(scope="session", autouse=True)
def compile_shape_budget_sentinel():
    """Fail the session when the suite built more distinct round-step
    programs than declared above (the recompile sentinel's session
    face; per-wrapper cache-miss counting lives in
    analysis.sentinels.CompileBudget)."""
    yield
    from etcd_tpu.analysis import sentinels

    used = sentinels.distinct_shapes("round_step")
    if used > ROUND_STEP_SHAPE_BUDGET:
        keys = "\n  ".join(sorted(sentinels.compile_keys("round_step")))
        pytest.fail(
            f"compile-shape budget exceeded: {used} distinct round-step "
            f"programs > declared {ROUND_STEP_SHAPE_BUDGET} "
            f"(tests/batched/conftest.py). Share an existing config or "
            f"bump the budget consciously — tier-1 runs ~15s from its "
            f"timeout and every config is a fresh compile.\n  {keys}")
