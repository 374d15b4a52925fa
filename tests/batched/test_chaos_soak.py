"""Full chaos matrix soak (ISSUE 2 acceptance): ≥3 seeds × {InProcRouter,
TCP fabric} × {message faults, crash/restart, torn tail}, each episode
closed out by all three checkers — KV-hash parity, committed-never-lost,
single-leader-per-term — at STRICT parity (no allow_lag) since ISSUE 5's
durability fence closed the last torn-tail carve-out. Long-running:
behind `-m slow` (excluded from tier-1); reproduce one seed with
ETCD_TPU_CHAOS_SEED=<seed>.
"""

import json
import os
import time

import pytest

from etcd_tpu.batched.faults import (
    ChaosHarness,
    FaultSpec,
    LeaderObserver,
    run_invariant_checks,
)
from etcd_tpu.batched.state import BatchedConfig
from etcd_tpu.functional import check_config_safety
from etcd_tpu.pkg import failpoint

pytestmark = [pytest.mark.chaos, pytest.mark.slow]

G, R = 64, 3
CFG = BatchedConfig(
    num_groups=G, num_replicas=R, window=16, max_ents_per_msg=4,
    max_props_per_round=4, election_timeout=10, heartbeat_timeout=1,
    pre_vote=True, check_quorum=True, auto_compact=True,
    # Kernel telemetry on for the soak: the on-device invariant sweep
    # watches every round, and a checker failure dumps each member's
    # flight recorder to artifacts/flightrec_*.json (ISSUE 4).
    telemetry=True,
)

SEEDS = tuple(
    int(s) for s in
    os.environ.get("ETCD_TPU_CHAOS_SEED", "7,11,13").split(",")
)
TRANSPORTS = ("inproc", "tcp")

SOAK_FAULTS = FaultSpec(drop=0.08, dup=0.08, delay=0.1,
                        delay_max_s=0.08, reorder=0.3)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    failpoint.disable_all()


def full_check(h, obs, allow_lag=0):
    run_invariant_checks(h, obs, expect_members=R,
                         hash_timeout=90.0, acked_timeout=45.0,
                         allow_lag=allow_lag)


@pytest.mark.parametrize("transport", TRANSPORTS)
@pytest.mark.parametrize("seed", SEEDS)
class TestChaosMatrix:
    def test_message_faults_with_partitions(self, tmp_path, transport,
                                            seed):
        """Lossy links + a seed-scheduled symmetric partition episode
        mid-workload."""
        h = ChaosHarness(str(tmp_path), seed, SOAK_FAULTS,
                         num_members=R, num_groups=G, cfg=CFG,
                         transport=transport,
                         # Tracing on under the heaviest fault class
                         # (ISSUE 9): the tracer must stay a pure
                         # observer — same strict three-checker close,
                         # same zero-invariant-trip bar as untraced
                         # episodes, with telemetry watching.
                         trace=True)
        obs = LeaderObserver(h.alive)
        try:
            h.wait_leaders()
            obs.start()
            h.run_workload(30, prefix=b"a")
            victim = h.plan.derived_rng("victim").randrange(R) + 1
            h.plan.isolate_member(victim, h.members.keys())
            h.run_workload(20, prefix=b"b", per_put_timeout=15.0)
            h.plan.heal_all()
            h.run_workload(10, prefix=b"c")
            h.plan.quiesce()
            full_check(h, obs)
            assert h.fabric.stats().get("dropped", 0) > 0
            assert h.fabric.stats().get("partitioned", 0) > 0
        finally:
            obs.stop()
            h.stop()

    def test_crash_restart_cycles(self, tmp_path, transport, seed):
        """Two scripted kill/restart cycles through _replay, alternating
        the storage-failpoint site, under light message faults."""
        h = ChaosHarness(str(tmp_path), seed,
                         FaultSpec(drop=0.03, delay=0.05,
                                   delay_max_s=0.03),
                         num_members=R, num_groups=G, cfg=CFG,
                         transport=transport)
        obs = LeaderObserver(h.alive)
        try:
            h.wait_leaders()
            obs.start()
            h.run_workload(15, prefix=b"pre")
            rng = h.plan.derived_rng("crash")
            for cycle, site in enumerate(("before_save", "after_save")):
                victim = rng.randrange(R) + 1
                h.crash_on_failpoint(victim, site)
                acked = h.run_workload(10, prefix=b"mid%d" % cycle,
                                       per_put_timeout=15.0)
                assert acked >= 5
                h.restart(victim)
                h.wait_leaders()
            h.run_workload(8, prefix=b"post")
            h.plan.quiesce()
            # Strict parity on BOTH transports: the restarted-member
            # progress wedge (stale-high match pinning next <= match)
            # is fixed in the kernel (ISSUE 4; regression coverage in
            # tests/batched/test_progress_wedge.py).
            full_check(h, obs)
        finally:
            obs.stop()
            h.stop()

    def test_torn_tail_recovery(self, tmp_path, transport, seed):
        """Crash + torn last WAL record + restart through the repair
        path, per seed and transport — at STRICT parity since ISSUE 5:
        the durability watermark detects the severed acked bytes at
        _replay and the victim boots FENCED for the damaged groups
        (no campaigning, no vote grants), so the torn member can never
        win the election that used to force a survivor to overwrite a
        committed-and-applied entry. The fence auto-lifts as the
        probe/snapshot catch-up restores the durable log, and the full
        3-checker close (hash parity, committed-never-lost, election
        safety) runs with no allow_lag."""
        h = ChaosHarness(str(tmp_path), seed, FaultSpec(),
                         num_members=R, num_groups=G, cfg=CFG,
                         transport=transport)
        obs = LeaderObserver(h.alive)
        try:
            h.wait_leaders()
            obs.start()
            h.run_workload(20, prefix=b"pre")
            victim = h.plan.derived_rng("torn-victim").randrange(R) + 1
            h.crash(victim)
            assert h.torn_tail(victim, max_chop=48) > 0
            h.run_workload(10, prefix=b"mid", per_put_timeout=15.0)
            m = h.restart(victim)
            h.wait_leaders()
            h.run_workload(5, prefix=b"post")
            # Force traffic into every group: an idle group's leader
            # never probes the torn member (no probe without traffic),
            # and the fence lift rides the resulting append →
            # reject → backtrack → resend catch-up.
            h.touch_all_groups(per_put_timeout=15.0)
            # Every fence the tear armed must have lifted by episode
            # close — a lingering fence means catch-up never reached
            # the durable watermark.
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline and m._fenced.any():
                time.sleep(0.1)
            assert not m._fenced.any(), (
                f"fences never lifted: {m.health()}")
            h.plan.quiesce()
            full_check(h, obs)
        finally:
            obs.stop()
            h.stop()


# -- conf-change-under-fault cells (ISSUE 11) ----------------------------------
#
# Membership churn CONCURRENT with each fault class — the classic place
# real multi-raft systems break (ROADMAP item 5). Every cell drives the
# full migration cycle on a batch of groups (joint-implicit remove →
# add-as-learner → catch-up-gated promote, auto-leave exiting every
# joint config) while the fault plane fires, then closes at the same
# strict bar as the base matrix: all three checkers, zero on-device
# invariant trips (bit 8 voter_out_no_joint armed via CFG telemetry),
# PLUS check_config_safety (committed configs never lost, adjacent
# configs always share a quorum, joint always exited).

CHURN_GROUPS = range(16)  # churned subset; the other 48 groups keep
# serving the workload on the full electorate throughout


def _churn_cell(h: ChaosHarness, obs: LeaderObserver,
                fault_phase) -> None:
    """Shared cell body: workload → (faults + churn concurrent) →
    heal → restore full membership → strict close + config safety."""
    h.wait_leaders()
    obs.start()
    h.run_workload(15, prefix=b"pre", per_put_timeout=15.0)
    victim = 3  # churned member; fault victims are chosen per phase

    def dwell():
        fault_phase()
        h.run_workload(10, prefix=b"dwell", per_put_timeout=20.0)

    h.churn_member(victim, groups=CHURN_GROUPS,
                   timeout_each=180.0, dwell=dwell)
    h.plan.quiesce()
    h.run_workload(8, prefix=b"post", per_put_timeout=15.0)
    h.touch_all_groups(per_put_timeout=20.0)
    full_check(h, obs)
    check_config_safety(h.alive(), timeout=60.0)
    # The churn really happened: joint configs entered and exited on
    # the churned groups, and every group ended at full membership.
    snap = h.members[1].conf_snapshot()
    assert all(v == (1, 2, 3) for v in snap["voters"]), snap["voters"]
    assert any(e["joint"] for g in CHURN_GROUPS
               for e in h.members[1].conf_history(g))


@pytest.mark.parametrize("transport", TRANSPORTS)
class TestConfChurnMatrix:
    def test_churn_under_message_faults_and_partition(self, tmp_path,
                                                      transport):
        """Lossy/reordering links + a symmetric partition episode
        while the churned member is mid-cycle."""
        seed = SEEDS[0]
        h = ChaosHarness(str(tmp_path), seed, SOAK_FAULTS,
                         num_members=R, num_groups=G, cfg=CFG,
                         transport=transport)
        obs = LeaderObserver(h.alive)

        def fault_phase():
            # Partition a NON-churned member mid-dwell, heal after the
            # dwell workload has fought through it.
            h.plan.partition(1, 2)
            h.run_workload(6, prefix=b"cut", per_put_timeout=20.0)
            h.plan.heal_all()

        try:
            _churn_cell(h, obs, fault_phase)
            assert h.fabric.stats().get("dropped", 0) > 0
        finally:
            obs.stop()
            h.stop()

    def test_churn_under_crash_restart(self, tmp_path, transport):
        """Kill -9 a NON-churned member at a storage failpoint while
        the churned member is out of the config, restart it through
        _replay mid-cycle — the restarted member must reconstruct the
        conf state it crashed holding (RT_CONF_BATCH + committed-entry
        re-apply) before rejoining the churn quorum."""
        seed = SEEDS[1 % len(SEEDS)]
        h = ChaosHarness(str(tmp_path), seed,
                         FaultSpec(drop=0.03, delay=0.05,
                                   delay_max_s=0.03),
                         num_members=R, num_groups=G, cfg=CFG,
                         transport=transport)
        obs = LeaderObserver(h.alive)
        site = ("before_save" if transport == "inproc"
                else "after_save")

        def fault_phase():
            h.crash_on_failpoint(2, site, timeout=60.0)
            h.run_workload(6, prefix=b"down", per_put_timeout=25.0)
            h.restart(2)
            h.wait_leaders(timeout=120.0)

        try:
            _churn_cell(h, obs, fault_phase)
        finally:
            obs.stop()
            h.stop()

    def test_churn_under_torn_tail(self, tmp_path, transport):
        """Crash + torn WAL tail on the CHURNED member while it is out
        of the churned groups' configs: it boots FENCED for whatever
        the tear damaged, heals through the probe/snapshot path, and
        is then re-admitted (learner → gate → promote) into groups
        whose quorum kept serving — closing strict with every joint
        exited. (Tearing a NON-churned member here would be a designed
        unavailability, not a robustness gap: the churned groups run a
        two-voter config mid-cycle, and a two-voter group has zero
        fault tolerance — fencing one of its voters makes elections
        impossible by construction until catch-up, which itself needs
        a leader.)"""
        seed = SEEDS[2 % len(SEEDS)]
        h = ChaosHarness(str(tmp_path), seed, FaultSpec(),
                         num_members=R, num_groups=G, cfg=CFG,
                         transport=transport)
        obs = LeaderObserver(h.alive)

        def fault_phase():
            h.crash(3)
            h.torn_tail(3, max_chop=48)
            h.run_workload(6, prefix=b"torn", per_put_timeout=25.0)
            h.restart(3)
            h.wait_leaders(timeout=120.0)

        try:
            _churn_cell(h, obs, fault_phase)
        finally:
            obs.stop()
            h.stop()


# -- shm ring-fabric cells (ISSUE 16) ------------------------------------------
#
# The mmap'd SPSC ring fabric under the two heaviest fault classes ×
# both WAL modes (inline and the async group-commit pipeline), closed
# at the same strict bar as the base matrix: all three checkers +
# invariant_trips()==0. Reuses the module CFG — zero new round-step
# compiles (wal_pipeline is a member flag, not a config field). The
# cells prove the restart semantics the fabric documents: frames sent
# to a crashed peer fill its rings and count (ring_full_drop), a
# restarted reader resyncs its predecessor's backlog (stale_drop) —
# loss is counted, never silent.


@pytest.mark.parametrize("wal_pipeline", [False, True],
                         ids=["inline", "walpipe"])
class TestShmFabricMatrix:
    def test_shm_message_faults_with_partitions(self, tmp_path,
                                                wal_pipeline):
        """Lossy links + a symmetric isolation episode over the shm
        rings (FaultyFabric interposes through the same _send_block
        seam as the other two transports)."""
        seed = SEEDS[0]
        h = ChaosHarness(str(tmp_path), seed, SOAK_FAULTS,
                         num_members=R, num_groups=G, cfg=CFG,
                         transport="shm", wal_pipeline=wal_pipeline)
        obs = LeaderObserver(h.alive)
        try:
            h.wait_leaders()
            obs.start()
            h.run_workload(30, prefix=b"a")
            victim = h.plan.derived_rng("victim").randrange(R) + 1
            h.plan.isolate_member(victim, h.members.keys())
            h.run_workload(20, prefix=b"b", per_put_timeout=15.0)
            h.plan.heal_all()
            h.run_workload(10, prefix=b"c")
            h.plan.quiesce()
            full_check(h, obs)
            assert h.fabric.stats().get("dropped", 0) > 0
            assert h.fabric.stats().get("partitioned", 0) > 0
            # Frames really rode the rings (both priority classes).
            lanes = {f"{mid}/{k}": v
                     for mid, r in h.routers.items()
                     for k, v in r.lane_stats().items()}
            assert sum(v["frames"] for k, v in lanes.items()
                       if k.endswith(":live")) > 0
            assert sum(v["frames"] for k, v in lanes.items()
                       if k.endswith(":bulk")) > 0
        finally:
            obs.stop()
            h.stop()

    def test_shm_crash_restart_cycles(self, tmp_path, wal_pipeline):
        """Two kill/restart cycles through _replay over the rings: the
        reborn member's fabric reopens the SAME lane files, resumes
        write positions, and resyncs (counted, never delivered) any
        backlog addressed to its dead incarnation."""
        seed = SEEDS[0]
        h = ChaosHarness(str(tmp_path), seed,
                         FaultSpec(drop=0.03, delay=0.05,
                                   delay_max_s=0.03),
                         num_members=R, num_groups=G, cfg=CFG,
                         transport="shm", wal_pipeline=wal_pipeline)
        obs = LeaderObserver(h.alive)
        try:
            h.wait_leaders()
            obs.start()
            h.run_workload(15, prefix=b"pre")
            rng = h.plan.derived_rng("crash")
            for cycle, site in enumerate(("before_save", "after_save")):
                victim = rng.randrange(R) + 1
                h.crash_on_failpoint(victim, site)
                acked = h.run_workload(10, prefix=b"mid%d" % cycle,
                                       per_put_timeout=15.0)
                assert acked >= 5
                h.restart(victim)
                h.wait_leaders()
            h.run_workload(8, prefix=b"post")
            h.plan.quiesce()
            full_check(h, obs)
            # Any loss across the crash windows is COUNTED on the
            # shared registry (stale_drop / ring_full_drop / no_route),
            # and stats() answers on every live fabric.
            for r in h.routers.values():
                assert isinstance(r.stats(), dict)
        finally:
            obs.stop()
            h.stop()


# -- log-lifecycle soak cell (ISSUE 17) ----------------------------------------
#
# The long-horizon boundedness bar for the lifecycle plane at G=1024:
# under sustained traffic with message faults, crash/restart cycles and
# a torn tail, the WAL must PLATEAU (segments cut and released, bytes
# on disk bounded), snapshot files must stay within retention, the host
# payload arena must stay near ring occupancy (compaction floor
# advancing), and mean round time must stay flat between an early and a
# late measurement window — growth in any of these is exactly the slow
# leak a short tier-1 episode cannot see. Closed at the same strict bar
# as the rest of the matrix: all three checkers + invariant_trips()==0
# (which now includes the ring_over_window bit). Runs the async
# group-commit WAL pipeline so rotation rides the commit worker — the
# tier-1 cells in test_lifecycle.py cover the inline path.

LIFE_G = 1024
LIFE_CFG = BatchedConfig(
    num_groups=LIFE_G, num_replicas=R, window=16, max_ents_per_msg=4,
    max_props_per_round=4, election_timeout=10, heartbeat_timeout=1,
    pre_vote=True, check_quorum=True, auto_compact=True,
    telemetry=True, fleet_summary=True,
)
LIFE_SNAP_CADENCE = 6
# Rotation vs cover pacing: the sealed backlog settles near
# cadence x (bytes-per-bulk-pass / rotate) — one bulk pass writes
# ~80-100 KiB (1024 entries + watermark/hardstate records). Snapshot
# build throughput is fsync-bound (~G-scaled cap per lifecycle pass x
# two fsyncs per file), so the sustainable regime at G=1024 is rarer
# cuts: with 512 KiB segments a cut lands every ~5 passes and the
# overdue-priority build queue sweeps the whole fleet several times
# between cuts, keeping the backlog at 1-2 segments. (Cadence 3 +
# 64 KiB cuts every pass and demands ~340 builds/pass — past the
# fsync budget, the backlog grows without bound; that regime is the
# wal_pinned anomaly's job to report, not this cell's to pass.)
LIFE_ROTATE_BYTES = 512 * 1024


def _bulk_touch(h, prefix):
    """One proposal per group WITHOUT per-put ack polling — h.put's
    confirm poll × 1024 groups would dominate the horizon. The drain
    worker batches the proposals through the round; a group whose
    propose was refused (leadership moved, ring at the clamp) is simply
    caught by the next pass, since release gating is per-group cover,
    not per-pass. These writes are unacked so the committed-never-lost
    ledger does not constrain them; the acked ledger is fed by the
    bracketing run_workload calls."""
    from etcd_tpu.batched.hosting import GroupKV
    ok = 0
    for g in range(LIFE_G):
        payload = GroupKV.put_payload(
            b"%s-g%d" % (prefix, g), b"bulk")
        for m in h.alive():
            if m.propose(g, payload):
                ok += 1
                break
    return ok


def _round_clock(m):
    return (float(m.stats.get("round_s", 0.0)),
            int(m.stats.get("rounds", 0)))


def _window_ms(t0, t1):
    return 1000.0 * (t1[0] - t0[0]) / max(1, t1[1] - t0[1])


class TestLogLifecycleSoak:
    def test_bounded_growth_g1024_long_horizon(self, tmp_path):
        seed = SEEDS[0]
        h = ChaosHarness(
            str(tmp_path), seed,
            FaultSpec(drop=0.02, dup=0.02, delay=0.05,
                      delay_max_s=0.02),
            num_members=R, num_groups=LIFE_G, cfg=LIFE_CFG,
            wal_pipeline=True, snap_cadence=LIFE_SNAP_CADENCE,
            wal_rotate_bytes=LIFE_ROTATE_BYTES)
        obs = LeaderObserver(h.alive)
        try:
            h.wait_leaders(timeout=180.0)
            obs.start()
            # Member 1 is the timing/measurement anchor: it never
            # crashes, so its cumulative round clock survives the
            # whole horizon (restart resets a member's stats).
            anchor = h.members[1]
            h.run_workload(10, prefix=b"led0")

            # Warm phase: drive every group past the cadence a few
            # times so cuts, builds and releases all start.
            for i in range(3):
                _bulk_touch(h, b"warm%d" % i)
                time.sleep(0.4)
            # Early round-time window, after warmup absorbed compiles.
            t0 = _round_clock(anchor)
            for i in range(2):
                _bulk_touch(h, b"early%d" % i)
                time.sleep(0.4)
            t1 = _round_clock(anchor)
            early_ms = _window_ms(t0, t1)
            warm_bytes = max(
                m.health()["lifecycle"]["wal_bytes"]
                for m in h.alive())
            assert warm_bytes > 0

            # Chaos mid-phase: a torn-tail crash cycle and a clean
            # crash cycle, traffic flowing throughout.
            h.crash(2)
            h.torn_tail(2)
            for i in range(2):
                _bulk_touch(h, b"mid%d" % i)
                time.sleep(0.3)
            h.restart(2)
            h.wait_leaders(timeout=180.0)
            h.crash(3)
            for i in range(2):
                _bulk_touch(h, b"mid2%d" % i)
                time.sleep(0.3)
            m3 = h.restart(3)
            h.wait_leaders(timeout=180.0)
            # The restart replayed from file snapshots + rotated tail:
            # the newest fsync'd markers found their .snap files.
            assert int(m3._snap_file_idx.max()) > 0

            # Late phase: pump until every live member's segment count
            # sits at the sealed-backlog bound with the cut counter
            # past it — the plateau, not the slope.
            bound = anchor.wal_pinned_segments + 2

            def plateaued():
                for m in h.alive():
                    lc = m.health()["lifecycle"]
                    if not (lc["wal_segments"] <= bound
                            and lc["segments_released"] > 0
                            and lc["wal_cuts"] > lc["wal_segments"]):
                        return False
                return True

            ok = False
            deadline = time.monotonic() + 120.0
            i = 0
            while time.monotonic() < deadline:
                _bulk_touch(h, b"late%d" % i)
                i += 1
                time.sleep(0.5)
                if plateaued():
                    ok = True
                    break
            assert ok, {str(m.id): m.health()["lifecycle"]
                        for m in h.alive()}

            # Late round-time window: flat, not creeping — a lifecycle
            # pass that scanned released state or an arena leak would
            # show up here long before it OOMs.
            t2 = _round_clock(anchor)
            for i in range(2):
                _bulk_touch(h, b"flat%d" % i)
                time.sleep(0.4)
            t3 = _round_clock(anchor)
            late_ms = _window_ms(t2, t3)
            assert late_ms <= 3.0 * early_ms + 50.0, (
                early_ms, late_ms)

            # Boundedness at the end of the horizon, per live member:
            # bytes on disk plateaued (~3x more traffic than the warm
            # measurement, bounded growth), snapshot files inside
            # retention — keep+1 per group, since a crash landing
            # between save_snap and the retention prune leaves a
            # transient extra file that the group's NEXT build prunes
            # (bounded, self-correcting; a real retention leak grows
            # per build and blows through keep+1 immediately) — and
            # the host payload arena near ring occupancy.
            measured = {}
            for m in h.alive():
                hl = m.health()
                lc = hl["lifecycle"]
                assert lc["wal_segments"] <= bound, lc
                # Structural byte cap: every surviving segment is at
                # most rotate + checkpoint + one pass of overshoot
                # (~1 MiB of slack each). Immune to pacing variance,
                # still orders of magnitude under what a release leak
                # accumulates over the horizon.
                assert lc["wal_bytes"] <= (
                    (bound + 2) * (LIFE_ROTATE_BYTES + (1 << 20))), (
                    warm_bytes, lc)
                assert lc["snap_files"] <= (
                    LIFE_G * (m.snap_keep + 1)), lc
                arena_entries = sum(len(d) for d in m.rn.arena)
                assert arena_entries <= LIFE_G * LIFE_CFG.window * 2, (
                    arena_entries)
                assert hl["ring"]["window"] == LIFE_CFG.window
                assert hl["ring"]["occ_high_water"] >= 1
                measured[str(m.id)] = {
                    "wal_bytes": lc["wal_bytes"],
                    "wal_segments": lc["wal_segments"],
                    "wal_cuts": lc["wal_cuts"],
                    "segments_released": lc["segments_released"],
                    "snapshots_built": lc["snapshots_built"],
                    "snap_files": lc["snap_files"],
                    "arena_entries": arena_entries,
                    "ring_occ_high_water":
                        hl["ring"]["occ_high_water"],
                }

            # Evidence of the measured plateau (r17).
            os.makedirs("artifacts", exist_ok=True)
            with open("artifacts/lifecycle_soak_r17.json", "w") as f:
                json.dump({
                    "groups": LIFE_G, "members": R, "seed": seed,
                    "snap_cadence": LIFE_SNAP_CADENCE,
                    "wal_rotate_bytes": LIFE_ROTATE_BYTES,
                    "warm_wal_bytes_max": int(warm_bytes),
                    "round_ms_early": round(early_ms, 3),
                    "round_ms_late": round(late_ms, 3),
                    "members_end": measured,
                }, f, indent=1)

            h.run_workload(8, prefix=b"led1")
            # Per-group convergence pass before the strict close: a
            # group whose last entries landed while a member was down
            # has no probe without traffic (touch_all_groups'
            # docstring) — the restarted member's applied would sit
            # frozen a few entries behind forever, and the hash
            # checker polls state, it doesn't drive it. Unacked bulk
            # touches are enough: any fresh append triggers the
            # reject/backtrack resend for laggards, and quiesce()
            # drives the proposals to commit — touch_all_groups' 1024
            # acked puts would add ~15 min at G=1024 round latency.
            for i in range(3):
                _bulk_touch(h, b"conv%d" % i)
                time.sleep(0.3)
            h.plan.quiesce()
            full_check(h, obs)
        finally:
            obs.stop()
            h.stop()
