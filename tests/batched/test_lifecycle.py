"""Log-lifecycle plane (ISSUE 17): cadence snapshots, WAL segment
rotation + fleet-min-gated release, and ring back-pressure — tier-1.

The cells share the test_chaos BatchedConfig VALUES (lifecycle knobs
are host-side member args, not compile keys), so the jitted round
program is reused from the cache — zero new round-step compiles. The
G=1024 long-horizon soak lives in test_chaos_soak.py behind `-m slow`.
"""

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
from etcd_tpu.pkg import failpoint

pytestmark = pytest.mark.chaos

G, R = 8, 3
# Value-identical to test_chaos.CFG: _step_round_jit caches per config
# VALUES, so this module adds no compile.
CFG = BatchedConfig(
    num_groups=G, num_replicas=R, window=16, max_ents_per_msg=4,
    max_props_per_round=4, election_timeout=10, heartbeat_timeout=1,
    pre_vote=True, check_quorum=True, auto_compact=True,
    fleet_summary=True,
)

SEEDS = tuple(
    int(s) for s in
    os.environ.get("ETCD_TPU_CHAOS_SEED", "101,202").split(",")
)

# Aggressive lifecycle knobs so a short tier-1 episode rotates,
# snapshots and releases many times over: snapshot every 2 applied
# entries, cut the tail past 1 KiB.
SNAP_CADENCE = 2
ROTATE_BYTES = 1024


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    failpoint.disable_all()


def make_harness(tmp_path, seed, spec=None, **kw):
    return ChaosHarness(
        str(tmp_path), seed, spec or FaultSpec(), num_members=R,
        num_groups=G, cfg=CFG, snap_cadence=SNAP_CADENCE,
        wal_rotate_bytes=ROTATE_BYTES, **kw,
    )


def _wait(pred, timeout=90.0, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


def _total(h, stat):
    return sum(int(m.stats.get(stat, 0)) for m in h.members.values())


class TestRotationAndCadence:
    def test_rotate_snapshot_release_restart_replay(self, tmp_path):
        """The full lifecycle loop under traffic: segments cut past
        the byte threshold, cadence file snapshots cover them, sealed
        segments release (bytes on disk plateau instead of growing
        monotonically), and a crash/restart replays from snapshot +
        rotated tail with the strict three-checker close."""
        h = make_harness(tmp_path, SEEDS[0])
        obs = LeaderObserver(h.alive)
        try:
            h.wait_leaders()
            obs.start()
            h.run_workload(24, prefix=b"pre")
            # Every group past the cadence so no group pins release.
            for i in range(3):
                h.touch_all_groups(prefix=b"cad%d" % i)
            _wait(lambda: _total(h, "wal_cuts") > 0,
                  what="a WAL segment cut")
            _wait(lambda: _total(h, "snapshots_built") > 0,
                  what="a cadence snapshot build")
            _wait(lambda: _total(h, "wal_segments_released") > 0,
                  what="a sealed-segment release")
            m2 = h.members[2]
            built_pre = int(m2.stats.get("snapshots_built", 0))
            hl = m2.health()
            assert hl["lifecycle"]["enabled"]
            assert hl["lifecycle"]["wal_segments"] >= 1
            assert hl["lifecycle"]["snap_files"] >= 1
            # Retention: no more than keep files per group dir — once
            # the builder's prune has run (it writes the new file and
            # fsyncs its marker BEFORE pruning, so a listing taken in
            # between legitimately sees keep + 1).
            snap_root = os.path.join(m2.dir, "snap")

            def over_keep():
                return [
                    (sub, files) for sub in os.listdir(snap_root)
                    for files in [[
                        n for n in os.listdir(os.path.join(snap_root, sub))
                        if n.endswith(".snap")]]
                    if len(files) > m2.snap_keep]

            _wait(lambda: not over_keep(), timeout=10.0,
                  what="retention to prune every dir to snap_keep")

            h.crash(2)
            h.run_workload(6, prefix=b"mid")
            m2 = h.restart(2)  # replay: snapshot files + rotated tail
            if built_pre:
                # Markers are fsync'd before their fold, so a clean
                # crash always leaves the file snapshots findable.
                assert int(m2._snap_file_idx.max()) > 0
            h.wait_leaders()
            h.touch_all_groups(prefix=b"post")
            run_invariant_checks(h, obs, expect_members=R)
        finally:
            obs.stop()
            h.stop()

    def test_wal_segments_plateau_not_monotone(self, tmp_path):
        """Measured boundedness: under sustained traffic the on-disk
        segment count must plateau at the sealed-backlog bound (tail +
        unreleasable backlog), while the cut counter keeps climbing —
        the plateau, not the slope. A release leak would pin every cut
        segment on disk and blow through the bound. (The soak asserts
        the same shape at G=1024 over a long horizon.)"""
        h = make_harness(tmp_path, SEEDS[-1])
        try:
            h.wait_leaders()
            bound = (h.members[1].wal_pinned_segments + 2)

            def plateaued():
                for m in h.alive():
                    hl = m.health()["lifecycle"]
                    if not (hl["wal_segments"] <= bound
                            and hl["segments_released"] > 0
                            # Cuts outnumber surviving segments:
                            # segments really are being reclaimed,
                            # not just never created.
                            and hl["wal_cuts"] > hl["wal_segments"]):
                        return False
                return True

            ok = False
            for i in range(24):
                h.touch_all_groups(prefix=b"pump%d" % i)
                if plateaued():
                    ok = True
                    break
            assert ok, {
                str(m.id): m.health()["lifecycle"]
                for m in h.alive()}
        finally:
            h.stop()


class TestRingBackpressure:
    def test_ring_full_refusal_is_typed_and_counted(self, tmp_path):
        """propose() refuses with the counted ring_full at exactly the
        occupancy where the device headroom clamp would drop the
        proposal — mirror-driven, so the cell pins the mirrors by
        stopping the harness first (roles freeze at their last fold)."""
        h = make_harness(tmp_path, SEEDS[0])
        try:
            h.wait_leaders()
            h.touch_all_groups(prefix=b"seed")
            h.stop()  # freeze the role/occupancy mirrors
            m = next(mm for mm in h.members.values()
                     if any(mm.rn.is_leader(g) for g in range(G)))
            g = next(gg for gg in range(G) if m.rn.is_leader(gg))
            occ_floor = CFG.window - CFG.max_props_per_round
            # Headroom available: accepted (staged only — stopped).
            m.rn.m_snap[g] = m.rn.m_last[g]
            assert m.propose(g, b"x")
            assert m.stats.get("ring_full_refusals", 0) == 0
            # Squeeze the ring to the clamp point: typed refusal.
            m.rn.m_snap[g] = int(m.rn.m_last[g]) - occ_floor
            assert not m.propose(g, b"x")
            assert m.stats["ring_full_refusals"] == 1
            hl = m.health()
            assert hl["ring"]["full_refusals"] == 1
            assert hl["ring"]["window"] == CFG.window
            assert hl["ring"]["occ_high_water"] >= occ_floor
        finally:
            h.stop()


class TestFenceReleaseInteraction:
    def test_fence_demand_never_dangles_into_released_segment(
            self, tmp_path):
        """Regression for the fence/release interaction: a torn tail
        fences groups whose acked bytes it severed, the fenced member
        must NOT build snapshots for them (cover frozen), survivors
        rotate + release around it, and after heal the three checkers
        close with invariant_trips()==0 — if retention ever reclaimed
        a segment a fence demand still pointed into, the
        committed-never-lost checker would catch the hole."""
        h = make_harness(tmp_path, SEEDS[0])
        obs = LeaderObserver(h.alive)
        try:
            h.wait_leaders()
            obs.start()
            for i in range(3):
                h.touch_all_groups(prefix=b"pre%d" % i)
            h.crash(3)
            assert h.torn_tail(3) > 0
            # Survivors keep rotating/releasing while 3 is down.
            for i in range(3):
                h.touch_all_groups(prefix=b"mid%d" % i)
            _wait(lambda: _total(h, "wal_segments_released") > 0,
                  what="release while the torn member is down")
            m3 = h.restart(3)
            fenced_boot = int(m3._fenced.sum())
            if fenced_boot:
                # The frozen-cover contract while the fence stands:
                # cadence must skip fenced groups outright.
                fenced = m3._fenced.copy()
                assert not (
                    m3._snap_file_idx[fenced] >
                    m3._snap_cover[fenced]).any()
            h.wait_leaders()
            h.touch_all_groups(prefix=b"heal")
            _wait(lambda: int(m3._fenced.sum()) == 0,
                  what="fence heal on the torn member")
            run_invariant_checks(h, obs, expect_members=R)
        finally:
            obs.stop()
            h.stop()
