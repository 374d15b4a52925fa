"""Storage fault plane (ISSUE 15): injected IO errors at the Walog
seam, the IO-error contract, and the gray-failure eviction loop.

The fault classes are the two papers' lists made executable:

* **fsync failure** (Rebello et al., ATC'19) — the first failed fsync
  must FAIL-STOP the member: nothing gated on the failed window (acks,
  sends, applies) is ever released, and nothing retries an fsync whose
  dirty pages the kernel may already have dropped. Regression-tested
  for BOTH WAL modes (inline drain + async group-commit pipeline).
* **ENOSPC** — a write refused at the seam (provably nothing written)
  is back-pressure, not death: proposals refuse, health reports
  ``disk_full``, and once space returns the member resumes with zero
  acked writes lost.
* **bit-rot** — at-rest CRC corruption mid-log (not the tail) is
  salvaged at boot (walog.salvage amputates at the first bad record)
  and the damaged groups boot FENCED via the ISSUE 5 durable
  watermark, healing by snapshot/probe rejoin.
* **limp** (Huang et al., HotOS'17 gray failure) — a member whose
  fsyncs are merely SLOW raises the counted ``member_limping``
  anomaly, and the rebalancer drains leadership off it (as a follower
  it leaves every commit's critical path).

Quick deterministic cells run in tier-1 (the satellite-6 pair: one
fsync-error fail-stop, one bit-rot fence — sharing test_chaos.py's
config so the round program compiles once per process); the full
matrix (both transports x inline/pipeline WAL x all four fault kinds)
is slow-marked. Every episode closes with the strict 3-checker suite
and ``invariant_trips() == 0``.
"""

import time

import pytest

from etcd_tpu.batched.faults import (
    ChaosHarness,
    FaultSpec,
    LeaderObserver,
    run_invariant_checks,
)
from etcd_tpu.batched.rebalance import (
    InProcActuator,
    RebalanceConfig,
    Rebalancer,
)
from etcd_tpu.batched.state import BatchedConfig
from etcd_tpu.pkg import failpoint

pytestmark = pytest.mark.chaos

G, R = 8, 3
SEED = 404
# Value-identical to tests/batched/test_chaos.py CFG: _step_round_jit
# caches the compiled round per config VALUE, so these cells reuse the
# chaos subset's program — zero new tier-1 round-step compiles
# (ROUND_STEP_SHAPE_BUDGET stays honest at 43).
CFG = BatchedConfig(
    num_groups=G, num_replicas=R, window=16, max_ents_per_msg=4,
    max_props_per_round=4, election_timeout=10, heartbeat_timeout=1,
    pre_vote=True, check_quorum=True, auto_compact=True,
    fleet_summary=True,  # keep value-identical to test_chaos.CFG
)


@pytest.fixture(autouse=True)
def _clean_failpoints():
    yield
    failpoint.disable_all()


def make_harness(tmp_path, transport="inproc", wal_pipeline=False,
                 seed=SEED):
    return ChaosHarness(
        str(tmp_path), seed, FaultSpec(), num_members=R, num_groups=G,
        cfg=CFG, transport=transport, wal_pipeline=wal_pipeline,
        # A dwell window makes pipeline-mode group-commit coalescing
        # deterministic enough for the fault cells; None = inline.
        wal_group_max_delay=0.01 if wal_pipeline else None,
    )


def _led_group(h, mid):
    """Some group the member currently leads. If it leads none, the
    leader of group 0 hands that one over: a campaign cannot displace a
    healthy pre-vote/check-quorum leader, a transfer can."""
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        for g in range(G):
            if h.members[mid].is_leader(g):
                return g
        for m in h.members.values():
            m.transfer_leader(0, mid)  # False unless m leads group 0
        time.sleep(0.1)
    raise TimeoutError(f"member {mid} never led a group")


def run_fsync_failstop_episode(h):
    """Shared body of the fsync-error cells: arm a sticky fsync error
    on a LEADER, prove the write riding the failed window never acks,
    prove the member fail-stopped with nothing released (durability
    envelope), then heal, restart, and close strict."""
    obs = LeaderObserver(h.alive)
    try:
        h.wait_leaders()
        obs.start()
        assert h.run_workload(6, prefix=b"pre") >= 5
        victim = 2
        g = _led_group(h, victim)
        m = h.members[victim]
        h.disk.arm_fsync_error(victim, sticky=True)
        # The write riding the failed window: proposed at the victim
        # leader AFTER arming — its MsgApp/ack can only leave behind a
        # successful covering fsync, so it must NEVER apply anywhere
        # while the victim lives, and the victim must die fail-stop.
        m.propose(g, b"P" + b"doomed\x00never")
        cause = h.wait_fail_stop(victim, timeout=30.0)
        assert cause.startswith("fsync:"), cause
        assert m.get(g, b"doomed") is None, (
            "apply released from the failed fsync window")
        hl = m.health()
        assert hl["fail_stop"] and hl["crashed"]
        # Release-barrier audit: applied <= durable on every group.
        h.failstop_envelope(victim)
        assert h.disk.stats().get("fsync_error", 0) >= 1
        # Survivor quorum keeps serving while the victim is down.
        assert h.run_workload(4, prefix=b"mid") >= 3
        # Heal + restart through _replay; strict 3-checker close.
        h.disk.quiesce()
        h.restart(victim)
        h.wait_leaders()
        h.touch_all_groups()
        run_invariant_checks(h, obs, expect_members=R)
    finally:
        obs.stop()
        h.stop()


def run_enospc_episode(h):
    """Shared body of the ENOSPC cells: sticky disk-full on a member's
    write path => disk_full back-pressure (health-visible, proposals
    refuse, member stays ALIVE), heal => resumes, episode closes
    strict with zero acked writes lost."""
    obs = LeaderObserver(h.alive)
    try:
        h.wait_leaders()
        obs.start()
        assert h.run_workload(6, prefix=b"pre") >= 5
        victim = 1
        m = h.members[victim]
        h.disk.arm_enospc(victim)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if m.health()["disk_full"]:
                break
            time.sleep(0.05)
        assert m.health()["disk_full"], "never entered disk_full"
        # Back-pressured, not dead: proposals refuse at the victim,
        # the survivor quorum keeps acking (leadership moves off the
        # stalled member organically as its heartbeats stall).
        assert not m.propose(0, b"P" + b"x\x00y")
        assert not m._stopped.is_set()
        assert h.run_workload(6, prefix=b"mid",
                              per_put_timeout=15.0) >= 4
        h.disk.heal_enospc(victim)
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if not m.health()["disk_full"]:
                break
            time.sleep(0.05)
        assert not m.health()["disk_full"], "never recovered"
        assert not m._stopped.is_set(), "ENOSPC must not crash-loop"
        assert h.run_workload(4, prefix=b"post") >= 3
        assert h.disk.stats().get("enospc", 0) >= 1
        assert m.health()["disk_full_waits"] >= 1
        run_invariant_checks(h, obs, expect_members=R)
    finally:
        obs.stop()
        h.stop()


def run_bitrot_episode(h):
    """Shared body of the bit-rot cells: crash a member, flip a seeded
    bit in a MID-LOG fsync'd record, restart => salvage + fenced boot,
    heal by the probe/snapshot catch-up, close strict."""
    obs = LeaderObserver(h.alive)
    try:
        h.wait_leaders()
        obs.start()
        assert h.run_workload(8, prefix=b"pre") >= 6
        victim = 3
        h.crash(victim)
        off, byte = h.bit_rot(victim)
        assert off >= 0, "WAL too short to hold a mid-log record"
        h.run_workload(4, prefix=b"mid")
        m = h.restart(victim)  # must boot, not refuse
        hl = m.health()
        assert hl["salvage"] is not None, "salvage never ran"
        assert hl["salvage"]["bytes_dropped"] > 0
        assert hl["wal_tail"] == "corrupt"  # the boot-time finding
        h.wait_leaders()
        # A write per group forces the append/reject/backtrack heal
        # for every amputated log (and lifts any fences armed).
        h.touch_all_groups()
        run_invariant_checks(h, obs, expect_members=R)
        assert not m.health()["fenced_groups"], "fences never lifted"
    finally:
        obs.stop()
        h.stop()


def run_limp_episode(h):
    """Shared body of the limp cells — the gray-failure loop end to
    end: seeded slow-disk on one member -> member_limping anomaly from
    its fleet hub -> rebalancer evicts every leadership off it ->
    healthy members hold all leaderships; heal, close strict."""
    obs = LeaderObserver(h.alive)
    try:
        h.wait_leaders()
        obs.start()
        victim = 2
        m = h.members[victim]
        # Sensitize the detector for test cadence (defaults: 25ms/8).
        for mm in h.members.values():
            mm.fleet.limp_ms = 10.0
            mm.fleet.limp_ops = 4
        h.disk.set_limp(victim, 0.03)  # 30ms fsyncs: alive, slow
        deadline = time.monotonic() + 60.0
        wave = 0
        while time.monotonic() < deadline:
            h.run_workload(2, prefix=b"limp%d" % wave)
            wave += 1
            if m.fleet.anomalies().get("member_limping", 0) >= 1:
                break
        assert m.fleet.anomalies().get("member_limping", 0) >= 1, (
            "limp detector never fired")
        assert m.fleet.limp_state()["limping"]
        # Eviction: the rebalancer consumes the anomaly and drains
        # every leadership off the limping member.
        reb = Rebalancer(
            InProcActuator(h.members),
            RebalanceConfig(skew_ratio=1.5, cooldown_s=0.5,
                            max_moves_per_pass=G, transfer_wait_s=5.0,
                            min_groups=G))
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            rep = reb.run_once()
            led = sum(1 for g in range(G) if m.is_leader(g))
            if led == 0 and rep["converged"]:
                break
            time.sleep(0.5)
        led = sum(1 for g in range(G) if m.is_leader(g))
        assert led == 0, f"limping member still leads {led} groups"
        assert any(mv["reason"] == "limp_evict"
                   for mv in rep["moves"]) or rep["converged"]
        h.disk.heal_limp(victim)
        assert h.run_workload(4, prefix=b"post") >= 3
        run_invariant_checks(h, obs, expect_members=R)
    finally:
        obs.stop()
        h.stop()


# -- walog salvage edge cases (no cluster, no jax) ----------------------------


class TestSalvageSeedRecords:
    def _make_wal(self, tmp_path, segments=3, recs_per_seg=4):
        from etcd_tpu.native import walog

        wd = str(tmp_path / "wal")
        w = walog.Walog(wd, segment_bytes=1 << 16, create=True)
        for s in range(segments):
            if s:
                w.cut(s)
            for i in range(recs_per_seg):
                w.append(1, b"seg%d-rec%d-" % (s, i) * 4)
        w.flush(sync=True)
        w.close()
        return wd

    @staticmethod
    def _flip_seed(wd, seg_index, byte_off=8):
        """Damage a segment's CRC-reset seed record. byte_off 8 hits
        the stored chain crc (detectable as a cross-boundary chain
        mismatch — only for segments AFTER the first, whose expected
        crc is known); byte_off 4 hits the record TYPE, detectable in
        any segment (a first record that is not kTypeCrcReset)."""
        import os

        segs = sorted(f for f in os.listdir(wd)
                      if f.endswith(".wal"))
        path = os.path.join(wd, segs[seg_index])
        with open(path, "r+b") as f:
            f.seek(byte_off)
            b = f.read(1)
            f.seek(byte_off)
            f.write(bytes([b[0] ^ 0x40]))
        return segs

    def test_first_segment_seed_corruption_refuses_salvage(
            self, tmp_path):
        """Seed of segment 0 damaged: NO valid prefix exists — salvage
        must refuse (None) rather than truncate to an unbootable husk
        after destroying the intact later segments."""
        from etcd_tpu.native import walog

        wd = self._make_wal(tmp_path)
        self._flip_seed(wd, 0, byte_off=4)  # type byte: seed no more
        assert walog.salvage(wd) is None
        with pytest.raises(walog.WalogError):
            walog.read_all(wd)

    def test_later_segment_seed_corruption_drops_from_there(
            self, tmp_path):
        """Seed of a LATER segment damaged: the chain through the
        previous segments is whole — salvage drops the damaged segment
        (and everything after) entirely, and the survivor prefix both
        replays and reopens for appends."""
        import os

        from etcd_tpu.native import walog

        wd = self._make_wal(tmp_path, segments=3)
        segs = self._flip_seed(wd, 1)
        info = walog.salvage(wd)
        assert info is not None
        assert info["removed_segments"] == segs[1:]
        assert sorted(f for f in os.listdir(wd)
                      if f.endswith(".wal")) == segs[:1]
        recs, ts = walog.read_all_classified(wd)
        assert len(recs) == 4 and ts == walog.TAIL_CLEAN
        w = walog.Walog(wd)  # must reopen positioned at the new tail
        w.append(1, b"post-salvage")
        w.flush(sync=True)
        w.close()
        assert len(walog.read_all(wd)) == 5


# -- Snapshotter seam (no cluster, no jax): the DiskFaultPlan hook on
#    storage/snap.py file ops -------------------------------------------------


class TestSnapshotterSeam:
    def _snap(self, idx=5, term=2):
        from etcd_tpu.raft.types import (
            ConfState,
            Snapshot,
            SnapshotMetadata,
        )

        return Snapshot(
            data=b"payload",
            metadata=SnapshotMetadata(
                conf_state=ConfState(voters=[1, 2, 3]),
                index=idx, term=term))

    def test_enospc_aborts_save_loss_free(self, tmp_path):
        """A seam-raised ENOSPC fires BEFORE the tmp write starts:
        save_snap aborts with no tmp leftover and the previous
        snapshot file untouched (load() still serves it)."""
        from etcd_tpu.batched.faults import DiskFaultPlan
        from etcd_tpu.native.walog import DiskFullError
        from etcd_tpu.storage.snap import Snapshotter

        plan = DiskFaultPlan(seed=SEED)
        s = Snapshotter(str(tmp_path), fault_hook=plan.hook_for(1))
        s.save_snap(self._snap(idx=5))
        plan.arm_enospc(1)
        with pytest.raises(DiskFullError):
            s.save_snap(self._snap(idx=9))
        assert not [f for f in tmp_path.iterdir()
                    if f.name.endswith(".tmp")]
        assert s.load().metadata.index == 5
        plan.heal_enospc(1)
        s.save_snap(self._snap(idx=9))
        assert s.load().metadata.index == 9
        assert plan.stats().get("enospc", 0) == 1

    def test_fsync_error_fires_on_snap_fsync(self, tmp_path):
        from etcd_tpu.batched.faults import DiskFaultPlan
        from etcd_tpu.native.walog import InjectedIOError
        from etcd_tpu.storage.snap import Snapshotter

        plan = DiskFaultPlan(seed=SEED)
        s = Snapshotter(str(tmp_path), fault_hook=plan.hook_for(1))
        plan.arm_fsync_error(1)  # one-shot
        with pytest.raises(InjectedIOError):
            s.save_snap(self._snap())
        s.save_snap(self._snap())  # one-shot consumed: next succeeds
        assert s.load().metadata.index == 5

    def test_limp_delays_snapshot_ops(self, tmp_path):
        from etcd_tpu.batched.faults import DiskFaultPlan
        from etcd_tpu.storage.snap import Snapshotter

        plan = DiskFaultPlan(seed=SEED)
        s = Snapshotter(str(tmp_path), fault_hook=plan.hook_for(1))
        plan.set_limp(1, 0.05, ops=("snap_fsync",))
        t0 = time.perf_counter()
        s.save_snap(self._snap())
        assert time.perf_counter() - t0 >= 0.05
        assert plan.stats().get("delay", 0) == 1


# -- quick tier-1 cells (satellite 6: one fsync-error, one bit-rot) -----------


class TestFsyncFailStop:
    def test_fsync_error_failstop_inline(self, tmp_path):
        run_fsync_failstop_episode(make_harness(tmp_path))


class TestBitRotFence:
    def test_bit_rot_mid_log_salvage_and_fence(self, tmp_path):
        run_bitrot_episode(make_harness(tmp_path))


# -- full matrix: both transports x inline/pipeline WAL x fault kinds ---------

_EPISODES = {
    "fsync": run_fsync_failstop_episode,
    "enospc": run_enospc_episode,
    "bitrot": run_bitrot_episode,
    "limp": run_limp_episode,
}


@pytest.mark.slow
@pytest.mark.parametrize("transport", ["inproc", "tcp"])
@pytest.mark.parametrize("wal_pipeline", [False, True],
                         ids=["inline", "pipeline"])
@pytest.mark.parametrize("fault", sorted(_EPISODES))
def test_disk_fault_matrix(tmp_path, transport, wal_pipeline, fault):
    # The tier-1 quick cells already cover (inproc, inline) x
    # {fsync, bitrot}; the matrix re-runs them anyway so one -m slow
    # sweep proves every combination at the same strict bar — the
    # (inproc, pipeline, fsync) cell is the acceptance-criteria
    # "fail-stop provable in BOTH WAL modes" regression.
    _EPISODES[fault](make_harness(
        tmp_path, transport=transport, wal_pipeline=wal_pipeline))
