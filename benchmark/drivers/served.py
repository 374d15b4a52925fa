"""Driver ``served``: the served path, from a client's side.

Builds ``hosting.MultiRaftCluster`` — ``num_replicas`` ``MultiRaftMember``
in this process over ``InProcRouter``, the member's default
``BatchedConfig``, pipelined drain, WAL with fsync on — and gives the
generator the four things a client can do: find a group's leader, offer
a put to it, see whether it has been applied there, and read
linearizably. After the window it reads everything back and hands plain
data to ``compare.served_checks``.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..compare import Check, served_checks
from ..harness import say

MEMBER_STATS = ("rounds", "round_s", "wal_s", "apply_s", "send_s",
                "wal_fsyncs", "fsync_s")


class Retry(Exception):
    """The member asked is not (or no longer) the group's leader."""


class Driver:
    Retry = Retry
    RESTART_WAIT_S = 120.0
    LREAD_WAIT_S = 30.0

    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        self.sizes = config["sizes"]
        self.traffic = traffic
        self.seed = seed
        self.data_dir = os.path.join(workdir, "data")
        self.cluster = None
        self.members: list = []
        self.groups = int(self.sizes["num_groups"])
        self._phase: List[List[float]] = []
        self._win0: Optional[dict] = None
        self._win1: Optional[dict] = None

    # -- set-up -----------------------------------------------------------------

    def _open(self):
        from etcd_tpu.batched.hosting import MultiRaftCluster

        return MultiRaftCluster(
            self.data_dir, num_members=int(self.sizes["num_replicas"]),
            num_groups=self.groups)

    def setup(self, load, gen) -> None:
        # Fsync is part of the guarantee: refuse a run whose environment
        # would soften the WAL.
        for var in ("ETCD_TPU_WAL_PIPELINE", "ETCD_TPU_FSYNC_DELAY_MS"):
            if os.environ.get(var):
                raise RuntimeError(f"{var} is set; the cell runs the "
                                   "member's defaults")
        from etcd_tpu.raft.logger import DefaultLogger, set_logger

        set_logger(DefaultLogger(level=2))
        os.makedirs(self.data_dir, exist_ok=True)
        t0 = time.perf_counter()
        self.cluster = self._open()
        self.members = list(self.cluster.members.values())
        cfg = self.members[0].cfg
        for key in ("num_groups", "num_replicas", "window",
                    "max_ents_per_msg", "max_props_per_round",
                    "election_timeout", "heartbeat_timeout", "pre_vote",
                    "check_quorum", "auto_compact"):
            if getattr(cfg, key) != self.sizes[key]:
                raise RuntimeError(
                    f"the member's default {key}={getattr(cfg, key)!r} is "
                    f"not the configuration's {self.sizes[key]!r}")
        if self.members[0].tick_interval != self.sizes["tick_interval_s"]:
            raise RuntimeError("tick interval differs from the config's")
        self._place_leaders()
        self.elect_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        # The preload is also the warm-up: every group appends, fsyncs
        # and applies once before the ramp, so that whatever is built
        # lazily (programs, WAL segments) is built here, and a
        # leadership it shook loose is put back.
        gen.preload(self, load, self.traffic)
        self._hold_leaders()
        self.preload_s = time.perf_counter() - t0
        say("served", elect_s=self.elect_s, preload_s=self.preload_s,
            leaders_per_member=np.bincount(
                self.leaders(), minlength=len(self.members) + 1).tolist(),
            data_dir=self.data_dir)

    def _place_leaders(self, timeout: float = 600.0) -> None:
        """Each group's leader is drawn from the seed, a third of the
        groups on each member. Left to the timers, the elections put
        all 1024 on one member in one run and 819/205/0 in the next
        (chip runs, PR 23), and ``ops_per_s`` follows by a fifth. The
        drawn member campaigns before any timer fires; a group that
        elected another all the same is handed over; a leader with a
        hand-over in flight drops proposals, so the placement has to
        read right twice, half a second apart, before load starts."""
        n = len(self.members)
        rng = np.random.default_rng([self.seed, 0x1EAD])
        self.target = rng.permutation(self.groups) % n + 1
        for i, m in enumerate(self.members):
            m.campaign(np.nonzero(self.target == i + 1)[0])
        self._hold_leaders(timeout)

    def _hold_leaders(self, timeout: float = 600.0) -> None:
        self.cluster.wait_leaders(timeout=timeout)
        deadline = time.monotonic() + timeout
        settled = 0
        while settled < 2:  # twice in a row: no hand-over still in flight
            lead = self.leaders()
            wrong = np.nonzero(lead != self.target)[0]
            settled = 0 if len(wrong) else settled + 1
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"{len(wrong)} groups are not led by the member the "
                    "seed drew")
            for g in wrong.tolist():
                if lead[g]:
                    self.members[int(lead[g]) - 1].transfer_leader(
                        g, int(self.target[g]))
            time.sleep(0.5)

    # -- what a client can do -------------------------------------------------------

    def leaders(self) -> np.ndarray:
        """Per group the index+1 of the member that leads it, 0 if none
        says so (each member's own view of its rows)."""
        from etcd_tpu.batched.state import LEADER

        out = np.zeros(self.groups, np.int64)
        for i, m in enumerate(self.members):
            _term, role, _lead = m.rn.m_view
            out[role == LEADER] = i + 1
        return out

    def propose(self, member: int, group: int, key: bytes,
                value: bytes) -> bool:
        from etcd_tpu.batched.hosting import GroupKV

        return self.members[member].propose(
            group, GroupKV.put_payload(key, value))

    def applied_marks(self, member: int) -> np.ndarray:
        return self.members[member].applied_index.copy()

    def applied_value(self, member: int, group: int,
                      key: bytes) -> Optional[bytes]:
        return self.members[member].get(group, key)

    def lread(self, member: int, group: int, key: bytes,
              timeout: float) -> Optional[bytes]:
        from etcd_tpu.batched.hosting import NotLeaderError

        try:
            return self.members[member].linearizable_get(
                group, key, timeout=timeout)
        except NotLeaderError as e:
            raise Retry(str(e)) from None

    # -- counters -----------------------------------------------------------------

    def counters(self) -> dict:
        """The program's own host-clock counters, read as they stand."""
        return {
            "members": [{k: m.stats.get(k, 0) for k in MEMBER_STATS}
                        for m in self.members],
            "wal_sync": [list(m.wal.sync_stats()) for m in self.members],
            "router": self.cluster.router.stats(),
        }

    def window_opens(self) -> None:
        self._win0 = self.counters()

    def window_closes(self) -> None:
        self._win1 = self.counters()

    def sample(self) -> None:
        """``rn.phase_last`` of each member, at the generator's pace."""
        for m in self.members:
            p = m.rn.phase_last
            self._phase.append(
                [p["stage"], p["step"], p["extract"], p["collect"]])

    def window_counters(self) -> dict:
        return {"before": self._win0, "after": self._win1,
                "phase_samples": self._phase}

    # -- the comparisons, outside the window ------------------------------------------

    @staticmethod
    def _kvs(members) -> List[List[Dict[bytes, bytes]]]:
        return [[dict(kv.data) for kv in m.kvs] for m in members]

    def _converged(self, timeout: float = 120.0) -> None:
        """Followers apply behind the leader: wait until the members'
        apply marks agree (or the time is up; the comparison then says
        what is missing)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            marks = np.stack([m.applied_index for m in self.members])
            if (marks == marks[0]).all():
                return
            time.sleep(0.05)

    def _lread_following_hints(self, key: Tuple[int, bytes]):
        """(group, key, value, served): one linearizable read at the
        group's leader, redirected like a client following hints, given
        up ``LREAD_WAIT_S`` after it began."""
        g, k = key
        deadline = time.monotonic() + self.LREAD_WAIT_S
        while time.monotonic() < deadline:
            try:
                m = int(self.leaders()[g]) - 1
                if m >= 0:
                    return g, k, self.lread(m, g, k, 5.0), True
            except (Retry, TimeoutError):
                pass
            time.sleep(0.05)
        say("lread_unserved", group=g, views=[
            {"member": m.id, "term": int(m.rn.m_view[0][g]),
             "role": int(m.rn.m_view[1][g]), "lead": int(m.rn.m_view[2][g]),
             "applied": int(m.applied_index[g]),
             "read_opened": m._read_opened.get(g),
             "read_result": m._read_results.get(g)}
            for m in self.members])
        return g, k, None, False

    def check(self, load, raw) -> List[Check]:
        acked: Dict[Tuple[int, bytes], bytes] = raw["acked"]
        proposed = raw["proposed"]
        rng = np.random.default_rng([self.seed, 0xC4EC])
        keys = list(acked)
        n = min(int(self.traffic.get("check_sample", 64)), len(keys))
        pick = rng.choice(len(keys), size=n, replace=False) if n else []
        sample = [keys[i] for i in pick]

        lreads, unserved = None, 0
        if self.traffic.get("check_lread"):
            lreads = list(raw.get("lreads", ()))
            with ThreadPoolExecutor(max_workers=16) as pool:
                asked = list(pool.map(self._lread_following_hints, sample))
            lreads.extend((g, k, got) for g, k, got, ok in asked if ok)
            unserved = sum(1 for a in asked if not a[3])

        self._converged()
        member_kvs = self._kvs(self.members)
        wal = [m.wal.sync_stats()[0] for m in self.members]
        win = [b[0] - a[0] for a, b in zip(self._win0["wal_sync"],
                                           self._win1["wal_sync"])]
        restart_kvs = None
        if self.traffic.get("check_restart"):
            restart_kvs = self._restart(acked, sample)
        if not raw.get("clients_putting"):
            win = None
        return served_checks(acked, proposed, member_kvs, wal, win, lreads,
                             restart_kvs, sample, unserved)

    def _restart(self, acked, sample):
        """An acknowledged write survives a restart: stop, re-open on
        the same directory (WAL replay), read the sample everywhere."""
        marks_before = [m.applied_index.copy() for m in self.members]
        t0 = time.perf_counter()
        self.cluster.stop()
        self.cluster = None
        c2 = self._open()
        try:
            m2 = list(c2.members.values())
            deadline = time.monotonic() + self.RESTART_WAIT_S
            while time.monotonic() < deadline:
                if all(m.get(g, k) == acked[(g, k)]
                       for m in m2 for g, k in sample):
                    break
                time.sleep(0.1)
            restart_kvs = self._kvs(m2)
            stale = [(m.id, g, int(m.applied_index[g]),
                      int(marks_before[i][g]))
                     for i, m in enumerate(m2) for g, k in sample
                     if m.get(g, k) != acked[(g, k)]]
            if stale:
                say("restart_stale", member_group_applied_was=stale[:8])
        finally:
            c2.stop()
        say("restart", seconds=time.perf_counter() - t0,
            sample=len(sample))
        return restart_kvs

    def close(self) -> None:
        if self.cluster is not None:
            self.cluster.stop()
            self.cluster = None
