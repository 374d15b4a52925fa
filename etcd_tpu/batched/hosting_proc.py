"""Multi-raft hosting members as real OS processes.

One process = one ``MultiRaftMember`` (slot of every group) wired to its
peers by ``TCPRouter`` over real sockets — the deployment shape of the
reference, where each peer is a separate process reached via rafthttp
(ref: server/etcdserver/api/rafthttp/transport.go:97-132, Procfile).

The process exposes a small line-delimited JSON admin API on a local
TCP port so harnesses (tests/e2e, tools/multiraft_proc_demo) can drive
puts/reads, trigger campaigns, run a hosted-path benchmark, and stop it.
Run as::

    python -m etcd_tpu.batched.hosting_proc --id 1 --members 3 \
        --groups 1024 --data-dir /tmp/mr --bind 127.0.0.1:7001 \
        --admin 127.0.0.1:8001 --peer 2=127.0.0.1:7002 --peer 3=...
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import socket
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

# NB: jax import happens inside MultiRaftMember; keep module import
# cheap so the spawning harness can import the client half freely.


def _b64(b: bytes) -> str:
    return base64.b64encode(b).decode()


def _unb64(s: str) -> bytes:
    return base64.b64decode(s.encode())


# -- server side ---------------------------------------------------------------


class AdminServer:
    """Line-delimited JSON admin endpoint for one member process."""

    def __init__(self, member, router, bind: Tuple[str, int]) -> None:
        self.member = member
        self.router = router
        self._stopping = threading.Event()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(bind)
        self._srv.listen(8)
        self.addr = self._srv.getsockname()
        threading.Thread(target=self._accept, daemon=True).start()

    def _accept(self) -> None:
        while not self._stopping.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True).start()

    def _serve(self, conn: socket.socket) -> None:
        f = conn.makefile("rwb")
        try:
            for line in f:
                req: Dict = {}
                try:
                    req = json.loads(line)
                    resp = self._handle(req)
                except Exception as e:  # noqa: BLE001 — report to caller
                    resp = {"err": f"{type(e).__name__}: {e}"}
                f.write(json.dumps(resp).encode() + b"\n")
                f.flush()
                if req.get("op") == "stop":
                    break
        except (OSError, ValueError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def _handle(self, req: Dict) -> Dict:
        m = self.member
        op = req["op"]
        if op == "ping":
            # Liveness probes are exactly what gray failures slip past
            # (HotOS'17): a fail-stopped or disk-full member still
            # answers this socket, so the ping carries the IO-error
            # contract's state — orchestration can see "up but dead"
            # and "up but write-stalled" without the full health op.
            return {"ok": True, "id": m.id,
                    "fail_stop": m._fail_stop_cause,
                    "disk_full": m._disk_full}
        if op == "campaign":
            m.campaign(req["groups"])
            return {"ok": True}
        if op == "leaders":
            import numpy as np

            from .state import LEADER

            mask = np.asarray(m.rn.m_role == LEADER)
            leads = [int(m.rn.lead(g)) for g in req.get(
                "groups", range(m.g))]
            return {"ok": True, "leads": leads,
                    "own": int(mask.sum())}
        if op == "put":
            g = req["g"]
            from .hosting import GroupKV

            payload = GroupKV.put_payload(_unb64(req["k"]), _unb64(req["v"]))
            if not m.propose(g, payload):
                return {"ok": False, "redirect": m.leader_of(g)}
            return {"ok": True}
        if op == "get":
            v = m.get(req["g"], _unb64(req["k"]))
            return {"ok": True, "v": _b64(v) if v is not None else None}
        if op == "lget":
            try:
                v = m.linearizable_get(req["g"], _unb64(req["k"]),
                                       timeout=req.get("timeout", 10.0))
            except Exception as e:  # noqa: BLE001 — NotLeader/Timeout
                return {"ok": False, "err": type(e).__name__}
            return {"ok": True, "v": _b64(v) if v is not None else None}
        if op == "applied":
            g = req["g"]
            return {"ok": True, "applied": int(m.applied_index[g])}
        if op == "transfer":
            to = req["to"]
            if not isinstance(to, int) or not 1 <= to <= m.cfg.num_replicas:
                return {"err": f"transfer target must be a member id "
                               f"1..{m.cfg.num_replicas}, got {to!r}"}
            moved = [g for g in req["groups"] if m.transfer_leader(g, to)]
            # Bounded wait-for-completion (default on; wait_s=0 keeps
            # the old fire-and-forget): a transfer is DONE once this
            # member no longer leads the group (the transferee's
            # TimeoutNow campaign displaced it) — callers like
            # rebalancerd need completion, not staging, and an
            # unbounded wait would wedge the admin lane on a wedged
            # transferee.
            wait_s = float(req.get("wait_s", 5.0))
            done, pending = (m.wait_transfers(moved, to, timeout=wait_s)
                             if wait_s > 0 and moved else (moved, []))
            return {"ok": True, "moved": len(moved), "done": done,
                    "pending": pending}
        if op == "reconfig":
            # Batched membership admin (ISSUE 11): add-learner /
            # promote (catch-up-gated) / remove, proposed through the
            # log on groups this member leads; per-group results tell
            # the driver exactly what to retry where ("not-leader" →
            # redirect, "not-ready" → wait for catch-up, "refused" →
            # illegal against the current config).
            action = req["action"]
            target = req["member"]
            if (not isinstance(target, int)
                    or not 1 <= target <= m.cfg.num_replicas):
                return {"err": f"reconfig member must be a member id "
                               f"1..{m.cfg.num_replicas}, got {target!r}"}
            try:
                res = m.reconfig(action, target, req["groups"],
                                 joint=bool(req.get("joint", False)))
            except ValueError as e:
                return {"err": str(e)}
            ok_n = sum(1 for v in res.values() if v == "ok")
            return {"ok": True, "proposed": ok_n,
                    "results": {str(g): v for g, v in res.items()}}
        if op == "conf":
            # Membership rollup: per-group voters/learners/joint state
            # plus applied/refused totals (check_config_safety's admin
            # face; fleet_console reads the cheaper health census).
            snap = m.conf_snapshot()
            return {"ok": True,
                    "voters": [list(v) for v in snap["voters"]],
                    "learners": [list(v) for v in snap["learners"]],
                    "voters_out": [list(v) for v in snap["voters_out"]],
                    "in_joint": [int(x) for x in snap["in_joint"]],
                    "applied_index":
                        [int(x) for x in snap["applied_index"]],
                    "refused": snap["refused"]}
        if op == "prof_reset":
            for k in list(m.stats):
                m.stats[k] = 0 if isinstance(m.stats[k], int) else 0.0
            for k in list(m.rn.phase_total):
                m.rn.phase_total[k] = 0
            return {"ok": True}
        if op == "prof":
            # The member pipeline's stats plus the rawnode's cumulative
            # seconds per advance_round span (rn_stage, rn_step, ...).
            st = dict(m.stats)
            st.update({f"rn_{k}": v for k, v in m.rn.phase_total.items()})
            return {"ok": True, "stats": st}
        if op == "stats":
            # Loss/error observability (ISSUE 2 satellite): member
            # pipeline stats + the fabric's drop counters — queue-full
            # drops, dial failures, redial-budget drops, send errors —
            # so operators see loss instead of silence. (The counters
            # live on the shared metrics registry; see op 'metrics'
            # for the full Prometheus-text dump.)
            rstats = {}
            rs = getattr(self.router, "stats", None)
            if callable(rs):
                rstats = rs()
            # Fabric identity + per-lane ring occupancy (shm only):
            # fleet_console's transport column reads this.
            fabric = {"kind": getattr(self.router, "kind", "tcp")}
            ls = getattr(self.router, "lane_stats", None)
            if callable(ls):
                fabric["lanes"] = ls()
            return {"ok": True, "member": dict(m.stats),
                    "router": rstats, "fabric": fabric}
        if op == "health":
            # Durability-fence visibility (protocol-aware torn-tail
            # recovery): per-group fenced state, the index gap still to
            # close to the durable watermark, and the boot WAL-tail
            # classification (clean boundary vs mid-record break) —
            # plus, since ISSUE 15, the IO-error contract's state:
            # disk_full back-pressure, the fail-stop cause, and the
            # boot-time salvage record for at-rest corruption.
            return {"ok": True, **m.health()}
        if op == "metrics":
            # Prometheus text exposition of the process registry —
            # kernel telemetry counters, invariant trips, WAL fsync /
            # round-phase histograms, router loss classes. Scrape with
            # tools/dump_metrics.py --admin host:port.
            from ..pkg import metrics as pmet

            return {"ok": True, "text": pmet.DEFAULT.expose()}
        if op == "trace":
            # Proposal-lifecycle trace ring (etcd_tpu.obs): inline
            # payload by default (tools/trace_merge.py joins the
            # members' payloads), or a JSON dump next to the flight
            # recorders with {"dump": true}.
            if m.tracer is None:
                return {"err": "tracing disabled (start the member "
                               "with --trace / ETCD_TPU_TRACE=1)"}
            # "rounds": the member's round-span ring (obs.spans), so
            # that the merge can place a proposal's hops inside the
            # rounds that carried them (same clock; a sampled
            # proposal's stage stamp is its round's rawnode.stage
            # start).
            from ..obs import spans

            if req.get("dump"):
                reason = req.get("reason", "admin")
                path = m.tracer.dump(reason=reason)
                return {"ok": True, "path": path,
                        "spans": m.tracer.span_count(),
                        "rounds_path": spans.DEFAULT.dump(
                            m.id, reason, m.tracer.dump_dir)}
            payload = m.tracer.to_payload()
            payload["rounds"] = spans.DEFAULT.to_payload(m.id)
            return {"ok": True, "payload": payload}
        if op == "fleet":
            # Fleet observatory (obs/fleet.py): inline rollup of the
            # latest device SummaryFrame — leader balance, top-K
            # laggards with group ids, fenced/role/progress censuses,
            # anomaly flags — or a groups×time heatmap ring dump with
            # {"dump": true}. tools/fleet_console.py renders the
            # rollups of every member as a live cluster view.
            if m.fleet is None:
                return {"err": "fleet summary disabled (start the "
                               "member with --fleet)"}
            if req.get("dump"):
                path = m.fleet.dump(reason=req.get("reason", "admin"))
                return {"ok": True, "path": path,
                        "frames": m.fleet.frames()}
            return {"ok": True, "rollup": m.fleet.snapshot(),
                    "invariant_trips": (m.hub.trips()
                                        if m.hub is not None else None)}
        if op == "flightrec":
            # Dump the member's flight recorder (last K rounds of
            # per-group telemetry deltas) to a JSON file on demand.
            if m.hub is None:
                return {"err": "telemetry disabled "
                               "(BatchedConfig.telemetry=False)"}
            path = m.hub.dump(reason=req.get("reason", "admin"))
            return {"ok": True, "path": path,
                    "trips": m.hub.trips()}
        if op == "bench":
            return self._bench(int(req["n"]),
                               int(req.get("value_size", 64)),
                               int(req.get("inflight", 4)),
                               float(req.get("read_mix", 0.0)))
        if op == "stop":
            threading.Thread(target=self._shutdown, daemon=True).start()
            return {"ok": True}
        return {"err": f"unknown op {op}"}

    def _bench(self, n: int, value_size: int,
               inflight: int = 4, read_mix: float = 0.0) -> Dict:
        """Hosted-path benchmark: propose n entries across the groups
        this member leads, confirm each applied locally (read-your-
        write at the leader), report throughput + commit p50/p99 —
        the service-rate number next to bench.py's kernel rate.

        read_mix in (0, 1] converts that fraction of the n ops into
        linearizable reads interleaved with the put stream (the first
        non-put hosted workload): each read is a synchronous
        linearizable_get on a bench key of a led group — lease-held
        leaders serve it locally with zero quorum rounds, cold leaders
        fall back to ReadIndex; the hit/fallback split rides the
        result so hosted_bench's SLO table reports the read hop."""
        import numpy as np

        from ..pkg.errors import NotLeaderError
        from .hosting import GroupKV
        from .state import LEADER

        m = self.member
        own = [g for g in range(m.g) if m.is_leader(g)]
        if not own:
            return {"err": "no groups led by this member"}
        val = b"v" * value_size
        n_reads = max(0, min(n, int(round(n * read_mix))))
        n = n - n_reads
        rd_lat: List[float] = []
        rd_lost = 0
        rd_issued = 0
        hits0 = int(m.stats.get("lease_read_hits", 0))
        falls0 = int(m.stats.get("lease_read_fallbacks", 0))

        def do_reads(owed: int) -> None:
            nonlocal rd_issued, rd_lost
            for _ in range(owed):
                g = own[rd_issued % len(own)]
                k = b"bench-%d" % (rd_issued % max(n, 1))
                t0 = time.perf_counter()
                try:
                    m.linearizable_get(g, k, timeout=5.0)
                    rd_lat.append(time.perf_counter() - t0)
                except (NotLeaderError, TimeoutError):
                    rd_lost += 1
                rd_issued += 1

        t_start = time.perf_counter()
        # Pipeline: propose in waves to bound the per-group inflight
        # (the engine caps proposals staged per round). A proposal
        # queued on a row that loses leadership before a round consumes
        # it is stranded (leader-only propose, no cross-member
        # forwarding at this layer), so stuck keys are re-proposed
        # while we still lead and counted lost otherwise — the etcd
        # benchmark tool's client-side retry, collapsed into the
        # worker (ref: tools/benchmark/cmd/put.go retry-on-error).
        lat: List[float] = []
        # Completion detection is watermark-driven: one numpy compare
        # of applied_index per poll, then key checks ONLY for groups
        # whose watermark moved — a flat poll over every outstanding
        # key burned most of the core and displaced the round loop it
        # was measuring.
        from collections import deque as _dq

        pend: Dict[int, "_dq"] = {g: _dq() for g in own}
        outstanding = 0
        lost = 0
        i = 0
        deadline = time.perf_counter() + max(60.0, n / 50.0)
        last_applied = m.applied_index.copy()
        last_sweep = time.perf_counter()
        while i < n or outstanding:
            while i < n and outstanding < inflight * len(own):
                g = own[i % len(own)]
                k = b"bench-%d" % i
                now = time.perf_counter()
                if m.propose(g, GroupKV.put_payload(k, val)):
                    pend[g].append([k, now, now])
                    outstanding += 1
                else:
                    lost += 1
                i += 1
            arr = m.applied_index.copy()
            now = time.perf_counter()
            changed = np.nonzero(arr != last_applied)[0]
            last_applied = arr
            sweep = now - last_sweep > 1.0
            groups = pend.keys() if sweep else changed
            if sweep:
                last_sweep = now
            for g in groups:
                q = pend.get(g)
                if not q:
                    continue
                while q and m.get(g, q[0][0]) is not None:
                    _k, t0, _tp = q.popleft()
                    outstanding -= 1
                    lat.append(now - t0)
                if sweep:
                    for rec in q:
                        if now - rec[2] > 2.0:
                            if m.propose(g, GroupKV.put_payload(
                                    rec[0], val)):
                                rec[2] = now
                            else:
                                rec[2] = float("inf")  # stranded
                    while q and q[0][2] == float("inf"):
                        q.popleft()
                        outstanding -= 1
                        lost += 1
            # Interleave owed reads with the put stream (same clock,
            # same thread — the mix is a schedule, not a second
            # phase, so the A/B stays same-day AND same-second).
            if n_reads and n:
                do_reads(min(i * n_reads // n, n_reads) - rd_issued)
            if now > deadline:
                lost += outstanding
                outstanding = 0
                break
            if outstanding:
                time.sleep(0.005)
        if n_reads:
            do_reads(n_reads - rd_issued)  # pure-read mixes land here
        dt = time.perf_counter() - t_start
        if not lat and not rd_lat:
            return {"err": "no ops completed", "lost": lost + rd_lost}
        lat_ms = sorted(x * 1000 for x in lat) or [0.0]
        out = {
            "ok": True,
            "n": n,
            "completed": len(lat),
            "lost": lost,
            "groups": len(own),
            "puts_per_sec": round(len(lat) / dt, 1) if lat else 0.0,
            "p50_ms": round(lat_ms[len(lat_ms) // 2], 3),
            "p99_ms": round(lat_ms[int(len(lat_ms) * 0.99) - 1], 3),
            # Raw samples so a multi-member harness can compute true
            # percentiles of the MERGED distribution (a mean of p50s is
            # not a percentile of anything).
            "lat_ms_samples": [round(x, 2) for x in lat_ms],
        }
        if n_reads:
            rms = sorted(x * 1000 for x in rd_lat) or [0.0]
            out.update({
                "reads": n_reads,
                "reads_completed": len(rd_lat),
                "reads_lost": rd_lost,
                "reads_per_sec": (
                    round(len(rd_lat) / dt, 1) if rd_lat else 0.0),
                "read_p50_ms": round(rms[len(rms) // 2], 3),
                "read_p99_ms": round(rms[int(len(rms) * 0.99) - 1], 3),
                "read_lat_ms_samples": [round(x, 2) for x in rms],
                # Serving-path split over THIS bench window (stats
                # deltas): lease_hit reads took zero quorum rounds.
                "lease_hits": int(
                    m.stats.get("lease_read_hits", 0)) - hits0,
                "lease_fallbacks": int(
                    m.stats.get("lease_read_fallbacks", 0)) - falls0,
            })
        return out

    def close(self) -> None:
        """Close the listening socket WITHOUT exiting the process —
        the in-process embedding path (tools/fleet_smoke.py hosts
        AdminServers around in-proc members); the worker-process path
        keeps using the 'stop' op → _shutdown → os._exit contract."""
        self._stopping.set()
        try:
            self._srv.close()
        except OSError:
            pass

    def _shutdown(self) -> None:
        self._stopping.set()
        try:
            self.member.stop()
        finally:
            self.router.stop()
            try:
                self._srv.close()
            except OSError:
                pass
            # Hard-exit: daemon threads (jax runtime included) must not
            # keep the worker alive after an orderly stop.
            os._exit(0)


def serve(member_id: int, num_members: int, num_groups: int,
          data_dir: str, bind: Tuple[str, int],
          admin: Tuple[str, int],
          peers: Dict[int, Tuple[str, int]],
          window: int = 32,
          tick_interval: float = 0.1,
          telemetry: bool = False,
          fleet: bool = False,
          trace: Optional[bool] = None,
          wal_pipeline: Optional[bool] = None,
          fabric: str = "tcp",
          shm_dir: Optional[str] = None,
          pin_core: Optional[int] = None,
          snap_cadence: Optional[int] = None,
          snap_keep: int = 2,
          wal_rotate_bytes: Optional[int] = None,
          apply_plane: bool = False) -> None:
    from .hosting import MultiRaftMember
    from .state import BatchedConfig

    if fabric == "inproc":
        raise SystemExit(
            "--fabric=inproc is the single-process harness fabric "
            "(MultiRaftCluster / tools/fleet_smoke.py); a hosting_proc "
            "worker is its own OS process — use tcp or shm")
    if fabric == "shm" and not shm_dir:
        raise SystemExit("--fabric=shm requires --shm-dir (one "
                         "directory SHARED by all member processes)")
    if pin_core is not None:
        # One pinned core per member process (true multi-core runs):
        # the shm fabric's whole point is that co-hosted members stop
        # time-slicing one socket loop.
        try:
            os.sched_setaffinity(0, {pin_core})
        except (AttributeError, OSError) as e:
            print(f"member {member_id}: pin to core {pin_core} "
                  f"failed: {e}", flush=True)

    cfg = BatchedConfig(
        num_groups=num_groups,
        num_replicas=num_members,
        window=window,
        max_ents_per_msg=4,
        max_props_per_round=4,
        election_timeout=10,
        heartbeat_timeout=1,
        pre_vote=True,
        check_quorum=True,
        auto_compact=True,
        # --telemetry: kernel counters + invariant sweep + flight
        # recorder, served through the admin 'metrics'/'flightrec' ops.
        telemetry=telemetry,
        # --fleet: device-side fleet SummaryFrame + FleetHub, served
        # through the admin 'fleet' op (tools/fleet_console.py).
        fleet_summary=fleet,
        # --apply-plane (ISSUE 19): device-resident KV/watch/lease
        # tensors + leader-lease local reads; the bench op's read_mix
        # serving-path split and the admin 'health' apply_plane block
        # light up with it.
        apply_plane=apply_plane,
    )
    member = MultiRaftMember(
        member_id, num_members, num_groups, data_dir, cfg=cfg,
        tick_interval=tick_interval, trace=trace,
        # --wal-pipeline / ETCD_TPU_WAL_PIPELINE (ISSUE 13): async
        # group-commit WAL pipeline — persistence decoupled from the
        # round cadence, acks released on fsync completion.
        wal_pipeline=wal_pipeline,
        # --snap-cadence / --wal-rotate-bytes (ISSUE 17): log-lifecycle
        # plane — cadence file snapshots, WAL segment rotation and
        # fleet-min-gated release; admin 'health' reports the
        # lifecycle/ring blocks, fleet_console renders them.
        snap_cadence=snap_cadence,
        snap_keep=snap_keep,
        wal_rotate_bytes=wal_rotate_bytes,
    )
    if fabric == "shm":
        from .shmfabric import ShmFabric

        router = ShmFabric(member, shm_dir)
        for pid in peers:
            router.add_peer(pid)
        raft_ep = f"shm:{shm_dir}"
    else:
        from .hosting import TCPRouter

        router = TCPRouter(member, bind=bind)
        for pid, addr in peers.items():
            router.add_peer(pid, addr)
        raft_ep = router.addr
    srv = AdminServer(member, router, admin)
    member.start()
    print(f"member {member_id} serving: raft={raft_ep} "
          f"admin={srv.addr} groups={num_groups}", flush=True)
    threading.Event().wait()  # park; admin 'stop' hard-exits


def main(argv: Optional[List[str]] = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--id", type=int, required=True)
    p.add_argument("--members", type=int, required=True)
    p.add_argument("--groups", type=int, required=True)
    p.add_argument("--data-dir", required=True)
    p.add_argument("--bind", required=True)
    p.add_argument("--admin", required=True)
    p.add_argument("--peer", action="append", default=[],
                   help="peerid=host:port (repeatable)")
    p.add_argument("--window", type=int, default=32)
    p.add_argument("--tick-interval", type=float, default=0.1)
    p.add_argument("--telemetry", action="store_true",
                   help="enable the kernel telemetry plane (metrics + "
                        "flight recorder via the admin API)")
    p.add_argument("--fleet", action="store_true",
                   help="enable the fleet observatory (device-side "
                        "group-state summary frames; admin 'fleet' op "
                        "+ etcd_tpu_fleet_* metrics + heatmap ring — "
                        "see tools/fleet_console.py)")
    p.add_argument("--trace", action="store_true",
                   help="enable proposal-lifecycle tracing (sampled "
                        "span stamps; admin 'trace' op serves the "
                        "ring — see ETCD_TPU_TRACE_SAMPLE/_SEED)")
    p.add_argument("--wal-pipeline", action="store_true",
                   help="run persistence as an async group-commit "
                        "pipeline: WAL append+fsync on a dedicated "
                        "worker overlapped with device rounds, one "
                        "fsync covering every round queued since the "
                        "last, acks released at fsync completion "
                        "(ETCD_TPU_WAL_PIPELINE=1 is the env form; "
                        "admin 'health' reports rounds_per_fsync)")
    p.add_argument("--fabric", choices=("tcp", "shm", "inproc"),
                   default="tcp",
                   help="peer transport: tcp (TCPRouter sockets, "
                        "default), shm (mmap'd SPSC ring fabric for "
                        "co-hosted members — requires --shm-dir), "
                        "inproc (single-process harness only; a "
                        "worker process rejects it with a pointer)")
    p.add_argument("--shm-dir", default=None,
                   help="directory for the shm fabric's lane ring "
                        "files; must be the SAME directory for every "
                        "member process of the cluster")
    p.add_argument("--pin-core", type=int, default=None,
                   help="pin this member process to one CPU core "
                        "(sched_setaffinity) — one core per member "
                        "is the multi-core hosted-bench shape")
    p.add_argument("--snap-cadence", type=int, default=None,
                   help="build a file snapshot for a group every N "
                        "applied entries (log-lifecycle plane; off by "
                        "default — the WAL then grows unboundedly)")
    p.add_argument("--snap-keep", type=int, default=2,
                   help="snapshot files retained per group after each "
                        "successful build (keep-K pruning)")
    p.add_argument("--wal-rotate-bytes", type=int, default=None,
                   help="cut the WAL tail segment past this many "
                        "bytes and release sealed segments once every "
                        "group's snapshot covers them (off by "
                        "default)")
    p.add_argument("--apply-plane", action="store_true",
                   help="enable the device-resident apply plane "
                        "(tensorized KV/watch/lease state + leader-"
                        "lease local reads; protocol state stays "
                        "bit-identical — see README 'Device apply "
                        "plane')")
    a = p.parse_args(argv)

    def hp(s: str) -> Tuple[str, int]:
        h, _, pt = s.rpartition(":")
        return h, int(pt)

    peers = {}
    for spec in a.peer:
        pid, _, addr = spec.partition("=")
        peers[int(pid)] = hp(addr)
    serve(a.id, a.members, a.groups, a.data_dir, hp(a.bind),
          hp(a.admin), peers, window=a.window,
          tick_interval=a.tick_interval, telemetry=a.telemetry,
          fleet=a.fleet, trace=a.trace or None,
          wal_pipeline=a.wal_pipeline or None,
          fabric=a.fabric, shm_dir=a.shm_dir, pin_core=a.pin_core,
          snap_cadence=a.snap_cadence, snap_keep=a.snap_keep,
          wal_rotate_bytes=a.wal_rotate_bytes,
          apply_plane=a.apply_plane)


# -- client side ---------------------------------------------------------------


class ProcClient:
    """Admin-API client for one member process."""

    def __init__(self, addr: Tuple[str, int], timeout: float = 60.0):
        self.addr = addr
        self.timeout = timeout
        self._sock: Optional[socket.socket] = None
        self._f = None
        self._lock = threading.Lock()

    def _ensure(self) -> None:
        if self._sock is None:
            self._sock = socket.create_connection(
                self.addr, timeout=self.timeout)
            self._f = self._sock.makefile("rwb")

    def call(self, **req) -> Dict:
        with self._lock:
            self._ensure()
            try:
                self._f.write(json.dumps(req).encode() + b"\n")
                self._f.flush()
                line = self._f.readline()
            except OSError:
                self.close()
                raise
            if not line:
                self.close()
                raise ConnectionError("admin connection closed")
            return json.loads(line)

    def put(self, g: int, k: bytes, v: bytes) -> Dict:
        return self.call(op="put", g=g, k=_b64(k), v=_b64(v))

    def get(self, g: int, k: bytes) -> Optional[bytes]:
        r = self.call(op="get", g=g, k=_b64(k))
        return _unb64(r["v"]) if r.get("v") else None

    def lget(self, g: int, k: bytes, timeout: float = 10.0) -> Dict:
        return self.call(op="lget", g=g, k=_b64(k), timeout=timeout)

    def close(self) -> None:
        try:
            if self._sock is not None:
                self._sock.close()
        except OSError:
            pass
        self._sock = None
        self._f = None


def wait_admin(addr: Tuple[str, int], timeout: float = 120.0) -> ProcClient:
    """Wait for a member process's admin endpoint to come up (device
    program compile happens at process start and can take a while)."""
    deadline = time.monotonic() + timeout
    last: Optional[Exception] = None
    while time.monotonic() < deadline:
        try:
            c = ProcClient(addr)
            r = c.call(op="ping")
            if r.get("ok"):
                return c
        except (OSError, ConnectionError, ValueError) as e:
            last = e
        time.sleep(0.25)
    raise TimeoutError(f"admin {addr} not up: {last}")


if __name__ == "__main__":
    main()
