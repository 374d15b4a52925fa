"""Hosted-path benchmark, a CPU tool: 3 real OS processes over a
selectable peer fabric (``--fabric=tcp`` sockets or ``--fabric=shm``
mmap'd SPSC rings, ISSUE 16), G groups — the service-rate number next
to bench.py's kernel rate (review round 4, task 1: an artifact with a
floor). A chip belongs to one process, so three member processes
cannot share it: every worker is pinned to ``JAX_PLATFORMS=cpu`` and
the artifact says ``"platform": "cpu"``. Its numbers are not device
metrics; the served path on the chip is ``chip_smoke.py``'s in-process
cluster. ``--pin-cores`` pins member i to core (i-1) mod ncpu, the
one-core-per-member multi-core shape.

Writes HOSTED_BENCH.json at the repo root:

    {"platform": "cpu",
     "puts_per_sec": ..., "p50_ms": ..., "p99_ms": ...,
     "n": ..., "groups_led": ...,
     "phase_ms_per_round": {"stage": ..., "step": ..., "extract": ...,
                            "collect": ..., "wal": ..., "apply": ...,
                            "send": ...},
     "restart_catchup_s": ..., "config": "...", "captured_at": "..."}

(phase_ms_per_round is the member-round budget averaged over members —
the hosted phase table, reproducible from the artifact; the same
split is exported as the round-phase histograms under --telemetry.)

With ``--trace`` the workers run the proposal-lifecycle tracer
(etcd_tpu.obs) and the artifact additionally carries ``slo``: per-hop
p50/p99 over the merged cross-member spans (the named decomposition
propose→stage→step→fsync→send→peer-fsync→ack→commit→apply) plus
traced commit/apply percentiles — the per-hop budget shape ROADMAP
item 4's gRPC SLO story consumes. The merged Perfetto trace lands in
``artifacts/hosted_trace.json``. Tracing has measurable sampling cost,
so ``--trace`` runs are labeled and are NOT the parity baseline.

``--wal-pipeline`` (or ``ETCD_TPU_WAL_PIPELINE=1``) flies the workers
with the async group-commit WAL pipeline (ISSUE 13); A/B rows against
the same-day inline baseline are written with ``--out`` (e.g.
``artifacts/hosted_walpipe_*.json``). Pair with
``ETCD_TPU_FSYNC_DELAY_MS`` (walog-level slow-disk emulation) on boxes
whose local fsync is microsecond-class — the pipeline overlaps IO
wait, so a free fsync leaves nothing to win.

``--apply-plane`` flies the workers with the device-resident apply
plane (ISSUE 19: tensorized KV + leader leases); ``--read-mix 0.9``
converts that fraction of each member's ops into linearizable reads
and records a ``reads`` block (merged read percentiles plus the
lease-hit vs ReadIndex-fallback split). With ``--trace`` the SLO
table additionally carries a ``read_hop`` row with the same split —
the apply plane's headline is leased reads taking ZERO quorum hops.

Run:  python -m etcd_tpu.tools.hosted_bench [--groups 1024] [--n 3000]
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

MEMBERS = 3


def free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def spawn(mid, raft_ports, admin_ports, data_dir, groups, gen=0,
          trace=0, wal_pipeline=False, fabric="tcp", shm_dir=None,
          pin_cores=False, apply_plane=False):
    peers = [
        f"--peer={pid}=127.0.0.1:{raft_ports[pid]}"
        for pid in range(1, MEMBERS + 1) if pid != mid
    ]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # three processes cannot share a chip
    if trace:
        # Sample rate shared by all members (the cross-member join
        # requires identical sampling decisions); seed pinned so two
        # --trace runs sample the same key population.
        env["ETCD_TPU_TRACE_SAMPLE"] = str(trace)
        env.setdefault("ETCD_TPU_TRACE_SEED", "0")
    # Transfer sentinel (ISSUE 7): worker round dispatch fails hard on
    # any implicit transfer instead of silently syncing per round.
    env.setdefault("ETCD_TPU_TRANSFER_GUARD", "disallow")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    log = open(os.path.join(data_dir, f"worker-{mid}-gen{gen}.log"), "wb")
    return subprocess.Popen(
        [
            sys.executable, "-m", "etcd_tpu.batched.hosting_proc",
            "--id", str(mid), "--members", str(MEMBERS),
            "--groups", str(groups), "--data-dir", data_dir,
            "--bind", f"127.0.0.1:{raft_ports[mid]}",
            "--admin", f"127.0.0.1:{admin_ports[mid]}",
            "--tick-interval", "0.1",
        ] + (["--trace"] if trace else [])
        + (["--wal-pipeline"] if wal_pipeline else [])
        + (["--apply-plane"] if apply_plane else [])
        + (["--fabric", fabric] if fabric != "tcp" else [])
        + (["--shm-dir", shm_dir] if fabric == "shm" else [])
        # One pinned core per member: member i on core (i-1) mod ncpu.
        # On a 1-core box every member pins to core 0 (the status quo
        # made explicit); on a real multi-core box this is the shape
        # the shm fabric's headline targets assume.
        + (["--pin-core", str((mid - 1) % (os.cpu_count() or 1))]
           if pin_cores else [])
        + peers,
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )


def main() -> None:
    from etcd_tpu.batched.hosting_proc import wait_admin

    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=1024)
    ap.add_argument("--n", type=int, default=3000)
    ap.add_argument("--value-size", type=int, default=64)
    ap.add_argument("--inflight", type=int, default=4,
                    help="wave cap per led group")
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace", type=int, nargs="?", const=8, default=0,
                    metavar="SAMPLE",
                    help="run the workers with proposal-lifecycle "
                         "tracing (1-in-SAMPLE, default 8) and record "
                         "the per-hop SLO table into the artifact")
    from etcd_tpu.pkg import env_flag

    ap.add_argument("--wal-pipeline", action="store_true",
                    default=env_flag("ETCD_TPU_WAL_PIPELINE"),
                    help="run the workers with the async group-commit "
                         "WAL pipeline (ISSUE 13); also honored via "
                         "ETCD_TPU_WAL_PIPELINE=1 — A/B against the "
                         "same-day inline baseline")
    ap.add_argument("--fabric", choices=("tcp", "shm"), default="tcp",
                    help="peer transport for the workers: tcp "
                         "(TCPRouter sockets, default) or shm (the "
                         "mmap'd SPSC ring fabric, ISSUE 16); "
                         "artifacts are labeled with the choice")
    ap.add_argument("--shm-dir", default=None,
                    help="shared lane-ring directory for --fabric=shm "
                         "(default: <data-dir>/shmfabric)")
    ap.add_argument("--pin-cores", action="store_true",
                    help="pin member i to core (i-1) mod ncpu — the "
                         "one-core-per-member multi-core shape")
    ap.add_argument("--apply-plane", action="store_true",
                    help="run the workers with the device-resident "
                         "apply plane (ISSUE 19): tensorized KV + "
                         "leader leases; lease-held linearizable "
                         "reads skip the ReadIndex quorum round")
    ap.add_argument("--read-mix", type=float, default=0.0,
                    metavar="FRAC",
                    help="fraction of each member's ops issued as "
                         "linearizable reads (e.g. 0.9); the SLO "
                         "table gains a read-hop row splitting "
                         "lease-hit vs ReadIndex-fallback")
    args = ap.parse_args()
    if not 0.0 <= args.read_mix <= 1.0:
        ap.error("--read-mix must be in [0, 1]")
    # Slow-disk emulation label (native/walog.py): a bench flown with
    # ETCD_TPU_FSYNC_DELAY_MS set must say so in its artifact config.
    fsync_delay = os.environ.get("ETCD_TPU_FSYNC_DELAY_MS", "")
    delay_tag = (f" fsync_delay={fsync_delay}ms"
                 if fsync_delay not in ("", "0") else "")
    import tempfile

    data_dir = args.data_dir or tempfile.mkdtemp(prefix="hosted-bench-")
    shm_dir = args.shm_dir or os.path.join(data_dir, "shmfabric")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out_path = args.out or os.path.join(repo, "HOSTED_BENCH.json")

    raft_p = dict(zip(range(1, MEMBERS + 1), free_ports(MEMBERS)))
    admin_p = dict(zip(range(1, MEMBERS + 1), free_ports(MEMBERS)))
    procs, clients = {}, {}
    try:
        for mid in range(1, MEMBERS + 1):
            procs[mid] = spawn(mid, raft_p, admin_p, data_dir,
                               args.groups, trace=args.trace,
                               wal_pipeline=args.wal_pipeline,
                               fabric=args.fabric, shm_dir=shm_dir,
                               pin_cores=args.pin_cores,
                               apply_plane=args.apply_plane)
        for mid in range(1, MEMBERS + 1):
            clients[mid] = wait_admin(("127.0.0.1", admin_p[mid]),
                                      timeout=300.0)
        # Balanced leadership: group g led by member g%3+1, re-asserted
        # until it holds STEADY — an unbalanced cluster turns the bench
        # into a one-member measurement (and a still-settling one loses
        # proposals to leadership moves mid-run).
        deadline = time.monotonic() + 300.0
        nudge = 0.0
        while time.monotonic() < deadline:
            leads = clients[1].call(op="leaders")["leads"]
            misplaced = [g for g, x in enumerate(leads)
                         if x != g % MEMBERS + 1]
            if not misplaced:
                break
            if time.monotonic() > nudge:
                for mid, c in clients.items():
                    # Groups this member should NOT lead but does:
                    # transfer them to their assigned member. Groups
                    # with no leader at all: campaign directly.
                    for target in range(1, MEMBERS + 1):
                        if target == mid:
                            continue
                        mine = [g for g in misplaced
                                if leads[g] == mid
                                and g % MEMBERS == target - 1]
                        if mine:
                            # wait_s=0: this loop is a periodic nudge
                            # with its own re-poll cadence — the op's
                            # default bounded wait would serialize up
                            # to MEMBERS^2 five-second waits per pass.
                            c.call(op="transfer", groups=mine[:512],
                                   to=target, wait_s=0)
                    orphans = [g for g in misplaced
                               if leads[g] == 0
                               and g % MEMBERS == mid - 1]
                    if orphans:
                        c.call(op="campaign", groups=orphans[:512])
                nudge = time.monotonic() + 3.0
            time.sleep(0.25)
        else:
            raise TimeoutError(f"leadership never balanced "
                               f"({len(misplaced)} misplaced)")
        time.sleep(2.0)  # settle
        for c in clients.values():
            c.call(op="prof_reset")

        # Aggregate service rate: all three members bench their own
        # groups CONCURRENTLY (each drives ~G/3 leaders; the cluster's
        # real offered-load shape, like `benchmark put` with multiple
        # clients against all endpoints).
        from concurrent.futures import ThreadPoolExecutor

        from etcd_tpu.batched.hosting_proc import ProcClient

        per = max(args.n // MEMBERS, 1)

        def run_bench(mid):
            bc = ProcClient(("127.0.0.1", admin_p[mid]), timeout=900.0)
            try:
                return bc.call(op="bench", n=per,
                               value_size=args.value_size,
                               inflight=args.inflight,
                               read_mix=args.read_mix)
            finally:
                bc.close()

        with ThreadPoolExecutor(MEMBERS) as ex:
            parts = list(ex.map(run_bench, range(1, MEMBERS + 1)))
        bad = [p for p in parts if not p.get("ok")]
        if bad:
            raise RuntimeError(f"bench failed: {bad}")
        # Per-phase member-round budget (ms/round, averaged over the
        # members): stage/step/extract/collect from the rawnode timers
        # (rn.phase_total, summed from the round spans), wal/apply/send from
        # the member pipeline stats — the hosted phase table,
        # recorded in the artifact instead of ad-hoc profiling.
        phase_ms = {}
        for mid, c in clients.items():
            prof = c.call(op="prof")
            st = prof.get("stats", {})
            print(f"member {mid} prof: {st}", file=sys.stderr)
            rounds = max(st.get("rn_rounds", 0), 1)
            m_rounds = max(st.get("rounds", 0), 1)
            for p in ("stage", "step", "extract", "collect"):
                v = st.get(f"rn_{p}")
                if v is not None:
                    phase_ms.setdefault(p, []).append(v / rounds * 1e3)
            # "fsync" (stats fsync_s) is the device half alone; with
            # the pipeline on it runs OFF the round thread, so the
            # amortized ms/round here shrinking is the headline.
            for p in ("wal", "apply", "send", "fsync"):
                v = st.get(f"{p}_s")
                if v is not None:
                    phase_ms.setdefault(p, []).append(v / m_rounds * 1e3)
        phase_ms = {
            p: round(sum(v) / len(v), 2) for p, v in phase_ms.items()
        }
        # Aggregate: throughputs add (concurrent windows); percentiles
        # come from the UNION of the members' latency samples.
        total_done = sum(p["completed"] for p in parts)
        merged = sorted(
            x for p in parts for x in p.pop("lat_ms_samples", []))
        bench = {
            "ok": True,
            "n": sum(p["n"] for p in parts),
            "completed": total_done,
            "lost": sum(p["lost"] for p in parts),
            "groups": sum(p["groups"] for p in parts),
            "puts_per_sec": round(
                sum(p["puts_per_sec"] for p in parts), 1),
            "p50_ms": merged[len(merged) // 2] if merged else 0.0,
            "p99_ms": (merged[max(int(len(merged) * 0.99) - 1, 0)]
                       if merged else 0.0),
            "per_member": parts,
        }
        # Read-mix lane (ISSUE 19): merged read percentiles from the
        # union of samples (same rule as writes) plus the lease-hit /
        # ReadIndex-fallback split — the apply plane's headline is the
        # hit ratio, not just the latency.
        if args.read_mix > 0:
            rmerged = sorted(
                x for p in parts for x in p.pop("read_lat_ms_samples", []))
            hits = sum(p.get("lease_hits", 0) for p in parts)
            falls = sum(p.get("lease_fallbacks", 0) for p in parts)
            bench["reads"] = {
                "n": sum(p.get("reads", 0) for p in parts),
                "completed": sum(p.get("reads_completed", 0)
                                 for p in parts),
                "lost": sum(p.get("reads_lost", 0) for p in parts),
                "reads_per_sec": round(
                    sum(p.get("reads_per_sec", 0.0) for p in parts), 1),
                "p50_ms": rmerged[len(rmerged) // 2] if rmerged else 0.0,
                "p99_ms": (rmerged[max(int(len(rmerged) * 0.99) - 1, 0)]
                           if rmerged else 0.0),
                "lease_hits": hits,
                "lease_fallbacks": falls,
                "lease_hit_ratio": round(hits / max(hits + falls, 1), 4),
            }

        # SLO table (--trace): pull every member's span ring over the
        # admin 'trace' op and join them in-process — per-hop p50/p99
        # on the aligned clock, the shape the gRPC front-end's SLO
        # story consumes. Captured BEFORE the kill below tears member
        # 3's ring away.
        slo = None
        if args.trace:
            from etcd_tpu.obs.export import validate_chrome_trace
            from etcd_tpu.obs.merge import hop_stats, merge

            payloads = []
            for mid, c in clients.items():
                r = c.call(op="trace")
                if r.get("ok"):
                    payloads.append(r["payload"])
                else:
                    print(f"member {mid} trace pull failed: {r}",
                          file=sys.stderr)
            if len(payloads) == MEMBERS:
                trace_obj, slo = merge(payloads)
                validate_chrome_trace(trace_obj)
                tpath = os.path.join(repo, "artifacts",
                                     "hosted_trace.json")
                os.makedirs(os.path.dirname(tpath), exist_ok=True)
                with open(tpath, "w") as f:
                    json.dump(trace_obj, f)
                    f.write("\n")
                slo["merged_trace"] = os.path.relpath(tpath, repo)
                # Self-labeling: the slo block names its own capture
                # conditions, so grafting it into an untraced headline
                # artifact (traced runs are never the headline — the
                # sampling cost is real) keeps the provenance visible.
                # Read hop (ISSUE 19): the client-observed
                # linearizable-read latency next to the traced write
                # hops, with the lease-hit vs ReadIndex-fallback split
                # counted separately. Kept OUT of slo["hops"] — those
                # rows telescope to the write e2e; this one doesn't.
                if args.read_mix > 0 and "reads" in bench:
                    r = bench["reads"]
                    slo["read_hop"] = {
                        "n": r["completed"],
                        "p50_ms": r["p50_ms"],
                        "p99_ms": r["p99_ms"],
                        "lease_hit": r["lease_hits"],
                        "readindex_fallback": r["lease_fallbacks"],
                        "lease_hit_ratio": r["lease_hit_ratio"],
                    }
                slo["config"] = (f"G={args.groups} R={MEMBERS} "
                                 f"value={args.value_size}B "
                                 f"inflight={args.inflight}/group CPU "
                                 f"fabric={args.fabric} "
                                 f"trace=1/{args.trace}"
                                 + (" walpipe=on" if args.wal_pipeline
                                    else "")
                                 + (" applyplane=on" if args.apply_plane
                                    else "")
                                 + (f" read_mix={args.read_mix:g}"
                                    if args.read_mix > 0 else "")
                                 + delay_tag)
                slo["captured_at"] = time.strftime("%Y-%m-%dT%H:%M:%S")
                print(f"slo: {json.dumps(slo['hops'])}",
                      file=sys.stderr)

        # Restart catch-up: kill -9 member 3, write under its nose,
        # restart, time until it serves the missed write.
        procs[3].kill()
        procs[3].wait(timeout=10)
        clients[3].close()
        g = next(g for g in range(args.groups) if g % MEMBERS == 0)
        clients[1].call(op="put", g=g, k="Y2F0Y2h1cA==",  # b64 "catchup"
                        v="MQ==")
        t0 = time.monotonic()
        procs[3] = spawn(3, raft_p, admin_p, data_dir, args.groups,
                         gen=1, trace=args.trace,
                         wal_pipeline=args.wal_pipeline,
                         fabric=args.fabric, shm_dir=shm_dir,
                         pin_cores=args.pin_cores,
                         apply_plane=args.apply_plane)
        clients[3] = wait_admin(("127.0.0.1", admin_p[3]), timeout=300.0)
        while time.monotonic() - t0 < 180.0:
            if clients[3].get(g, b"catchup") == b"1":
                break
            time.sleep(0.25)
        else:
            raise TimeoutError("restarted member did not catch up")
        catchup_s = time.monotonic() - t0

        result = {
            "platform": "cpu",
            "puts_per_sec": bench["puts_per_sec"],
            "p50_ms": bench["p50_ms"],
            "p99_ms": bench["p99_ms"],
            "n": bench["n"],
            "completed": bench.get("completed", bench["n"]),
            "lost": bench.get("lost", 0),
            "groups_led": bench["groups"],
            "phase_ms_per_round": phase_ms,
            "fabric": args.fabric,
            "restart_catchup_s": round(catchup_s, 1),
            "config": (f"G={args.groups} R={MEMBERS} procs={MEMBERS} "
                       f"value={args.value_size}B "
                       f"inflight={args.inflight}/group CPU "
                       f"fabric={args.fabric}"
                       + (" pinned" if args.pin_cores else "")
                       + (f" trace=1/{args.trace}" if args.trace
                          else "")
                       + (" walpipe=on" if args.wal_pipeline else "")
                       + (" applyplane=on" if args.apply_plane else "")
                       + (f" read_mix={args.read_mix:g}"
                          if args.read_mix > 0 else "")
                       + delay_tag),
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
        }
        if "reads" in bench:
            result["reads"] = bench["reads"]
        if slo is not None:
            result["slo"] = slo
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(json.dumps(result))
    finally:
        for c in clients.values():
            try:
                c.call(op="stop")
            except Exception:  # noqa: BLE001
                pass
            c.close()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


if __name__ == "__main__":
    main()
