"""Multi-raft hosting demo with members as real OS processes, a CPU
tool: a chip belongs to one process, so R member processes cannot share
it — every worker runs with ``JAX_PLATFORMS=cpu`` and the summary says
``"platform": "cpu"`` (the served path on the chip is
``chip_smoke.py``'s in-process cluster).

Spawns R MultiRaftMember worker processes (one per member, wired by
TCPRouter over real sockets — the reference's peers-as-processes shape,
ref: rafthttp/transport.go:97-132, Procfile), elects balanced leaders
across G groups, runs a hosted-path put benchmark, then kill -9s one
member and restarts it to demonstrate WAL replay + catch-up at the
hosting layer.

    python -m etcd_tpu.tools.multiraft_proc_demo \
        [--groups 1024] [--members 3] [--puts 500] [--no-kill]

Prints a JSON summary line.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

from ..batched.hosting_proc import ProcClient, wait_admin


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _spawn(mid, members, groups, raft_ports, admin_ports, data_dir, gen=0):
    peers = [
        f"--peer={pid}=127.0.0.1:{raft_ports[pid]}"
        for pid in range(1, members + 1) if pid != mid
    ]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # R processes cannot share a chip
    log = open(os.path.join(data_dir, f"worker-{mid}-gen{gen}.log"), "wb")
    return subprocess.Popen(
        [
            sys.executable, "-m", "etcd_tpu.batched.hosting_proc",
            "--id", str(mid), "--members", str(members),
            "--groups", str(groups), "--data-dir", data_dir,
            "--bind", f"127.0.0.1:{raft_ports[mid]}",
            "--admin", f"127.0.0.1:{admin_ports[mid]}",
            "--tick-interval", "0.02",
        ] + peers,
        env=env, stdout=log, stderr=subprocess.STDOUT,
    )


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--groups", type=int, default=1024)
    p.add_argument("--members", type=int, default=3)
    p.add_argument("--puts", type=int, default=500)
    p.add_argument("--value-size", type=int, default=64)
    p.add_argument("--data-dir", default="")
    p.add_argument("--no-kill", action="store_true",
                   help="skip the kill -9 / restart phase")
    a = p.parse_args()

    data_dir = a.data_dir or tempfile.mkdtemp(prefix="multiraft-proc-")
    R, G = a.members, a.groups
    raft_p = dict(zip(range(1, R + 1), _free_ports(R)))
    admin_p = dict(zip(range(1, R + 1), _free_ports(R)))
    procs, clients = {}, {}
    summary = {"platform": "cpu", "groups": G, "members": R,
               "data_dir": data_dir}
    try:
        t0 = time.perf_counter()
        for mid in range(1, R + 1):
            procs[mid] = _spawn(mid, R, G, raft_p, admin_p, data_dir)
        for mid in range(1, R + 1):
            clients[mid] = wait_admin(("127.0.0.1", admin_p[mid]),
                                      timeout=300.0)
        summary["boot_s"] = round(time.perf_counter() - t0, 1)

        t0 = time.perf_counter()
        for mid, c in clients.items():
            c.call(op="campaign",
                   groups=[g for g in range(G) if g % R == mid - 1])
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            r = clients[1].call(op="leaders")
            if all(x > 0 for x in r["leads"]):
                break
            stuck = [g for g, x in enumerate(r["leads"]) if x == 0]
            clients[1].call(op="campaign", groups=stuck[:512])
            time.sleep(0.5)
        else:
            raise TimeoutError("leader election did not converge")
        summary["election_s"] = round(time.perf_counter() - t0, 1)

        bench = clients[1].call(op="bench", n=a.puts,
                                value_size=a.value_size)
        summary["hosted_puts_per_sec"] = bench.get("puts_per_sec")
        summary["commit_p50_ms"] = bench.get("p50_ms")
        summary["commit_p99_ms"] = bench.get("p99_ms")
        summary["bench_groups"] = bench.get("groups")

        if not a.no_kill:
            victim = R
            procs[victim].kill()
            procs[victim].wait(timeout=10)
            clients[victim].close()
            # Survivors still serve a group the victim led.
            g = next(g for g in range(G) if g % R == victim - 1)
            t0 = time.perf_counter()
            deadline = time.monotonic() + 120
            ok = False
            while time.monotonic() < deadline and not ok:
                for c in [clients[m] for m in clients if m != victim]:
                    r = c.put(g, b"after-kill", b"1")
                    if r.get("ok"):
                        ok = True
                        break
                time.sleep(0.1)
            summary["reelect_put_s"] = round(time.perf_counter() - t0, 1)

            procs[victim] = _spawn(victim, R, G, raft_p, admin_p,
                                   data_dir, gen=1)
            clients[victim] = wait_admin(
                ("127.0.0.1", admin_p[victim]), timeout=300.0)
            t0 = time.perf_counter()
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline:
                if clients[victim].get(g, b"after-kill") == b"1":
                    break
                time.sleep(0.25)
            else:
                raise TimeoutError("restarted member did not catch up")
            summary["catchup_s"] = round(time.perf_counter() - t0, 1)
        print(json.dumps(summary))
    finally:
        for c in clients.values():
            try:
                c.call(op="stop")
            except Exception:  # noqa: BLE001
                pass
            c.close()
        for pr in procs.values():
            try:
                pr.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pr.kill()


if __name__ == "__main__":
    main()
