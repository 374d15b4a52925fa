"""Multi-raft hosting layer as real OS processes: 3 MultiRaftMember
workers wired by TCPRouter over real sockets at G=1024, driven through
the admin API — the reference's deployment shape (each peer its own
process, ref: rafthttp/transport.go:97-132, Procfile; e2e process
discipline of tests/e2e). Covers puts across groups, kill -9 and
restart of a member (WAL replay + catch-up at the hosting layer), and
records a hosted-path throughput/commit-p50 line."""

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from etcd_tpu.batched.hosting_proc import ProcClient, wait_admin

G = 1024
MEMBERS = 3

pytestmark = pytest.mark.e2e


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


def spawn(mid, raft_ports, admin_ports, data_dir, gen=0, trace=False,
          fleet=False):
    peers = [
        f"--peer={pid}=127.0.0.1:{raft_ports[pid]}"
        for pid in range(1, MEMBERS + 1) if pid != mid
    ]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # Observability dumps (flight recorder / trace ring / fleet heat)
    # land in the test's tmp dir, not the repo's artifacts/.
    env["ETCD_TPU_FLIGHTREC_DIR"] = data_dir
    if trace:
        env["ETCD_TPU_TRACE_SAMPLE"] = "1"  # trace every proposal
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    # Logs go to files: an undrained PIPE would wedge the worker once
    # the buffer fills with XLA/compile chatter.
    log = open(os.path.join(data_dir, f"worker-{mid}-gen{gen}.log"), "wb")
    return subprocess.Popen(
        [
            sys.executable, "-m", "etcd_tpu.batched.hosting_proc",
            "--id", str(mid), "--members", str(MEMBERS),
            "--groups", str(G), "--data-dir", data_dir,
            "--bind", f"127.0.0.1:{raft_ports[mid]}",
            "--admin", f"127.0.0.1:{admin_ports[mid]}",
            "--tick-interval", "0.1",
        ] + (["--trace"] if trace else [])
        + (["--fleet", "--telemetry"] if fleet else []) + peers,
        env=env,
        stdout=log,
        stderr=subprocess.STDOUT,
    )


def put_any(clients, g, k, v, timeout=30.0):
    """Client-style redirect loop: try members until the leader takes
    the proposal and the write is readable at that member."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for c in clients.values():
            try:
                r = c.put(g, k, v)
            except (OSError, ConnectionError):
                continue
            if r.get("ok"):
                sub = min(deadline, time.monotonic() + 2.0)
                while time.monotonic() < sub:
                    if c.get(g, k) == v:
                        return c
                    time.sleep(0.01)
        time.sleep(0.05)
    raise TimeoutError(f"put group {g} never committed")


def wait_all_leaders(client, timeout=120.0):
    deadline = time.monotonic() + timeout
    nudge = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        r = client.call(op="leaders")
        leads = r["leads"]
        if all(x > 0 for x in leads):
            return leads
        if time.monotonic() > nudge:
            stuck = [g for g, x in enumerate(leads) if x == 0]
            client.call(op="campaign", groups=stuck[:512])
            nudge = time.monotonic() + 5.0
        time.sleep(0.25)
    raise TimeoutError("groups without leader")


def test_hosted_bench_floor(tmp_path):
    """Run the hosted-path benchmark (3 OS processes, TCPRouter,
    G=1024, CPU) and enforce the throughput floor: an 816 -> 100
    puts/s regression must fail CI, not pass invisibly (review round
    4, weak point 2). Writes artifacts/hosted_ci_floor.json — a CI-machine
    capture, deliberately SEPARATE from the committed headline
    HOSTED_BENCH.json (review round 5, weak point 3: the headline
    number must not depend on which run happened last; captures are taken
    deliberately via `python -m etcd_tpu.tools.hosted_bench --out
    HOSTED_BENCH.json` on an idle box)."""
    import json

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = os.path.join(repo, "artifacts", "hosted_ci_floor.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # n well past the in-flight cap (4x1024) so the committed artifact
    # records STEADY-STATE throughput, consistent with the headline
    # runs of HOSTED_BENCH.json (a one-burst n measures latency instead).
    r = subprocess.run(
        [sys.executable, "-m", "etcd_tpu.tools.hosted_bench",
         "--n", "9000", "--data-dir", str(tmp_path), "--out", out],
        env=env, capture_output=True, timeout=1500, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    res = json.loads(open(out).read())
    print(f"\nhosted-path: {res['puts_per_sec']} puts/s "
          f"p50 {res['p50_ms']}ms p99 {res['p99_ms']}ms "
          f"lost {res['lost']} catchup {res['restart_catchup_s']}s")
    # Floor, not target: the bar is >=5000 aggregate on an idle box;
    # 500 guards against order-of-magnitude regressions even on a
    # heavily loaded CI machine.
    assert res["puts_per_sec"] > 500, res
    assert res["lost"] == 0, res
    assert res["restart_catchup_s"] < 150, res


def test_three_process_cluster_kill9_restart(tmp_path):
    raft_p = dict(zip(range(1, MEMBERS + 1), free_ports(MEMBERS)))
    admin_p = dict(zip(range(1, MEMBERS + 1), free_ports(MEMBERS)))
    procs = {}
    clients = {}
    try:
        # Tracing on (ISSUE 9) + fleet observatory on (ISSUE 10): this
        # test doubles as the e2e exercise of the proposal-lifecycle
        # tracer AND the fleet console across real processes, a
        # kill -9, and a restart.
        for mid in range(1, MEMBERS + 1):
            procs[mid] = spawn(mid, raft_p, admin_p, str(tmp_path),
                               trace=True, fleet=True)
        for mid in range(1, MEMBERS + 1):
            clients[mid] = wait_admin(("127.0.0.1", admin_p[mid]),
                                      timeout=180.0)

        # Balanced leadership: member m campaigns groups g % 3 == m-1.
        for mid, c in clients.items():
            c.call(op="campaign",
                   groups=[g for g in range(G) if g % MEMBERS == mid - 1])
        wait_all_leaders(clients[1])

        # Puts across the group space via redirect loop.
        sample = list(range(0, G, 97)) + [G - 1]
        for g in sample:
            put_any(clients, g, b"k", b"v%d" % g)

        # Hosted-path perf line (throughput + commit p50) on whichever
        # member leads groups — under 2-core timesharing check_quorum
        # can drain leadership off a slow member between convergence
        # and here, so the balanced split is not assumed.
        bench = None
        for c in clients.values():
            b = c.call(op="bench", n=300, value_size=64)
            if b.get("ok"):
                bench = b
                break
        assert bench, "no member leads any group"
        print(f"\nhosted-path: {bench['puts_per_sec']} puts/s over "
              f"{bench['groups']} groups, commit p50 "
              f"{bench['p50_ms']}ms p99 {bench['p99_ms']}ms")
        assert bench["puts_per_sec"] > 0

        # Admin 'trace' op (ISSUE 9): every member serves its span
        # ring inline; the cross-process merge joins them and the
        # export validates — real processes, real clock domains.
        from etcd_tpu.obs.export import validate_chrome_trace
        from etcd_tpu.obs.merge import merge as trace_merge

        payloads = []
        for mid, c in clients.items():
            tr = c.call(op="trace")
            assert tr.get("ok"), tr
            assert tr["payload"]["member"] == str(mid)
            payloads.append(tr["payload"])
        trace_obj, tstats = trace_merge(payloads)
        validate_chrome_trace(trace_obj)
        assert tstats["spans_origin"] > 0, tstats
        assert tstats["spans_peer_decomposed"] > 0, tstats

        # Fleet console --once --json against the live cluster
        # (ISSUE 10 acceptance): the CLI contract itself, via a real
        # subprocess, validated with the console's own schema check.
        import importlib.util
        import json as json_mod

        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        console_py = os.path.join(repo, "tools", "fleet_console.py")
        spec = importlib.util.spec_from_file_location(
            "fleet_console", console_py)
        fc = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(fc)
        # leaders_total is an instantaneous cross-member census: under
        # 2-core timesharing a scrape can land mid-election (an old
        # leader stepped down, the successor not yet counted), so the
        # exact-G check retries like every other convergence wait here.
        deadline = time.monotonic() + 120.0
        while True:
            r = subprocess.run(
                [sys.executable, console_py, "--once", "--json"]
                + [x for mid in clients
                   for x in ("--admin", f"127.0.0.1:{admin_p[mid]}")],
                capture_output=True, text=True, timeout=120)
            assert r.returncode == 0, (r.stdout[-2000:],
                                       r.stderr[-2000:])
            rollup = json_mod.loads(r.stdout)
            assert fc.validate_rollup(rollup) == []
            cl = rollup["cluster"]
            assert cl["members_live"] == MEMBERS
            if cl["leaders_total"] == G:
                break
            assert time.monotonic() < deadline, cl["leader_balance"]
            time.sleep(1.0)
        assert cl["invariant_trips_total"] == 0, cl
        for mid in clients:
            m = rollup["members"][str(mid)]
            assert m["frames"] > 0 and m["wal_tail"] is not None

        # The fleet heatmap ring dumps through the admin op, under the
        # shared artifact naming (member+kind keyed, collision-free).
        fdump = clients[1].call(op="fleet", dump=True,
                                reason="proc-e2e")
        assert fdump.get("ok") and "fleetheat_m1_" in fdump["path"]

        # kill -9 member 3: quorum survives, its groups re-elect.
        procs[3].kill()
        procs[3].wait(timeout=10)
        clients[3].close()
        g3 = next(g for g in sample if g % MEMBERS == 2)
        survivors = {m: c for m, c in clients.items() if m != 3}
        put_any(survivors, g3, b"after-kill", b"1", timeout=60.0)
        # A group that was led elsewhere still serves writes.
        g1 = next(g for g in sample if g % MEMBERS == 0)
        put_any(survivors, g1, b"after-kill", b"1", timeout=60.0)

        # Restart member 3 from the same data dir: WAL replay +
        # snapshot/append catch-up at the hosting layer.
        procs[3] = spawn(3, raft_p, admin_p, str(tmp_path), gen=1,
                         trace=True, fleet=True)
        clients[3] = wait_admin(("127.0.0.1", admin_p[3]), timeout=180.0)

        # Durability-fence visibility (ISSUE 5): the health op reports
        # the boot WAL-tail classification and per-group fenced state.
        # A real kill -9 of a process whose WAL batches fsync before
        # acks normally leaves a clean boundary and nothing fenced;
        # either way the op must answer and any fence must heal.
        hl = clients[3].call(op="health")
        assert hl.get("ok"), hl
        assert hl["fence_enabled"] is True
        assert hl["wal_tail"] in ("clean", "torn"), hl
        assert isinstance(hl["fenced_groups"], list)
        assert isinstance(hl["catchup_gap"], dict)

        deadline = time.monotonic() + 120.0
        want = {g: b"v%d" % g for g in sample}
        want[g3] = want[g3]  # original key still present
        while time.monotonic() < deadline:
            missing = [
                g for g in sample
                if clients[3].get(g, b"k") != want[g]
            ]
            if not missing and clients[3].get(g3, b"after-kill") == b"1":
                break
            time.sleep(0.5)
        else:
            pytest.fail(f"restarted member did not catch up: {missing}")

        # Any fence the kill armed must have healed along the catch-up.
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            hl = clients[3].call(op="health")
            if hl.get("ok") and not hl["fenced_groups"]:
                break
            time.sleep(0.5)
        else:
            pytest.fail(f"fenced groups never healed: {hl}")

        # And it participates again: a fresh write lands everywhere.
        c = put_any(clients, g3, b"after-restart", b"2", timeout=60.0)
        assert c is not None
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            if clients[3].get(g3, b"after-restart") == b"2":
                break
            time.sleep(0.25)
        else:
            pytest.fail("restarted member missed post-restart write")
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
                p.wait(timeout=5)
