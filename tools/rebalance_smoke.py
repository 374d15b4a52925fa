#!/usr/bin/env python
"""Rebalance smoke for tools/check.sh (ISSUE 11): boot a tiny in-proc
3-member hosting cluster with the fleet observatory on, seed a gross
leader skew (every group's leadership transferred to member 1), then
run ``rebalancerd --once --json`` against in-process AdminServers and
require it to (a) emit a schema-valid report and (b) converge the
cluster below the skew threshold — a broken fleet signal, admin
transfer op, or rebalance policy fails the static gate, not a live
hosted run. Writes ``artifacts/rebalance_smoke.json`` (seeded-skew
shape, per-pass report, convergence wall time); lint.yml uploads it
on failure.

``--groups N`` scales the cell (default 24).
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
import tempfile
import time
from contextlib import redirect_stdout

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_TOOLS))  # repo root: etcd_tpu
sys.path.insert(0, _TOOLS)  # rebalancerd lives beside this script

os.environ.setdefault("JAX_PLATFORMS", "cpu")

R = 3
SKEW_BAR = 1.5  # rebalancerd trigger/convergence threshold


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--groups", type=int, default=24)
    p.add_argument("--out", default="artifacts/rebalance_smoke.json")
    args = p.parse_args(argv)
    g = args.groups

    from etcd_tpu.batched.hosting import MultiRaftCluster
    from etcd_tpu.batched.hosting_proc import AdminServer
    from etcd_tpu.batched.state import BatchedConfig

    import rebalancerd

    cfg = BatchedConfig(
        num_groups=g, num_replicas=R, window=16, max_ents_per_msg=4,
        max_props_per_round=4, election_timeout=10, heartbeat_timeout=1,
        pre_vote=True, check_quorum=True, auto_compact=True,
        telemetry=True, fleet_summary=True,
    )
    tmp = tempfile.mkdtemp(prefix="rebalance_smoke_")
    t_boot = time.monotonic()
    cluster = MultiRaftCluster(tmp, num_members=R, num_groups=g,
                               cfg=cfg)
    admins = []
    try:
        cluster.wait_leaders(timeout=180.0)
        m1 = cluster.members[1]

        # -- seed the skew: every leadership onto member 1 ------------
        t_skew = time.monotonic()
        deadline = t_skew + 120.0
        while time.monotonic() < deadline:
            own = sum(1 for gi in range(g) if m1.is_leader(gi))
            if own == g:
                break
            for gi in range(g):
                for m in cluster.members.values():
                    if m.id != 1 and m.is_leader(gi):
                        m.transfer_leader(gi, 1)
            time.sleep(0.2)
        else:
            print(f"rebalance smoke: seeded skew incomplete "
                  f"({own}/{g} on member 1)", file=sys.stderr)
            return 1

        # Fleet frames must reflect the skew before the daemon reads
        # them (the rollup is the daemon's ONLY input).
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            roll = m1.fleet.snapshot() if m1.fleet else {}
            if roll.get("leaders_total", 0) == g:
                break
            time.sleep(0.2)
        else:
            print("rebalance smoke: fleet rollup never showed the "
                  "seeded skew", file=sys.stderr)
            return 1

        for m in cluster.members.values():
            admins.append(AdminServer(m, cluster.router,
                                      ("127.0.0.1", 0)))
        specs = [f"{m.id}=127.0.0.1:{a.addr[1]}"
                 for m, a in zip(cluster.members.values(), admins)]

        # -- one rebalancerd pass must converge -----------------------
        t_reb = time.monotonic()
        buf = io.StringIO()
        with redirect_stdout(buf):
            # One-shot convergence needs per-pass headroom for ~2G/3
            # moves at scale; the 64-move default cap is the DAEMON's
            # per-interval churn bound, not a one-shot limit.
            rc = rebalancerd.main(
                ["--once", "--json", "--skew-ratio", str(SKEW_BAR),
                 "--max-moves", str(max(64, g))]
                + [x for s in specs for x in ("--admin", s)])
        out = buf.getvalue()
        try:
            report = json.loads(out)
        except ValueError:
            print(f"rebalance smoke: unparseable report: {out[-500:]}",
                  file=sys.stderr)
            return 1
        probs = rebalancerd.validate_report(report)
        if probs:
            print(f"rebalance smoke: invalid report: {probs}",
                  file=sys.stderr)
            return 1
        t_done = time.monotonic()
        artifact = {
            "groups": g,
            "members": R,
            "skew_bar": SKEW_BAR,
            "seed_skew_s": round(t_reb - t_skew, 3),
            "rebalance_s": round(t_done - t_reb, 3),
            "boot_s": round(t_skew - t_boot, 3),
            "report": report,
        }
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(artifact, fh, indent=1)
            fh.write("\n")
        if rc != 0 or not report["converged"]:
            print(f"rebalance smoke: did not converge "
                  f"(rc={rc}, ratio {report['ratio_before']} -> "
                  f"{report['ratio_after']}, balance "
                  f"{report['balance_after']})", file=sys.stderr)
            return 1
        if not report["triggered"] or report["moved"] == 0:
            print(f"rebalance smoke: seeded skew never triggered "
                  f"moves: {report}", file=sys.stderr)
            return 1
        print(f"rebalance smoke OK: G={g} ratio "
              f"{report['ratio_before']} -> {report['ratio_after']}, "
              f"{report['moved']} moves in {artifact['rebalance_s']}s "
              f"(balance {report['balance_after']})")
        return 0
    finally:
        for a in admins:
            a.close()
        cluster.stop()


if __name__ == "__main__":
    sys.exit(main())
