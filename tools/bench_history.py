#!/usr/bin/env python
"""Consolidate the scattered bench artifacts into one trajectory.

The repo accumulates one-off bench JSONs per PR round — ``BENCH_r*.json``
(kernel runs via bench.py), ``TPU_BENCH_r*.json`` (builder TPU
captures), ``HOSTED_BENCH.json`` + ``artifacts/hosted_*.json`` (hosted
service rate), ``MULTICHIP_r*.json`` (mesh dry-runs) — and the perf
trajectory is otherwise reconstructible only by reading BENCH_NOTES
prose. This tool scans them all and emits:

* ``artifacts/bench_history.json`` — one normalized row per artifact
  (kind, round, headline value, unit, config, captured_at, source);
* ``artifacts/BENCH_HISTORY.md`` — the trajectory as markdown tables.

Re-emitted by ``tools/check.sh``, so the history tracks the tree.
Corrections are honored: a ``<NAME>.CORRECTION.md`` next to an
artifact flags its row (a known-bad capture stays in the record,
marked as corrected, instead of silently winning the table).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional


def _load(path: str) -> Optional[Dict]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print(f"bench_history: skipping unreadable {path}: {e}",
              file=sys.stderr)
        return None


def _round_of(path: str) -> Optional[int]:
    m = re.search(r"_r0*(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else None


def _corrected(path: str) -> Optional[str]:
    base = re.sub(r"\.json$", "", path)
    corr = f"{base}.CORRECTION.md"
    return os.path.basename(corr) if os.path.exists(corr) else None


def collect(repo: str) -> List[Dict]:
    rows: List[Dict] = []

    def add(kind, path, value, unit, config="", captured_at="",
            extra=None):
        row = {
            "kind": kind,
            "round": _round_of(path),
            "source": os.path.relpath(path, repo),
            "value": value,
            "unit": unit,
            "config": config,
            "captured_at": captured_at,
        }
        corr = _corrected(path)
        if corr:
            row["corrected"] = corr
        if extra:
            row.update(extra)
        rows.append(row)

    # Kernel rate series: BENCH_r*.json wrap the parsed bench.py line;
    # TPU_BENCH_r*.json are the bare parsed object of a builder's run.
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        d = _load(path)
        if not d:
            continue
        p = d.get("parsed") or {}
        if "value" in p:
            add("kernel", path, p.get("value"), p.get("unit", ""),
                extra={"metric": p.get("metric", ""),
                       "vs_baseline": p.get("vs_baseline")})
    for path in sorted(glob.glob(os.path.join(repo,
                                              "TPU_BENCH_r*.json"))):
        d = _load(path)
        if d and "value" in d:
            add("kernel_tpu", path, d.get("value"), d.get("unit", ""),
                extra={"metric": d.get("metric", ""),
                       "vs_baseline": d.get("vs_baseline")})

    # Hosted service rate: the headline artifact plus per-run samples
    # and the CI floor under artifacts/.
    hosted = ([os.path.join(repo, "HOSTED_BENCH.json")]
              + sorted(glob.glob(os.path.join(
                  repo, "artifacts", "hosted_*.json"))))
    for path in hosted:
        d = _load(path) if os.path.exists(path) else None
        if not d or "puts_per_sec" not in d:
            continue
        extra = {"p50_ms": d.get("p50_ms"), "p99_ms": d.get("p99_ms"),
                 "lost": d.get("lost"),
                 "restart_catchup_s": d.get("restart_catchup_s")}
        # Transport provenance (ISSUE 16): hosted_shm_* rows carry the
        # fabric explicitly; older artifacts are implicitly tcp.
        if d.get("fabric"):
            extra["fabric"] = d["fabric"]
        add("hosted", path, d["puts_per_sec"], "puts/s",
            config=d.get("config", ""),
            captured_at=d.get("captured_at", ""),
            extra=extra)

    # Multi-chip dry-runs: ok/skip status per round (plus hosted-shape
    # numbers when the round captured them).
    for path in sorted(glob.glob(os.path.join(repo,
                                              "MULTICHIP_r*.json"))):
        d = _load(path)
        if not d:
            continue
        if "puts_per_sec" in d:
            add("multichip", path, d["puts_per_sec"], "puts/s",
                config=d.get("config", ""),
                captured_at=d.get("captured_at", ""))
        else:
            add("multichip", path,
                None, "",
                extra={"ok": d.get("ok"), "rc": d.get("rc"),
                       "skipped": d.get("skipped"),
                       "n_devices": d.get("n_devices")})

    # Observability overhead rows: fleet-summary A/B cells
    # (tools/fleet_overhead.py — interleaved on/off, one per G).
    path = os.path.join(repo, "artifacts", "fleet_overhead.json")
    d = _load(path) if os.path.exists(path) else None
    if d:
        for c in d.get("cells", ()):
            add("overhead_fleet", path, c.get("overhead_pct"),
                "% (off->on, interleaved)",
                config=f"G={c.get('groups')} ({d.get('platform', '')})",
                captured_at=d.get("captured_at", ""),
                extra={"off_median": c.get("off_median"),
                       "on_median": c.get("on_median")})

    rows.sort(key=lambda r: (r["kind"], r["round"] or 0, r["source"]))
    return rows


def markdown(rows: List[Dict]) -> str:
    out = ["# Bench trajectory (tools/bench_history.py)", ""]

    def fmt_val(r):
        v = r.get("value")
        if v is None:
            return f"ok={r.get('ok')} rc={r.get('rc')}"
        s = f"{v:,.1f}" if isinstance(v, (int, float)) else str(v)
        if r.get("corrected"):
            s += f" ⚠ (see {r['corrected']})"
        return s

    kernel = [r for r in rows if r["kind"].startswith("kernel")]
    if kernel:
        out += ["## Kernel (group-rounds/s)", "",
                "| round | source | value | unit/config |", "|---|---|---|---|"]
        for r in kernel:
            out.append(f"| {r['round'] if r['round'] is not None else ''} "
                       f"| {r['source']} | {fmt_val(r)} | {r['unit']} |")
        out.append("")
    hosted = [r for r in rows if r["kind"] == "hosted"]
    if hosted:
        out += ["## Hosted service rate (puts/s)", "",
                "| source | puts/s | p50 ms | p99 ms | lost | config "
                "| captured |", "|---|---|---|---|---|---|---|"]
        for r in hosted:
            out.append(
                f"| {r['source']} | {fmt_val(r)} | {r.get('p50_ms')} "
                f"| {r.get('p99_ms')} | {r.get('lost')} "
                f"| {r['config']} | {r['captured_at']} |")
        out.append("")
    mc = [r for r in rows if r["kind"] == "multichip"]
    if mc:
        out += ["## Multi-chip dry-runs", "",
                "| round | source | status |", "|---|---|---|"]
        for r in mc:
            out.append(f"| {r['round']} | {r['source']} | {fmt_val(r)} |")
        out.append("")
    ov = [r for r in rows if r["kind"].startswith("overhead_")]
    if ov:
        out += ["## Observability overhead (interleaved A/B)", "",
                "| source | overhead % | off | on | config | captured |",
                "|---|---|---|---|---|---|"]
        for r in ov:
            out.append(
                f"| {r['source']} | {fmt_val(r)} | {r.get('off_median')} "
                f"| {r.get('on_median')} | {r['config']} "
                f"| {r['captured_at']} |")
        out.append("")
    return "\n".join(out) + "\n"


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="consolidate bench artifacts into one history")
    ap.add_argument("--repo", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap.add_argument("--out-dir", default=None,
                    help="default: <repo>/artifacts")
    args = ap.parse_args(argv)
    out_dir = args.out_dir or os.path.join(args.repo, "artifacts")
    rows = collect(args.repo)
    if not rows:
        print("bench_history: no bench artifacts found", file=sys.stderr)
        return 1
    os.makedirs(out_dir, exist_ok=True)
    out_json = os.path.join(out_dir, "bench_history.json")
    with open(out_json, "w") as f:
        json.dump({"rows": rows}, f, indent=1)
        f.write("\n")
    out_md = os.path.join(out_dir, "BENCH_HISTORY.md")
    with open(out_md, "w") as f:
        f.write(markdown(rows))
    print(f"bench_history: {len(rows)} rows -> {out_json}, {out_md}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
