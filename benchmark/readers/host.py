"""Per-layer metrics from the program's host-clock counters and from the
harness's own clock. Each reader takes the run's context and returns a
number, or ``None`` where the run holds nothing for it to read."""

from __future__ import annotations

import statistics
from typing import Optional


def _served(ctx):
    c = ctx["raw"].get("counters")
    if not c or not c.get("before") or not c.get("after"):
        return None
    return c


def _acked(ctx) -> int:
    return int(ctx["raw"]["attempted"]) - int(ctx["raw"]["failed"])


def client_busy_pct(ctx) -> Optional[float]:
    cpu = ctx["raw"].get("client_cpu_s")
    if cpu is None:
        return None
    return 100.0 * cpu / ctx["raw"]["window_s"]


def member_ratio(ctx, num: str, den: str, scale: float = 1.0
                 ) -> Optional[float]:
    """Median over the members of delta(num) / delta(den) in the window."""
    c = _served(ctx)
    if c is None:
        return None
    vals = []
    for a, b in zip(c["before"]["members"], c["after"]["members"]):
        d = b[den] - a[den]
        if d > 0:
            vals.append(scale * (b[num] - a[num]) / d)
    return statistics.median(vals) if vals else None


def ops_per_round(ctx) -> Optional[float]:
    c = _served(ctx)
    if c is None:
        return None
    rounds = max(b["rounds"] - a["rounds"] for a, b in zip(
        c["before"]["members"], c["after"]["members"]))
    return _acked(ctx) / rounds if rounds > 0 else None


def fsyncs_per_op(ctx) -> Optional[float]:
    c = _served(ctx)
    if c is None or _acked(ctx) <= 0:
        return None
    n = sum(b[0] - a[0] for a, b in zip(c["before"]["wal_sync"],
                                        c["after"]["wal_sync"]))
    return n / _acked(ctx)


def fsync_ms(ctx) -> Optional[float]:
    """The WAL's own count and nanoseconds (``wal.sync_stats()``)."""
    c = _served(ctx)
    if c is None:
        return None
    vals = []
    for a, b in zip(c["before"]["wal_sync"], c["after"]["wal_sync"]):
        if b[0] > a[0]:
            vals.append((b[1] - a[1]) / 1e6 / (b[0] - a[0]))
    return statistics.median(vals) if vals else None


def rawnode_host_pct(ctx) -> Optional[float]:
    """(stage + extract + collect) over the whole of ``advance_round``,
    from ``rn.phase_last`` sampled through the window."""
    c = _served(ctx)
    if c is None or not c["phase_samples"]:
        return None
    host = sum(s[0] + s[2] + s[3] for s in c["phase_samples"])
    total = sum(sum(s) for s in c["phase_samples"])
    return 100.0 * host / total if total > 0 else None


def fabric_lost(ctx) -> Optional[float]:
    c = _served(ctx)
    if c is None:
        return None

    def total(stats) -> int:
        return sum(n for per in stats.values() for n in per.values())

    return float(total(c["after"]["router"]) - total(c["before"]["router"]))


def compile_count(ctx, key: str) -> Optional[float]:
    return float(ctx["compile"][key])


def hbm_peak_gb(ctx) -> Optional[float]:
    peak = ctx.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
