"""Per-layer metrics from what a scan with a control schedule counts in
its carry (``MultiRaftEngine.scan_watch``: instance-rounds spent in a
joint configuration and with a ReadIndex batch open), as the driver
read it when the window opened and closed. A run whose driver reads no
such counts gives ``None``.

Stands beside ``readers/telemetry.py`` and is not an edit of it: that
one reads the telemetry plane's event counters, which count a round's
events and not the rounds a state lasted. The reconfiguration cell's
other metrics are that file's readers with this cell's counters
(``layer_metrics/read.confirmed_per_kgr.json`` and the like)."""

from __future__ import annotations

from typing import Optional


def _watch_moved(ctx, name: str) -> Optional[int]:
    w = ctx["raw"].get("watch")
    if not w or name not in w["after"]:
        return None
    return w["after"][name] - w["before"][name]


def joint_pct(ctx) -> Optional[float]:
    """Instance-rounds of the window spent in a joint configuration,
    over all its instance-rounds."""
    n = _watch_moved(ctx, "joint_instance_rounds")
    if n is None:
        return None
    raw = ctx["raw"]
    return 100.0 * n / (int(raw["groups"]) * int(raw["replicas"])
                        * int(raw["rounds"]))


def rounds_to_confirm(ctx) -> Optional[float]:
    """Instance-rounds with a ReadIndex batch open over batches
    confirmed, in the window: how many rounds a batch waits for its
    heartbeat quorum (2 with nothing in its way: out and back)."""
    n = _watch_moved(ctx, "read_open_instance_rounds")
    t = ctx["raw"].get("telemetry")
    if n is None or not t or "reads_confirmed" not in t["after"]:
        return None
    done = t["after"]["reads_confirmed"] - t["before"]["reads_confirmed"]
    return n / done if done else None
