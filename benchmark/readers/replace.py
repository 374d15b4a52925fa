"""Per-layer metrics of a rolling node replacement, from what a scan
with a control schedule counts in its carry for a configuration with
``replace_replicas`` (``MultiRaftEngine.scan_watch``: swaps taken,
instance-rounds a learner spent short of REPLICATE) and from the
telemetry plane's totals, as the
driver read both when the window opened and closed. A run whose driver
reads no such counts (every other cell's, and the parent program's)
gives ``None``.

Stands beside ``readers/reconf.py`` and ``readers/telemetry.py`` and is
not an edit of either; ``replace.joint_pct`` and
``replace.committed_pct`` are those files' readers as they are
(``layer_metrics/replace.*.json`` name them)."""

from __future__ import annotations

from typing import Optional


def _watch_moved(ctx, name: str) -> Optional[int]:
    w = ctx["raw"].get("watch")
    if not w or name not in w["after"] or name not in w["before"]:
        return None
    return w["after"][name] - w["before"][name]


def _group_rounds(ctx) -> int:
    return int(ctx["raw"]["groups"]) * int(ctx["raw"]["rounds"])


def snapshots_per_swap(ctx) -> Optional[float]:
    """Snapshots sent in the window over swaps taken in it: what it
    took to carry a new replica. 1 when catch-up works (the snapshot
    is taken at the applied index, half a ring ahead of the floor);
    where the floor runs away from a replica a snapshot has carried
    (ROADMAP D12) it is sent another every other round and this reads
    in the tens."""
    swaps = _watch_moved(ctx, "swaps_taken")
    t = ctx["raw"].get("telemetry")
    if not swaps or not t or "sent_snapshot" not in t["after"]:
        return None
    return (t["after"]["sent_snapshot"] - t["before"]["sent_snapshot"]) / swaps


def catchup_rounds(ctx) -> Optional[float]:
    """Rounds a new replica spent a learner short of REPLICATE in its
    leader's row (probed, rejected, sent its snapshot, answering), a
    replacement: from the learner's change applied to REPLICATE."""
    swaps = _watch_moved(ctx, "swaps_taken")
    short = _watch_moved(ctx, "learner_rounds_short_of_replicate")
    if not swaps or short is None:
        return None
    return short / swaps


def swapped_per_kgr(ctx) -> Optional[float]:
    """Swaps taken per 1,000 group-rounds of the window: one a group a
    period of 128 rounds is 7.8125."""
    swaps = _watch_moved(ctx, "swaps_taken")
    return None if swaps is None else 1e3 * swaps / _group_rounds(ctx)
