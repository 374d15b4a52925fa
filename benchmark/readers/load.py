"""Per-layer metrics of a load plane (``MultiRaftEngine.run_rounds(
load=...)``): what the scans offered, from the counts they keep in their
carry (``load_counts``) and the telemetry plane's totals as the driver
read both when the window opened and closed (``drivers/engine_load.py``:
``raw["load"]``), and the share of the traced rounds under
``raft_load``, the scope of the draws. A run whose driver reads no such
counts, or whose program has no such scope (every other cell's, and the
parent program's), gives ``None``.

Stands beside ``readers/trickle.py``, ``readers/telemetry.py`` and
``readers/reconf.py`` and is not an edit of any; the cell's sixth entry,
``load.read_rounds_to_confirm``, is ``readers/reconf.py``'s
``rounds_to_confirm`` as it is, under this cell's name.
"""

from __future__ import annotations

from typing import Optional

from ..reduce.trace import scope_share_pct


def _load(ctx) -> Optional[dict]:
    return ctx["raw"].get("load") or None


def _group_rounds(ctx) -> int:
    return int(ctx["raw"]["groups"]) * int(ctx["raw"]["rounds"])


def active_pct(ctx) -> Optional[float]:
    """Group-rounds of the window in which the group was offered an
    update or asked a read, of all its group-rounds: what a design that
    steps only busy groups would still have to run."""
    t = _load(ctx)
    return None if not t else 100.0 * t["active"] / _group_rounds(ctx)


def committed_per_kgr(ctx) -> Optional[float]:
    """Entries committed in the window per 1,000 group-rounds of it."""
    n = ctx["raw"].get("entries_committed")
    if not _load(ctx) or n is None:
        return None
    return 1e3 * n / _group_rounds(ctx)


def committed_pct(ctx) -> Optional[float]:
    """Updates offered in the window that were committed in it, of the
    updates offered: the groups' commit indexes moved by so much, less
    the entries nobody offered (a new leader's empty entry) and less
    the entries that stood appended and uncommitted as the window
    opened (offered before it). What falls short of 100 is what was
    refused and what the last rounds' offers had not committed yet as
    the window closed."""
    t = _load(ctx)
    n = ctx["raw"].get("entries_committed")
    if not t or n is None or not t["offered"]:
        return None
    return 100.0 * (n - t["unoffered_committed"] - t["uncommitted_open"]) / (
        t["offered"])


def dropped_pct(ctx) -> Optional[float]:
    """Updates offered in the window that the group's leader did not
    append, of the updates offered: the telemetry plane's
    ``proposals_dropped`` over the window less what the followers' rows
    count of it (every replica is offered what its group is, and only
    a leader appends: R - 1 of every R)."""
    t = _load(ctx)
    if not t or not t["offered"]:
        return None
    followers = (int(ctx["raw"]["replicas"]) - 1) * t["offered"]
    return 100.0 * (t["dropped"] - followers) / t["offered"]


def load_pct(ctx) -> Optional[float]:
    """Share of the traced device time under ``raft_load``."""
    red = ctx.get("trace")
    return scope_share_pct(red, "raft_load") if red else None
