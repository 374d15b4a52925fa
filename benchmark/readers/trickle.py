"""Per-layer metrics of a node decommissioned range by range: the
groups in motion, from the telemetry plane's per-instance totals and
the scan's count of slots reset as the driver read both when the window
opened and closed (``drivers/engine_trickle.py``), and the share of
the traced rounds under ``raft_phase``, the scope of the work a phased
control schedule adds to the scan (each row's own round of the cycle
and what it asks there). A run whose driver reads no such counts, or
whose program has no such scope (every other cell's, and the parent
program's), gives ``None``.

Stands beside ``readers/replace.py``, ``readers/telemetry.py`` and
``readers/trace.py`` and is not an edit of any; the cell's other three
entries are those files' readers as they are
(``layer_metrics/trickle.*.json`` name them): a move is a swap, and
what carried it and how long it took to catch up are counted as the
lockstep cell counts them.
"""

from __future__ import annotations

from typing import Optional

from ..reduce.trace import scope_share_pct


def in_motion_pct(ctx) -> Optional[float]:
    """Groups between their learner's change applied and their old
    slot reset, of all groups: read as the window opened and as it
    closed (both on a call's first round, where seven of the eight
    batches in flight are past their learner's round) and averaged."""
    t = ctx["raw"].get("trickle")
    if not t:
        return None
    return 100.0 * (t["in_motion_open"] + t["in_motion_close"]) / (
        2.0 * int(ctx["raw"]["groups"]))


def committed_pct(ctx) -> Optional[float]:
    """Entries offered in the window that were committed in it, of the
    entries offered: the groups' commit indexes moved by so much, less
    the entries nobody offered (a configuration change, a new leader's
    empty entry; the driver counts both from the telemetry plane). A
    steady group's pipeline is as deep when the window closes as when
    it opened and reads 100; what falls short is what a leader asked
    to hand over did not append."""
    t = ctx["raw"].get("trickle")
    n = ctx["raw"].get("entries_committed")
    if not t or n is None or not t.get("offered"):
        return None
    return 100.0 * (n - t["unoffered_committed"]) / t["offered"]


def phase_pct(ctx) -> Optional[float]:
    """Share of the traced device time under ``raft_phase``."""
    red = ctx.get("trace")
    return scope_share_pct(red, "raft_phase") if red else None
