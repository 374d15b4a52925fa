"""Per-layer metrics of a deployment placed a node a chip
(``drivers/engine_nodes.py``): the exchange over the chips'
interconnect and what the chips agree on, from the reduced device trace
(the scopes ``raft_ici`` and ``raft_agree``) and from the engine's count
of lanes that crossed (``MultiRaftEngine.lane_exchanges``, read by the
driver as each call ended: ``raw["ici"]``).

A run of another driver has no ``raw["ici"]`` and its trace neither
scope, and a program without node placement runs no such cell: every
reader then gives ``None`` and the metric is left out.
"""

from __future__ import annotations

from typing import List, Optional

from ..harness import say
from ..reduce import roofline_ici
from ..reduce.trace import scope_share_pct

ICI, AGREE = "raft_ici", "raft_agree"


def _share(ctx, scope: str) -> Optional[float]:
    red = ctx.get("trace")
    return scope_share_pct(red, scope) if red else None


def exchange_pct(ctx) -> Optional[float]:
    """Share of leaf device time in the all-to-alls (and the wipes)."""
    return _share(ctx, ICI)


def agree_pct(ctx) -> Optional[float]:
    """Share of leaf device time in the occupancy's all-reduce and the
    ScanWatch's reduction over a group."""
    return _share(ctx, AGREE)


def _runs(ctx, lo: int, hi: int) -> Optional[List[int]]:
    """Lane runs by lane between the ends of calls `lo` and `hi`."""
    ici = ctx["raw"].get("ici")
    if not ici or not 0 <= lo < hi < len(ici["after_call"]):
        return None
    a, b = ici["after_call"][lo], ici["after_call"][hi]
    return [y - x for x, y in zip(a, b)]


def _window(ctx):
    ici = ctx["raw"].get("ici")
    if not ici:
        return None, 0
    runs = _runs(ctx, ici["open"], ici["close"])
    rounds = (ici["close"] - ici["open"]) * int(
        ctx["raw"].get("rounds_per_call", 0))
    return (runs, rounds) if runs and rounds > 0 else (None, 0)


def lanes_run(ctx) -> Optional[float]:
    """Lanes exchanged between the chips a round, 0..6, over the
    window's rounds and the tiles a chip's rows run in."""
    runs, rounds = _window(ctx)
    if runs is None:
        return None
    return sum(runs) / (rounds * ctx["raw"]["ici"]["tiles"])


def mb_per_round(ctx) -> Optional[float]:
    """MB a chip sends a round over the window (as many arrive)."""
    runs, rounds = _window(ctx)
    if runs is None:
        return None
    ici = ctx["raw"]["ici"]
    return roofline_ici.sent_bytes(
        runs, ici["tile_rows"], ici["replicas"], ici["slot_bytes"]
    ) / rounds / 1e6


def roofline_pct(ctx) -> Optional[float]:
    """The bytes a chip sent in the traced calls over the
    interconnect's peak, over the seconds a chip spent under
    ``raft_ici`` in the trace."""
    red = ctx.get("trace")
    ici = ctx["raw"].get("ici")
    traced = int(ctx["raw"].get("traced_calls", 0))
    if not red or not ici or traced <= 0 or ICI not in red["scope_s"]:
        return None
    last = len(ici["after_call"]) - 1
    runs = _runs(ctx, last - traced, last)
    if runs is None:
        return None
    sent = roofline_ici.sent_bytes(
        runs, ici["tile_rows"], ici["replicas"], ici["slot_bytes"])
    secs = red["scope_s"][ICI]
    kind = ctx["device"]["kind"]
    say("roofline", kernel="ici exchange",
        bound_by="interconnect bytes (no arithmetic)", bytes_sent=sent,
        seconds=secs, lane_runs=runs, slot_bytes=ici["slot_bytes"],
        tile_rows=ici["tile_rows"], replicas=ici["replicas"],
        achieved_GBps=sent / secs / 1e9,
        peak_GBps=roofline_ici.ici_peak(kind) / 1e9)
    return roofline_ici.roofline_pct(sent, secs, kind)
