"""Per-layer metrics from the reduced device trace (``reduce/trace.py``).
A run without a trace, or a trace without the program a reader looks
for, gives ``None``."""

from __future__ import annotations

from typing import Optional

from ..harness import say
from ..reduce import roofline
from ..reduce.trace import scope_share_pct


def _module(ctx, module: str):
    red = ctx.get("trace")
    if not red:
        return None
    hits = [m for name, m in red["modules"].items() if module in name]
    if not hits:
        return None
    return {"count": sum(m["count"] for m in hits),
            "seconds": sum(m["seconds"] for m in hits)}


def module_ms(ctx, module: str, rounds_key: Optional[str] = None
              ) -> Optional[float]:
    """Device ms of one execution of the program whose name holds
    ``module``; with ``rounds_key`` (a key of the traffic file), per
    round of a program that scans that many."""
    m = _module(ctx, module)
    if m is None or m["count"] == 0:
        return None
    rounds = int(ctx["traffic"][rounds_key]) if rounds_key else 1
    return 1e3 * m["seconds"] / (m["count"] * rounds)


def scope_pct(ctx, scope: str) -> Optional[float]:
    red = ctx.get("trace")
    return scope_share_pct(red, scope) if red else None


def traced_runs(ctx):
    """(how often each of the six lanes ran in the traced calls, how
    often the append lane's tail did, the driver's ``occupancy``), from
    the reading the driver took after the traced calls less the one at
    the window's close; ``None`` where no driver read them, or where
    the calls between the two readings are not the calls traced."""
    occ = ctx["raw"].get("occupancy")
    traced = int(ctx["raw"].get("traced_calls", 0))
    if not occ or not occ.get("traced") or traced <= 0:
        return None
    a, b = occ["after"], occ["traced"]
    if b["calls"] - a["calls"] != traced:
        return None
    runs = [y - x for x, y in zip(a["lanes"], b["lanes"])]
    return runs, b["bulk"] - a["bulk"], occ


def route_roofline_pct(ctx, module: str, rounds_key: str
                       ) -> Optional[float]:
    """The bytes the lanes that ran in the traced calls must move
    (``reduce/roofline.lane_bytes``) over the HBM peak, over the
    seconds the trace holds under ``raft_route``."""
    red = ctx.get("trace")
    m = _module(ctx, module)
    got = traced_runs(ctx)
    if (not red or m is None or got is None
            or "raft_route" not in red["scope_s"]):
        return None
    runs, bulk, occ = got
    rounds = m["count"] * int(ctx["traffic"][rounds_key])
    need = roofline.lane_bytes(occ["rows"], occ["replicas"], runs,
                               occ["slot_bytes"], bulk)
    secs = red["scope_s"]["raft_route"]
    say("roofline", kernel="route", bound_by="HBM bytes (no arithmetic)",
        bytes_needed=need, seconds=secs, rounds=rounds, lane_runs=runs,
        bulk_runs=bulk, slot_bytes=occ["slot_bytes"], rows=occ["rows"],
        replicas=occ["replicas"], achieved_GBps=need / secs / 1e9,
        peak_GBps=roofline.peaks(ctx["device"]["kind"])[
            "hbm_bytes_per_s"] / 1e9)
    return roofline.roofline_pct(need, secs, ctx["device"]["kind"])


def call_gap_ms(ctx, module: str, rounds_key: str) -> Optional[float]:
    """Host wall of a call less the device time of its rounds."""
    per_round = module_ms(ctx, module, rounds_key)
    med = ctx["raw"].get("call_s_median")
    if per_round is None or med is None:
        return None
    return max(0.0, med * 1e3
               - per_round * int(ctx["traffic"][rounds_key]))
