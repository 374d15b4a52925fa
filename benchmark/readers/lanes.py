"""Per-layer metrics from the closed loop's occupancy counters, as the
driver read them when the window opened and closed
(``drivers/engine.occupancy``; ``raw["occupancy"]``).
A run whose driver reads no such counter gives ``None``.

``MultiRaftEngine.lane_rounds``: for each of the six inbox lanes, the
scan rounds in which it held a message for any instance, counted in the
scan's carry. A lane that held a message is a lane deliver folded and
``route()`` exchanged, so the sum over the lanes a round is what a
round ran of the six (``run_a_round``). ``rare_rounds``: the rounds in
which the heartbeat lane held a MsgTimeoutNow and the
heartbeat-response lane a MsgAppResp, on which deliver takes those
lanes' whole handlers (``rare_pct``). ``bulk_rounds``: the rounds in
which a split append lane ran at its whole width (``bulk_pct``). These
three count a round in which ANY tile of the scan took the branch.
``emit_ring_rounds`` counts TILE-rounds (a node's, over nodes) in which
emit read the log ring for the terms it states (``ring_pct``: over the
window's rounds x ``ring_tiles``). So no count here is divided by
another: each stands over the rounds of its own kind.

Stands beside ``readers/telemetry.py`` and ``readers/reconf.py`` and is
not an edit of either: those read the telemetry plane and the
controlled scan's counts, which the append cells' configurations do not
have; every engine driver reads this one."""

from __future__ import annotations

from typing import Optional


def run_a_round(ctx) -> Optional[float]:
    """Lanes occupied a round, 0..6, over the window's rounds."""
    got = _window(ctx, "lanes")
    if got is None:
        return None
    moved, rounds, _occ = got
    return moved / rounds


def _window(ctx, key: str):
    """(the counter ``key`` as it moved over the window, the window's
    rounds, the driver's ``occupancy``) or ``None``."""
    occ = ctx["raw"].get("occupancy")
    rounds = int(ctx["raw"].get("rounds", 0))
    if not occ or rounds <= 0 or not occ.get("before") or not occ.get(
            "after") or key not in occ["after"]:
        return None
    a, b = occ["before"][key], occ["after"][key]
    moved = (sum(b) - sum(a)) if isinstance(b, list) else b - a
    return moved, rounds, occ


def rare_pct(ctx) -> Optional[float]:
    """Runs of the two heartbeat lanes' whole handlers (campaign and
    MsgAppResp fold included) over the two a round could run."""
    got = _window(ctx, "rare")
    if got is None:
        return None
    moved, rounds, _occ = got
    return 100.0 * moved / (2 * rounds)


def ring_pct(ctx) -> Optional[float]:
    """Tile-rounds in which emit read the log ring for the terms its
    messages state, of the window's rounds x the scan's tiles."""
    got = _window(ctx, "ring")
    if got is None or not got[2].get("ring_tiles"):
        return None
    moved, rounds, occ = got
    return 100.0 * moved / (rounds * occ["ring_tiles"])


def bulk_pct(ctx) -> Optional[float]:
    """Rounds in which the append lane ran whole, tail and all, of the
    window's rounds; ``None`` where the lane is not split."""
    got = _window(ctx, "bulk")
    if got is None or not got[2].get("app_head"):
        return None
    moved, rounds, _occ = got
    return 100.0 * moved / rounds
