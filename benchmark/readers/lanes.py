"""Per-layer metric from the closed loop's lane counter
(``MultiRaftEngine.lane_rounds``: for each of the six inbox lanes, the
scan rounds in which it held a message for any instance, counted in the
scan's carry), as the driver read it when the window opened and closed.
A lane that held a message is a lane deliver folded and ``route()``
exchanged, so the sum over the lanes a round is what a round ran of the
six. A run whose driver reads no such counter gives ``None``.

Stands beside ``readers/telemetry.py`` and ``readers/reconf.py`` and is
not an edit of either: those read the telemetry plane and the
controlled scan's counts, which the append cells' configurations do not
have; every engine driver reads this one."""

from __future__ import annotations

from typing import Optional


def run_a_round(ctx) -> Optional[float]:
    """Lanes occupied a round, 0..6, over the window's rounds."""
    lanes = ctx["raw"].get("lanes")
    rounds = int(ctx["raw"].get("rounds", 0))
    if not lanes or "after" not in lanes or rounds <= 0:
        return None
    return sum(b - a for a, b in zip(lanes["before"], lanes["after"])
               ) / rounds
