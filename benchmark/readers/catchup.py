"""Per-layer metrics of the deep-log cell: what a replica's catch-up by
appends cost, from the counts the engine's scan carries for a
configuration with ``log_runs`` (``MultiRaftEngine.catchup_counts``:
``engine.CATCHUP_NAMES``), the telemetry plane's totals and the
replicas that returned in the window (the schedule's heals x groups),
as ``drivers/engine_catchup.py`` read them when the window opened and
closed. A run whose driver hands none of it over (any other cell; a
program without the field) gives ``None``.

Stands beside ``readers/telemetry.py`` and ``readers/replace.py`` and is
not an edit of either: those know neither the counts nor a return.
"""

from __future__ import annotations

from typing import Optional


def _moved(marks: Optional[dict], name: str) -> Optional[int]:
    if not marks or name not in marks.get("after", {}):
        return None
    return int(marks["after"][name]) - int(marks["before"][name])


def _returned(ctx) -> int:
    return int(ctx["raw"].get("replicas_returned") or 0)


def per_return(ctx, count: str) -> Optional[float]:
    """A catch-up count of the window over the replicas that returned
    in it (``behind_rounds``: the rounds a returned replica stood more
    than E below its group's commit)."""
    n = _moved(ctx["raw"].get("catchup"), count)
    return None if n is None or not _returned(ctx) else n / _returned(ctx)


def telemetry_per_return(ctx, counter: str) -> Optional[float]:
    """A telemetry counter of the window over the replicas that
    returned in it (``append_rejected``: 1-2 where the reject hint
    works, thousands where it does not; ``sent_snapshot``: 0)."""
    n = _moved(ctx["raw"].get("telemetry"), counter)
    if n is None or "catchup" not in ctx["raw"] or not _returned(ctx):
        return None
    return n / _returned(ctx)


def ents_per_app(ctx) -> Optional[float]:
    """Entries an append to a peer not yet level carried, of E."""
    ents = _moved(ctx["raw"].get("catchup"), "catchup_entries")
    apps = _moved(ctx["raw"].get("catchup"), "catchup_appends")
    return None if ents is None or not apps else ents / apps


def depth_entries(ctx) -> Optional[float]:
    """Entries the median leader held above its floor as the window
    closed: the window's half (5,120) unless the run table gave depth
    away."""
    return ctx["raw"].get("log_depth_entries")
