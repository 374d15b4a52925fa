"""Per-layer metrics from the kernel telemetry plane's totals
(``BatchedConfig.telemetry``: counters accumulated inside the scan,
``etcd_tpu/batched/telemetry.TM_NAMES``), as the driver read them when
the window opened and closed, and from the commits at the same two
points. A run whose driver snapshots no telemetry gives ``None``.

Stands beside ``readers/host.py`` and ``readers/spans.py`` and is not an
edit of either: those read the host's counters and the program's spans,
and neither knows the device's counter plane."""

from __future__ import annotations

from typing import Optional, Sequence


def _moved(ctx, names: Sequence[str]) -> Optional[int]:
    t = ctx["raw"].get("telemetry")
    if not t or any(n not in t["after"] for n in names):
        return None
    return sum(t["after"][n] - t["before"][n] for n in names)


def _group_rounds(ctx) -> int:
    return int(ctx["raw"]["groups"]) * int(ctx["raw"]["rounds"])


def per_kgr(ctx, counters: Sequence[str]) -> Optional[float]:
    """Events of the window per 1,000 group-rounds of it."""
    n = _moved(ctx, counters)
    return None if n is None else 1e3 * n / _group_rounds(ctx)


def share_pct(ctx, of: Sequence[str], among: Sequence[str]
              ) -> Optional[float]:
    """Events ``of`` as a share of the events ``among`` in the window
    (``among=["sent_*"]``: every message emitted)."""
    t = ctx["raw"].get("telemetry")
    if not t:
        return None
    if list(among) == ["sent_*"]:
        among = [n for n in t["after"] if n.startswith("sent_")]
    num, den = _moved(ctx, of), _moved(ctx, among)
    if num is None or not den:
        return None
    return 100.0 * num / den


def committed_pct(ctx) -> Optional[float]:
    """Entries committed in the window over entries offered in it."""
    n = ctx["raw"].get("entries_committed")
    if n is None:
        return None
    return 100.0 * n / (_group_rounds(ctx)
                        * int(ctx["raw"]["proposals_per_round"]))
