"""Bytes from shapes, the table of peaks, and a kernel's roofline share.

``route()`` is the round's sender/target exchange: every field of the
outbox ``[G*R, R, K]`` (``ent_terms`` with a trailing ``[E]``) becomes
the inbox with sender and target swapped inside each group. Since PR 25
the program does it as a pad of R-1 rows, R*R row-shifted slices
selected under ``n % R == t`` and a stack, not as a transpose; what the
exchange *must* move is the same either way, each slot read once and
written once, and that is the count here. It does no arithmetic, so HBM
bytes bound it, and its least time is those bytes over the chip's HBM
bandwidth. The bytes are the algorithm's, from shapes alone (G, R, E of
the configuration, so the count holds at R=5 as at R=3: 30.5% and 15.2%
of the roofline on the chip, PERF.md) — not the padded tiles the
compiler's layout happens to touch (ROADMAP's hand figure, "11.9 GB/s
of accessed bytes", counted those), and not the second pass the select
and the stack make over them.
"""

from __future__ import annotations

import json
import os
from typing import Optional

# One message slot, wide lanes (``narrow_lanes=False``): valid and reject
# are bool, eight words are int32 (type, term, log_term, index, commit,
# reject_hint, n_ents, ctx), and ent_terms is int32[E].
SLOT_BOOL_FIELDS = 2
SLOT_WORD_FIELDS = 8
NUM_KINDS = 6

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``. A device that is not
    in the table is an error, never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PEAKS}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def route_slots(num_groups: int, num_replicas: int) -> int:
    return num_groups * num_replicas * num_replicas * NUM_KINDS


def route_bytes(num_groups: int, num_replicas: int,
                max_ents_per_msg: int) -> int:
    """Bytes one ``route()`` call must move: each slot read once and
    written once."""
    per_slot = (SLOT_BOOL_FIELDS * 1 + SLOT_WORD_FIELDS * 4
                + max_ents_per_msg * 4)
    return 2 * per_slot * route_slots(num_groups, num_replicas)


def roofline_pct(needed_bytes: float, seconds: float,
                 device_kind: str) -> Optional[float]:
    """Least time at the HBM peak over the time taken, in percent."""
    if seconds <= 0:
        return None
    least = needed_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
