"""Bytes the occupied lanes must move, the table of peaks, and a kernel's
roofline share.

The round's exchange hands every message slot a replica wrote to the
replica it is addressed to: the outbox ``[rows, R]`` of each of the six
kind lanes becomes the inbox with sender and target swapped inside each
group. On one chip that is ``route()`` (a pad, R*R row-shifted slices
selected under ``n % R == t`` and a stack, lane by lane, a lane that
held no message left as it was: ``step.route_lanes``); placed a node a
chip it is one all-to-all a lane over the chips' interconnect.
Either way it does no arithmetic, so bytes bound it: on one chip each
slot of an occupied lane is read once and written once from HBM,
between chips each slot addressed to another node is sent once.

``lane_bytes`` is that count and the only one (``readers/trace.py``
and, through ``reduce/roofline_ici.py``, ``readers/nodes.py`` use it):
the rows of the exchange, the peers a row addresses, how often each
lane ran (the engine's ``lane_rounds()`` / ``lane_exchanges()`` over
the calls that were timed, ``bulk_rounds()`` for a split append lane's
tail) and the bytes of one slot of each lane as the program carries it
(``etcd_tpu.batched.step.lane_slot_bytes``, read by the driver and said
on the ``[bench:roofline]`` line). It is what the occupied lanes must
move whatever implements the exchange: not the padded tiles a layout
touches, not the second pass a select and a stack make, and not the
lanes that held nothing.
"""

from __future__ import annotations

import json
import os
from typing import Optional, Sequence

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


def lane_bytes(rows: int, peers: int, runs: Sequence[int],
               slot_bytes: Sequence[int], bulk_runs: Optional[int] = None,
               passes: int = 2) -> int:
    """Bytes an exchange of ``rows`` rows must move over ``runs`` (how
    often each of the six lanes ran): a lane run moves ``rows * peers``
    slots of that lane's ``slot_bytes``, ``passes`` times (2 on one
    chip: read once, written once; 1 between chips: sent once). Where
    the append lane is split ``slot_bytes`` has a seventh number, the
    tail's bytes, which moved in ``bulk_runs`` of the append lane's
    runs: a caller that states a tail and not how often it ran is
    refused, since the count would leave the tail out and read low."""
    if len(runs) != NUM_KINDS or len(slot_bytes) not in (NUM_KINDS,
                                                         NUM_KINDS + 1):
        raise ValueError(
            f"{len(runs)} lanes' runs and {len(slot_bytes)} slot sizes: "
            f"the exchange has {NUM_KINDS} lanes, and a tail at the most")
    a_slot_row = sum(int(n) * int(b) for n, b in zip(runs, slot_bytes))
    if len(slot_bytes) > NUM_KINDS:
        if bulk_runs is None:
            raise ValueError(
                "the append lane is split (a seventh slot size) and no "
                "count of the tail's runs was given")
        a_slot_row += int(bulk_runs) * int(slot_bytes[NUM_KINDS])
    return passes * rows * peers * a_slot_row


def roofline_pct(needed_bytes: float, seconds: float,
                 device_kind: str) -> Optional[float]:
    """Least time at the HBM peak over the time taken, in percent."""
    if seconds <= 0:
        return None
    least = needed_bytes / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds
