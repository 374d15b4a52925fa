"""Bytes a node sends over the chips' interconnect, the interconnect's
peak, and the exchange's share of it.

Placed a node a chip (``MultiRaftEngine(nodes=...)``), the round's
exchange is one all-to-all a kind lane over the node axis: chip s holds
``outbox[g, t]`` for every group g of the tile and every target t,
keeps its own slot and sends each of the other R - 1 chips theirs. One
*lane run* (one lane exchanged in one tile's round: what
``eng.lane_exchanges()`` counts) therefore sends, a chip,
``tile_rows * (R - 1)`` slots of that lane's bytes, once; as many
arrive. The count is ``reduce/roofline.lane_bytes``, the one the
one-chip ``route()`` is held to, with the other nodes as the peers and
one pass: the algorithm's, from the lanes that crossed and the bytes of
a slot as the program carries it, not the padded tiles a layout
touches, not the pass that packs a boolean field for the wire. It does
no arithmetic, so the interconnect's bandwidth bounds it.

The peak is the chip's published interconnect figure, every link
together. One round's three peers sit behind different links, two of
them one hop away and one two (a 2x2 mesh), so no exchange can reach
it: the share reads low, and never over 100.
"""

from __future__ import annotations

from typing import Optional, Sequence

from .roofline import lane_bytes

# Bytes a second a chip can send over its interconnect, all links.
# Read on 2026-09-30 from Google Cloud's documentation, "TPU v5e"
# (cloud.google.com/tpu/docs/v5e, the table of chip specifications:
# "Interchip Interconnect BW: 1600 Gbps"), the page
# ``reduce/peaks.json`` cites for the chip's other peaks.
ICI_PEAKS = {
    "TPU v5 lite": {
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud documentation, 'TPU v5e': Interchip "
                  "Interconnect BW 1600 Gbps per chip",
    },
}


def ici_peak(device_kind: str) -> float:
    """A device that is not in the table is an error, never a
    default."""
    if device_kind not in ICI_PEAKS:
        raise KeyError(
            f"no interconnect peak for device kind {device_kind!r}; "
            f"known: {sorted(ICI_PEAKS)}")
    return ICI_PEAKS[device_kind]["ici_bytes_per_s"]


def sent_bytes(lane_runs: Sequence[int], tile_rows: int, num_replicas: int,
               slot_bytes: Sequence[int],
               bulk_runs: Optional[int] = None) -> int:
    """Bytes one chip sent over `lane_runs` (tile-rounds in which each
    lane crossed). A split append lane (seven slot sizes) needs
    `bulk_runs`, the tile-rounds its tail crossed in: the placed engine
    counts none today (no live placed configuration splits the lane),
    so such a run raises in ``lane_bytes`` and does not read low."""
    return lane_bytes(tile_rows, num_replicas - 1, lane_runs, slot_bytes,
                      bulk_runs, passes=1)


def roofline_pct(sent: float, seconds: float,
                 device_kind: str) -> Optional[float]:
    """Least time at the interconnect's peak over the time taken, in
    percent."""
    if seconds <= 0:
        return None
    return 100.0 * (sent / ici_peak(device_kind)) / seconds
