"""The node-placed replacement cell's plain reference
(``engine_shadow_replace_nodes``): ``reference.shadow_replace
.ReplaceCluster`` (beside this file, frozen, not edited), as it is.

A thin wrapper, as that file is over ``shadow_reconf.py``, and thinner:
it departs from it in nothing. The configuration
``engine1m-r3of4-x4`` places slot s of every group on chip s and sends
a round's messages between the chips; the reference is R plain
``RawNode``s over ``MemoryStorage`` in one Python process whose network
is a list of messages, and knows nothing of chips, placement or
collectives. That is the point: where the four chips' rows, put back in
the order ``g * R + s``, equal it in state, log, masks, read state and
the history of every round, the placement changed nothing a replica can
observe. The class has a name of its own so that a departure the
placement should ever force (none is known) has a place that is not an
edit of the one-chip cell's reference.
"""

from __future__ import annotations

from .shadow_replace import ReplaceCluster


class NodesCluster(ReplaceCluster):
    """``ReplaceCluster``; node s is whatever machine slot s lives on."""
