"""The trickle cell's plain reference (``engine_shadow_trickle``):
``reference.shadow_replace.ReplaceCluster`` (beside this file, frozen,
not edited) stepped on *one group's own rows*.

The deployment moves a few groups at a time; a group that is moved runs
the replacement cell's cycle from its own start round and is steady
before and after. The reference is one group: R plain ``RawNode``s in
one Python process. It knows nothing of batches, phases, tiles or the
device, and is told nothing of any other group: ``TrickleCluster``
takes the generator's ``row`` and the group's start and, round after
round, asks the generator what *this group* is asked in that round
(``row(load, rnd - start)``: the cycle's row, or the steady row outside
it). Where the device's row of the batch, which reads the cycle at its
own group's round of it, equals this in state, log, masks, read state
and the history of every round, the phased schedule changed nothing a
replica can observe.
"""

from __future__ import annotations

from .shadow_replace import ReplaceCluster


class TrickleCluster(ReplaceCluster):
    def __init__(self, num_replicas: int, *, start: int, row, load,
                 **kw) -> None:
        super().__init__(num_replicas, **kw)
        self.start, self._row, self._load = int(start), row, load
        self.rounds = 0  # of the schedule, stepped so far

    def schedule_round(self, offer: int, tick: bool) -> None:
        """The next round of the schedule, as this group sees it."""
        row = self._row(self._load, self.rounds - self.start)
        self.rounds += 1
        self.round(offer=offer, tick=tick,
                   isolate=[s for s in (row["cut"], row["retired"])
                            if s is not None],
                   control=row)
