"""The fault cells' plain reference: ``reference.shadow.ShadowCluster``
(beside this file, frozen, not edited) under etcd's server defaults and
a closed loop that offers proposals to every replica.

``FaultsCluster`` is that cluster of plain ``RawNode``s with what
``engine100k-r3`` adds; ``reference/raft/`` and ``RawNode`` are as they
were. Every departure from ``ShadowCluster``'s network emulation:

* **CheckQuorum** is on in every node (``raft.check_quorum``, the field
  ``Config.check_quorum`` sets; the frozen constructor does not pass it).
* **Proposals follow leadership.** ``round(offer=n)`` hands ``n``
  proposals, when the round's propose phase is reached, to whichever
  node then leads and is not handing leadership over. A follower's
  forwarded ``MsgProp`` has no lane on the device and is never emitted.
  The leader takes what the engine's admission control admits: no more
  than ``max_props``, and no more than the ring's headroom, ``window``
  less the entries it holds above its snapshot less ``max_props``
  (``step._propose``; etcd's ``MaxUncommittedEntriesSize`` plays this
  part upstream). A leader cut off from its quorum stops appending
  there, as the device's does.
* **One append lane a peer a round, of at most ``max_ents`` entries**
  (etcd's ``MaxSizePerMsg``, counted in entries). ``ShadowCluster``
  raises where an append passes the cap or two appends to one peer do
  not merge within it. Here the *sender* is held to the lane: its log
  fetch is capped to the room left in the peer's lane this round and a
  send to a full lane is refused like one to a paused peer, so its
  ``Progress`` tracks what the lane carries. Entries that
  ``_rematerialize`` adds past the cap (this round's proposals on an
  append queued before them) are dropped from the lane as the device's
  emit drops them (``n_send = min(last - prev, E)``).
* **A snapshot is the one of the end of the round.** The device
  compacts at the top of emit and sends the floor it has then; the
  frozen emulation re-slices appends at the end of the round but sends
  a ``MsgSnap`` as queued mid-deliver, one compaction behind. Here a
  leader's ``MsgSnap`` carries, and its ``Progress`` waits on, the
  snapshot its storage holds when the round's messages leave.
* **An append whose previous index lies past its sender's own log
  leaves as raft queued it.** Only a control gets there (votes granted
  to a log that is behind make a leader that learns of a peer's longer
  committed log and sets ``next`` past its own last index; raft then
  sends an append with no entries). The frozen re-slice raises on the
  negative length, which would show the emulation failing and not the
  comparison catching the entries such a leader rewrites.
"""

from __future__ import annotations

from typing import Iterable

from .raft.raft import StateType
from .raft.types import Message, MessageType
from .shadow import ShadowCluster


class _Offer(dict):
    """``proposals`` for ``ShadowCluster.round``, filled in when the
    propose phase reads it: after this round's deliver and tick."""

    def __init__(self, cluster: "FaultsCluster", n: int) -> None:
        super().__init__()
        self.cluster, self.n = cluster, n

    def __bool__(self) -> bool:
        return True

    def items(self):
        c = self.cluster
        return [(slot, c.admitted(slot, self.n)) for slot in range(c.r)]


class FaultsCluster(ShadowCluster):
    def __init__(self, num_replicas: int, *, window: int, max_ents: int,
                 max_props: int, **kw) -> None:
        # The frozen class is given no cap: it would raise where this
        # one holds the sender to the lane.
        super().__init__(num_replicas, auto_compact_window=window,
                         max_ents=None, **kw)
        self.window = window
        self.lane_ents = max_ents
        self.max_props = max_props
        for node in self.nodes:
            node.raft.check_quorum = True
            self._one_lane_a_round(node.raft)

    def _one_lane_a_round(self, r) -> None:
        r.lane_sent = {}  # peer id -> entries sent this round
        room = [self.lane_ents]
        fetch, send = r.raft_log.entries, r.maybe_send_append

        def entries(i, max_size):
            return fetch(i, max_size)[:room[0]]

        def maybe_send_append(to, send_if_empty):
            room[0] = self.lane_ents - r.lane_sent.get(to, 0)
            if room[0] <= 0:
                return False
            sent = send(to, send_if_empty)
            if sent and r.msgs[-1].type == MessageType.MsgApp:
                r.lane_sent[to] = (r.lane_sent.get(to, 0)
                                   + len(r.msgs[-1].entries))
            return sent

        r.raft_log.entries = entries
        r.maybe_send_append = maybe_send_append

    def admitted(self, slot: int, n: int) -> int:
        r = self.nodes[slot].raft
        if r.state != StateType.StateLeader or r.lead_transferee:
            return 0
        held = r.raft_log.last_index() - (
            r.raft_log.storage.first_index() - 1)
        return min(n, self.max_props,
                   max(self.window - held - self.max_props, 0))

    def round(self, offer: int = 0, tick: bool = False,
              isolate: Iterable[int] = (), campaigns=()) -> None:
        for node in self.nodes:
            node.raft.lane_sent.clear()
        super().round(campaigns=campaigns, tick=tick, isolate=isolate,
                      proposals=_Offer(self, offer) if offer else None)
        for target in self.inbox:
            for lanes in target:
                for m in lanes:
                    if m is not None and m.type == MessageType.MsgApp:
                        del m.entries[self.lane_ents:]

    def _rematerialize(self, node, m: Message) -> Message:
        r = node.raft
        if (m.type == MessageType.MsgSnap and m.term == r.term
                and r.state == StateType.StateLeader):
            snap = r.raft_log.storage.snapshot()
            pr = r.prs.progress[m.to]
            if pr.pending_snapshot == m.snapshot.metadata.index:
                pr.pending_snapshot = snap.metadata.index
            return Message(type=MessageType.MsgSnap, to=m.to,
                           from_=m.from_, term=m.term, snapshot=snap)
        if (m.type == MessageType.MsgApp
                and m.index > r.raft_log.last_index()):
            # The sender believes this peer holds more than it holds
            # itself. No sound run gets here (a leader's ``next`` never
            # passes its own last + 1): a control does, whose leader won
            # on a log that was behind. raft queued the append with no
            # entries and there is nothing to re-slice; the frozen
            # re-slice would raise on the negative length.
            return m
        return super()._rematerialize(node, m)
