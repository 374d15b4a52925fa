"""The replacement cell's plain reference:
``reference.shadow_reconf.ReconfCluster`` (beside this file, frozen, over
the frozen ``FaultsCluster``, ``ShadowCluster`` and ``reference/raft/``;
none of them edited) with replicas that are born and retired: plain
``RawNode``s over ``MemoryStorage``, a node replaced by a fresh
``RawNode`` over empty storage at the wipe, ``propose_conf_change`` with
the one-op and the two-op ``ConfChangeV2``, ``apply_conf_change`` at
each node's own apply point, snapshots whose metadata carries the
``ConfState`` and ``raft.restore`` rebuilding the tracker from it.

``ReplaceCluster.round(control=row)`` takes what one round asks (the
generator's ``row``). Every departure from ``ReconfCluster``:

* **A spare slot.** ``spare`` names the slot that starts empty: its
  node is a ``RawNode`` over a fresh ``MemoryStorage`` (no ConfState, no
  log, term 0), and the other nodes bootstrap with the three seated
  voters. The frozen constructor seats all R; the nodes are rebuilt
  here, by its own recipe.
* **The wipe** (``row["wipe"]``), as the control phase begins: the
  slot's node is replaced by a fresh one, and what this class keeps of
  the slot (its read view, how far it has applied) starts over; its
  history goes on, as the device's does. ``row["retired"]`` is a node
  switched off: cut off both ways like ``row["cut"]``.
* **Two kinds of change more.** ``add_learner`` is the simple change
  {AddLearnerNode e}, proposed where e is in nobody's tracker; ``swap``
  is {JointExplicit, AddNode e, RemoveNode d}, proposed only by a leader
  whose ``Progress`` for e is ``StateReplicate``: the stand-in for
  etcd's ``isLearnerReady`` (``assumed`` in the configuration's file).
* **A snapshot is taken at the applied index and states the
  configuration** (``create_snapshot(applied, ConfState)``), as etcd
  takes its own (it snapshots at the applied index and compacts
  ``SnapshotCatchUpEntries`` behind it), and **compaction is this
  class's**: the frozen round's (``min(commit, last - W/2)``, a
  snapshot at the floor with no ConfState) is switched off. The floor
  is ``min(applied, last - W/2)``, computed where the device computes
  it, at the top of emit: after every node's Ready is persisted, once a
  round.
* **Messages to a row this round's apply point deleted do not leave**
  (a heartbeat the tick queued for a peer LeaveJoint then removed): the
  device's emit sends to the rows it has when the messages leave.
* **A peer sent a snapshot in an append's place waits on it**: where
  the re-slice at the end of the round finds the append's previous
  index below the floor and sends the snapshot, the leader's
  ``Progress`` becomes ``StateSnapshot`` as the device's row does.

``restore_without_confstate`` is a control, the parent program's
``_handle_snapshot`` ("membership masks are taken to be current"): a
node that restores a snapshot takes its log and keeps the configuration
it has, which for a fresh replica is none. It follows and acknowledges
like any, so its leader finds it ready and swaps it in, but it never
learns that it is a member: a change it then cannot apply to the
configuration it holds is skipped (the parent's masks would take
whatever the flip gives), it neither campaigns nor honours a hand-over,
and its masks, and so its history, are not the program's.
"""

from __future__ import annotations

from typing import Iterable, List, Optional

from .raft import Config, MemoryStorage, RawNode
from .raft.raft import StateType
from .raft.tracker import ProgressStateType
from .raft.types import (ConfChangeSingle, ConfChangeTransition,
                         ConfChangeType, ConfChangeV2, ConfState, EntryType,
                         Message, MessageType)
from .shadow import DeviceHashRand
from .shadow_reconf import ReconfCluster, _ReadView

ADD_LEARNER, SWAP, LEAVE = "add_learner", "swap", "leave"


def conf_change(kind: str, node: Optional[int],
                node2: Optional[int]) -> ConfChangeV2:
    """The ConfChangeV2 of a row's ``conf``; the nodes are slots."""
    if kind == LEAVE:
        return ConfChangeV2()
    if kind == ADD_LEARNER:
        return ConfChangeV2(changes=[ConfChangeSingle(
            type=ConfChangeType.ConfChangeAddLearnerNode, node_id=node + 1)])
    return ConfChangeV2(
        transition=ConfChangeTransition.ConfChangeTransitionJointExplicit,
        changes=[
            ConfChangeSingle(type=ConfChangeType.ConfChangeAddNode,
                             node_id=node + 1),
            ConfChangeSingle(type=ConfChangeType.ConfChangeRemoveNode,
                             node_id=node2 + 1)])


class ReplaceCluster(ReconfCluster):
    def __init__(self, num_replicas: int, *, spare: int,
                 restore_without_confstate: bool = False, **kw) -> None:
        super().__init__(num_replicas, **kw)
        self.kw = kw
        self.no_confstate = restore_without_confstate
        # Compaction is this class's (`_compact_all`).
        self.auto_compact_window = 0
        seated = ConfState(voters=[s + 1 for s in range(num_replicas)
                                   if s != spare])
        self._snapshot_raw = [None] * num_replicas
        self._compacted = True
        for slot in range(num_replicas):
            self._seat(slot, None if slot == spare else seated)

    def _seat(self, slot: int, conf_state: Optional[ConfState]) -> None:
        """A node for the slot by the frozen constructors' recipe, over
        storage that holds `conf_state` and nothing else (None: empty
        storage, a fresh replica)."""
        kw = self.kw
        storage = MemoryStorage()
        if conf_state is not None:
            storage._snapshot.metadata.conf_state = conf_state
        node = RawNode(Config(
            id=slot + 1,
            election_tick=kw["election_timeout"],
            heartbeat_tick=kw["heartbeat_timeout"],
            storage=storage,
            max_size_per_msg=1 << 62,
            max_inflight_msgs=kw["max_inflight"],
            pre_vote=kw["pre_vote"],
            rand=DeviceHashRand(kw["group"] * self.r + slot)))
        node.raft.check_quorum = True
        self.nodes[slot] = node
        self.reads[slot] = _ReadView()
        self.conf_applied_to[slot] = 0
        self.confs[slot] = [(0, storage._snapshot.metadata.conf_state)]
        self._one_lane_a_round(node.raft)
        self._snapshot_raw[slot] = storage.create_snapshot
        self._wrap(node, self.reads[slot], self.confs[slot])
        ready = node.ready

        def ready_to_the_rows_there_are():
            rd = ready()
            r = node.raft
            if r.state == StateType.StateLeader:
                rd.messages[:] = [
                    m for m in rd.messages
                    if m.term != r.term or m.to in r.prs.progress]
            return rd

        node.ready = ready_to_the_rows_there_are
        if self.no_confstate:
            node.raft.restore = lambda s: self._restore_the_log_alone(
                node.raft, s)

    @staticmethod
    def _restore_the_log_alone(r, s) -> bool:
        """The control: ``raft.restore`` with the ConfState left out."""
        if s.metadata.index <= r.raft_log.committed:
            return False
        if r.raft_log.match_term(s.metadata.index, s.metadata.term):
            r.raft_log.commit_to(s.metadata.index)
            return False
        r.raft_log.restore(s)
        return True

    def _apply_conf_changes(self, slot: int) -> None:
        if not self.no_confstate:
            return super()._apply_conf_changes(slot)
        try:
            super()._apply_conf_changes(slot)
        except Exception:
            # The control: a change that does not fit the configuration
            # this node kept is skipped, with the rest of the span.
            self.conf_applied_to[slot] = (
                self.nodes[slot].raft.raft_log.committed)

    # -- one round ----------------------------------------------------------------

    def round(self, offer: int = 0, tick: bool = False,
              isolate: Iterable[int] = (), campaigns=(),
              control: Optional[dict] = None) -> None:
        self._compacted = False
        super().round(offer=offer, tick=tick, isolate=isolate,
                      campaigns=campaigns, control=control)
        self._compact_all()  # a round in which no message left

    def control_phase(self) -> None:
        row = self.row
        if row is not None and row.get("wipe") is not None:
            self._seat(row["wipe"], None)
        for slot in range(self.r):
            self._apply_conf_changes(slot)
        if row is None:
            return
        drained = row["drained"]
        if drained is not None and self._leads(drained):
            self.nodes[drained].transfer_leader(row["transfer_to"] + 1)
        for slot in range(self.r):
            self._read(slot, row["reads"])
            if row["conf"] is not None and slot != drained:
                self._offer(slot, *row["conf"])

    def _offer(self, slot: int, kind: str, who, whom) -> None:
        node = self.nodes[slot]
        r = node.raft
        cfg = r.prs.config
        joint = bool(cfg.voters.outgoing)
        if kind == LEAVE:
            fits = joint
        elif kind == ADD_LEARNER:
            fits = not joint and who + 1 not in r.prs.progress
        else:
            fits = (not joint and who + 1 in cfg.learners
                    and whom + 1 in cfg.voters.incoming
                    and r.prs.progress[who + 1].state
                    == ProgressStateType.StateReplicate)
        held = r.raft_log.last_index() - (
            r.raft_log.storage.first_index() - 1)
        if (self._leads(slot) and not r.lead_transferee
                and r.id in r.prs.progress and fits
                and r.pending_conf_index <= r.raft_log.applied
                and self.window - held - self.max_props > 0):
            node.propose_conf_change(conf_change(kind, who, whom))

    # -- compaction, where the messages leave ---------------------------------------

    def _applied(self, slot: int) -> int:
        """The commit index, or the entry before a configuration change
        the node has yet to apply."""
        log = self.nodes[slot].raft.raft_log
        lo = max(self.conf_applied_to[slot], log.first_index() - 1)
        if log.committed > lo:
            for e in log.slice(lo + 1, log.committed + 1, 1 << 62):
                if e.type == EntryType.EntryConfChangeV2:
                    return e.index - 1
        return log.committed

    def _compact_all(self) -> None:
        if self._compacted:
            return
        self._compacted = True
        for slot, node in enumerate(self.nodes):
            r = node.raft
            st = r.raft_log.storage
            applied = self._applied(slot)
            target = min(applied, st.last_index() - self.window // 2)
            if applied > st._snapshot.metadata.index:
                self._snapshot_raw[slot](applied, r.prs.conf_state(), b"")
            if target > st.first_index() - 1:
                st.compact(target)

    def _rematerialize(self, node, m: Message) -> Message:
        self._compact_all()
        r = node.raft
        if (m.type == MessageType.MsgApp and m.term == r.term
                and r.state == StateType.StateLeader
                and m.index < r.raft_log.storage.first_index() - 1):
            # The re-slice sends the snapshot in the append's place.
            r.prs.progress[m.to].become_snapshot(
                r.raft_log.storage.snapshot().metadata.index)
        return super()._rematerialize(node, m)
