"""The reconfiguration cell's plain reference:
``reference.shadow_faults.FaultsCluster`` (beside this file, frozen, over
the frozen ``reference.shadow.ShadowCluster`` and ``reference/raft/``;
none of them edited) with a control plane: leadership transfers,
ReadIndex reads and configuration changes, each through ``RawNode``'s
own entry point (``transfer_leader``, ``read_index`` and the
``ReadStates`` it yields, ``propose_conf_change``, ``apply_conf_change``
at each node's own apply point).

``ReconfCluster.round(control=row)`` takes what one round asks (the
generator's ``row``). Every departure from ``FaultsCluster``:

* **A control phase between tick and propose**, where the device has
  one (``step._control``), in its order, node by node: a node applies
  every configuration change among the entries its commit has reached
  since it last looked (``apply_conf_change``; the changes are real
  ``EntryConfChangeV2`` entries of its log); a leader on the drained
  node is asked to hand over (``transfer_leader``; a follower's forward
  has no lane and is not made); a read is asked of whoever leads; the
  change on offer is proposed to whoever leads off the drained node.
  The frozen round has no such phase: it is hooked where the propose
  phase reads its proposals, after the frozen round's deliver and tick.
* **One ReadIndex batch a leader at a time.** The device holds one
  batch an instance and a request that finds one in flight waits for
  it (etcd's ``linearizableReadLoop`` keeps one ReadIndex in flight a
  member and serves every waiting read from it). Here ``read_index`` is
  called, with the batch's number as its context, only when a leader
  that has committed in its term has no batch in flight; the
  ``ReadState`` raft yields for that context confirms it; ``raft.reset``
  (a term or a role gone) forgets it, as it forgets raft's own read
  queue. ``read_state()`` is the device's three lanes: batches opened,
  the index of the last, whether it is confirmed.
* **An offer is idempotent.** It stands round after round, so it is
  proposed only by a leader that would append it: none pending
  (``pending_conf_index <= applied``), no hand-over in flight, room in
  the ring as for a proposal, and a change that fits and would change
  something (demote a voter or promote a learner outside a joint
  configuration, leave inside one). Upstream appends an empty entry in
  place of a refused change; nothing is appended here.
* **A heartbeat is stamped where it leaves**: the read batch then open
  and ``min(match, commit)`` as then held, as the device's emit stamps
  it (the control phase may have opened a batch since the tick queued
  the heartbeat). ``MsgTimeoutNow`` shares the heartbeat lane and
  supersedes that peer's heartbeat.
* **Of two appends to one peer with a gap between them the probe
  leaves** (a commit broadcast sent ahead in REPLICATE, then the healed
  peer's rejection in the same deliver takes ``next`` back): the device
  holds one send flag a peer and slices at emit from the ``next`` it
  has then. And a probe whose duplicate the full lane refused (a
  heartbeat response cleared ``probe_sent`` after the probe left) ends
  the round waiting on that probe, as the device does.
* **A snapshot carries the configuration as of its index**, as
  upstream's does: the frozen round compacts with no ConfState, which
  keeps the bootstrap's. No sound run of this cell sends a snapshot
  (``correct`` asserts it: the device's carries none yet); under a
  control the reference may, and has to stay inside the protocol for
  the comparison, and not a crash, to catch the control.
* **A history** (``history()``): each replica's state after every
  round, folded into 32 bits by the rule of the engine's
  ``ScanWatch.history`` (``HISTORY_FIELDS``, FNV-1a; copied here, the
  yardstick imports nothing of the program, and pinned to it by a
  test), so that the comparison sees a commit that ran ahead for a few
  rounds and was caught up with.
"""

from __future__ import annotations

from typing import Iterable, List, Optional, Tuple

from .raft.raft import StateType
from .raft.read_only import ReadOnlyOption
from .raft.tracker import ProgressStateType
from .raft.types import (ConfChangeSingle, ConfChangeTransition,
                         ConfChangeType, ConfChangeV2, EntryType, Message,
                         MessageType)
from .shadow import ShadowCluster
from .shadow_faults import FaultsCluster, _Offer

DEMOTE, LEAVE, PROMOTE = "demote", "leave", "promote"
HISTORY_FIELDS = ("term", "role", "lead", "commit", "last", "read_seq",
                  "read_index", "read_ready", "in_joint", "voter",
                  "voter_out", "learner")
_FNV = 16777619


def history_fold(h: int, values) -> int:
    for v in values:
        h = ((h ^ (int(v) & 0xFFFFFFFF)) * _FNV) & 0xFFFFFFFF
    return h


def conf_change(kind: str, node: int) -> ConfChangeV2:
    """The ConfChangeV2 of a row's ``conf``; ``node`` is a slot."""
    if kind == LEAVE:
        return ConfChangeV2()
    how = {DEMOTE: ConfChangeType.ConfChangeAddLearnerNode,
           PROMOTE: ConfChangeType.ConfChangeAddNode}[kind]
    return ConfChangeV2(
        transition=ConfChangeTransition.ConfChangeTransitionJointExplicit,
        changes=[ConfChangeSingle(type=how, node_id=node + 1)])


class _ReadView:
    def __init__(self) -> None:
        self.seq, self.index, self.ready = 0, -1, False


class _Phase(_Offer):
    """``proposals`` for the frozen round: where the propose phase
    reads it, the control phase runs first."""

    def items(self):
        self.cluster.control_phase()
        return super().items()


class ReconfCluster(FaultsCluster):
    def __init__(self, num_replicas: int, *, reads_without_quorum: bool = False,
                 **kw) -> None:
        super().__init__(num_replicas, **kw)
        self.reads = [_ReadView() for _ in self.nodes]
        self.conf_applied_to = [0] * num_replicas
        self.conf_applied = [0] * num_replicas
        self.hist = [0] * num_replicas
        # Per node, (index, ConfState) of every configuration it has
        # held, for its storage's snapshots.
        self.confs = [[(0, n.raft.raft_log.storage._snapshot.metadata
                        .conf_state)] for n in self.nodes]
        self.row: Optional[dict] = None
        for slot, node in enumerate(self.nodes):
            self._wrap(node, self.reads[slot], self.confs[slot])
            if reads_without_quorum:
                # The control: a read is confirmed at once, on the
                # leader's word alone.
                node.raft.read_only.option = (
                    ReadOnlyOption.ReadOnlyLeaseBased)

    def _wrap(self, node, view: _ReadView, confs: list) -> None:
        r = node.raft
        reset, ready = r.reset, node.ready

        def reset_and_forget(term):
            view.index, view.ready = -1, False
            reset(term)

        def ready_as_the_lanes_carry_it():
            rd = ready()
            rd.messages[:] = self._one_a_lane(rd.messages)
            return rd

        # A snapshot carries the configuration as of its index, as
        # upstream's does (the frozen round compacts with none, which
        # keeps the bootstrap's): no sound run of this cell sends one,
        # a control's reference may.
        storage = r.raft_log.storage
        create = storage.create_snapshot

        def create_snapshot(i, cs, data):
            as_of = [c for at, c in confs if at <= i][-1]
            return create(i, as_of if cs is None else cs, data)

        storage.create_snapshot = create_snapshot
        r.reset = reset_and_forget
        node.ready = ready_as_the_lanes_carry_it

    @staticmethod
    def _one_a_lane(msgs: List[Message]) -> List[Message]:
        """What the frozen emit cannot coalesce: a heartbeat beside a
        ``MsgTimeoutNow`` to the same peer goes, and of two appends to
        one peer with a gap between them the later-indexed goes."""
        ton = {m.to for m in msgs if m.type == MessageType.MsgTimeoutNow}
        apps = {}
        for m in msgs:
            if m.type == MessageType.MsgApp:
                apps.setdefault(m.to, []).append(m)
        dropped = set()
        for sent in apps.values():
            low = min(sent, key=lambda m: m.index)
            for m in sent:
                if m is not low and m.term == low.term and (
                        low.index + len(low.entries) < m.index):
                    dropped.add(id(m))
        return [m for m in msgs
                if id(m) not in dropped
                and not (m.type == MessageType.MsgHeartbeat and m.to in ton)]

    def _one_lane_a_round(self, r) -> None:
        # FaultsCluster's, and where the full lane refuses a probe's
        # duplicate the round ends waiting on the probe that left.
        super()._one_lane_a_round(r)
        send = r.maybe_send_append

        def maybe_send_append(to, send_if_empty):
            pr = r.prs.progress[to]
            if (self.lane_ents - r.lane_sent.get(to, 0) <= 0
                    and pr.state == ProgressStateType.StateProbe):
                pr.probe_sent = True
            return send(to, send_if_empty)

        r.maybe_send_append = maybe_send_append

    # -- one round ----------------------------------------------------------------

    def round(self, offer: int = 0, tick: bool = False,
              isolate: Iterable[int] = (), campaigns=(),
              control: Optional[dict] = None) -> None:
        self.row = control
        for node in self.nodes:
            node.raft.lane_sent.clear()
        ShadowCluster.round(self, campaigns=campaigns, tick=tick,
                            isolate=isolate, proposals=_Phase(self, offer))
        for target in self.inbox:
            for lanes in target:
                for m in lanes:
                    if m is not None and m.type == MessageType.MsgApp:
                        del m.entries[self.lane_ents:]
        if control is not None:
            self._fold_history()

    def control_phase(self) -> None:
        row = self.row
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
                self._offer_conf(slot, *row["conf"])

    def _leads(self, slot: int) -> bool:
        return self.nodes[slot].raft.state == StateType.StateLeader

    def _apply_conf_changes(self, slot: int) -> None:
        node = self.nodes[slot]
        log = node.raft.raft_log
        lo, hi = self.conf_applied_to[slot], log.committed
        if hi <= lo:
            return
        lo = max(lo, log.first_index() - 1)
        for e in log.slice(lo + 1, hi + 1, 1 << 62):
            if e.type == EntryType.EntryConfChangeV2:
                cs = node.apply_conf_change(ConfChangeV2.unmarshal(e.data))
                self.confs[slot].append((e.index, cs))
                self.conf_applied[slot] += 1
        self.conf_applied_to[slot] = hi

    def _confirmed(self, slot: int) -> bool:
        ctx = str(self.reads[slot].seq).encode()
        return any(rs.request_ctx == ctx
                   for rs in self.nodes[slot].raft.read_states)

    def _read(self, slot: int, asked: bool) -> None:
        node, view = self.nodes[slot], self.reads[slot]
        r = node.raft
        if self._confirmed(slot):
            view.ready = True
        in_flight = view.index >= 0 and not view.ready
        if not (asked and self._leads(slot) and not in_flight
                and r.committed_entry_in_current_term()):
            return
        view.seq += 1
        view.index, view.ready = r.raft_log.committed, False
        node.read_index(str(view.seq).encode())
        if self._confirmed(slot):
            view.ready = True  # a quorum of one, or the control

    def _offer_conf(self, slot: int, kind: str, who: int) -> None:
        node = self.nodes[slot]
        r = node.raft
        cfg = r.prs.config
        joint = bool(cfg.voters.outgoing)
        if kind == LEAVE:
            fits = joint
        elif kind == DEMOTE:
            fits = not joint and who + 1 in cfg.voters.incoming
        else:
            fits = not joint and who + 1 in cfg.learners
        held = r.raft_log.last_index() - (
            r.raft_log.storage.first_index() - 1)
        if (self._leads(slot) and not r.lead_transferee
                and r.id in r.prs.progress and fits
                and r.pending_conf_index <= r.raft_log.applied
                and self.window - held - self.max_props > 0):
            node.propose_conf_change(conf_change(kind, who))

    def _rematerialize(self, node, m: Message) -> Message:
        r = node.raft
        if (m.type == MessageType.MsgHeartbeat and m.term == r.term
                and r.state == StateType.StateLeader):
            return Message(
                type=m.type, to=m.to, from_=m.from_, term=m.term,
                commit=min(r.prs.progress[m.to].match,
                           r.raft_log.committed),
                context=r.read_only.last_pending_request_ctx())
        return super()._rematerialize(node, m)

    # -- what the comparison reads ------------------------------------------------------

    def membership(self) -> List[Tuple]:
        """(voters, outgoing voters, learners, learners next) per
        replica, as sorted tuples of slots: each replica's own view."""
        out = []
        for node in self.nodes:
            c = node.raft.prs.config
            out.append(tuple(
                tuple(sorted(i - 1 for i in ids))
                for ids in (c.voters.incoming, c.voters.outgoing,
                            c.learners, c.learners_next)))
        return out

    def read_state(self) -> List[Tuple[int, int, bool]]:
        return [(v.seq, v.index, v.ready) for v in self.reads]

    def _fold_history(self) -> None:
        bits = lambda ids: sum(1 << (i - 1) for i in ids)  # noqa: E731
        for slot, node in enumerate(self.nodes):
            r, view = node.raft, self.reads[slot]
            c = r.prs.config
            self.hist[slot] = history_fold(self.hist[slot], (
                r.term, int(r.state), r.lead, r.raft_log.committed,
                r.raft_log.last_index(), view.seq, view.index, view.ready,
                bool(c.voters.outgoing), bits(c.voters.incoming),
                bits(c.voters.outgoing), bits(c.learners)))

    def history(self) -> List[int]:
        return list(self.hist)
