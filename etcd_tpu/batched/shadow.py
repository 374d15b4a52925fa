"""Host shadow cluster: the single-group oracle driven under the batched
engine's round/slot network semantics, for lockstep differential testing.

The batched engine's network delivers at most one message of each KIND
per (sender, target) pair per round and processes inbox slots in a fixed
(sender, kind) order. This adapter runs R reference-semantics RawNodes
(etcd_tpu.raft) under exactly those rules so that, for schedules within
the common feature envelope (explicit campaigns, leader-side proposals,
heartbeat ticks, full-instance partitions; no timer elections), the
device state must match the oracle state field-for-field after every
round. Schedules that would overflow a slot (two same-kind messages to
one target in one round) raise, keeping the comparison honest.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..raft import Config, MemoryStorage, RawNode
from ..raft.errors import RaftError
from ..raft.tracker import ProgressStateType
from ..raft.types import (
    ConfChangeSingle,
    ConfChangeTransition,
    ConfChangeType,
    ConfChangeV2,
    ConfState,
    EntryType,
    Message,
    MessageType,
)
from .state import (CONF_ADD_LEARNER, CONF_DEMOTE, CONF_LEAVE, CONF_PROMOTE,
                    CONF_SWAP, conf_decode)
from .step import (
    KIND_APP,
    KIND_APP_RESP,
    KIND_HB,
    KIND_HB_RESP,
    KIND_VOTE,
    KIND_VOTE_RESP,
    NUM_KINDS,
)

# Kind lanes, matching step.py's inbox layout.
_TYPE_TO_KIND = {
    MessageType.MsgVote: KIND_VOTE,
    MessageType.MsgPreVote: KIND_VOTE,
    MessageType.MsgApp: KIND_APP,
    MessageType.MsgSnap: KIND_APP,
    MessageType.MsgHeartbeat: KIND_HB,
    MessageType.MsgTimeoutNow: KIND_HB,
    MessageType.MsgVoteResp: KIND_VOTE_RESP,
    MessageType.MsgPreVoteResp: KIND_VOTE_RESP,
    MessageType.MsgAppResp: KIND_APP_RESP,
    MessageType.MsgHeartbeatResp: KIND_HB_RESP,
}


def _same_message(a: Message, b: Message) -> bool:
    return (
        a.type == b.type and a.term == b.term and a.log_term == b.log_term
        and a.index == b.index and a.commit == b.commit
        and a.reject == b.reject and a.reject_hint == b.reject_hint
        and [(e.index, e.term) for e in a.entries]
        == [(e.index, e.term) for e in b.entries]
    )


def _merge_apps(a: Message, b: Message) -> Optional[Message]:
    """Coalesce two same-round MsgApps to one target the way the
    device's single send flag does (one append per peer per round
    carrying the union): contiguous, same-term appends merge, of two
    with a gap between them the probe stays; anything else is a real
    envelope violation (returns None).

    The oracle legitimately emits two — commit-advance bcastAppend plus
    the proposal bcastAppend in the same Ready (raft.go maybeCommit →
    bcastAppend; appendEntry → bcastAppend)."""
    if a.type != MessageType.MsgApp or b.type != MessageType.MsgApp:
        return None
    if a.term != b.term:
        return None
    first, second = (a, b) if a.index <= b.index else (b, a)
    end1 = first.index + len(first.entries)
    end2 = second.index + len(second.entries)
    if end1 < second.index:
        # A gap: the later-indexed one was sent ahead in REPLICATE and a
        # rejection handled after it took `next` back (a commit
        # broadcast, then the healed peer's reject, in one deliver).
        # The device holds one send flag a peer and slices at emit from
        # the `next` it has then, so only the probe leaves.
        return Message(
            type=MessageType.MsgApp, to=first.to, from_=first.from_,
            term=first.term, log_term=first.log_term, index=first.index,
            entries=list(first.entries), commit=max(a.commit, b.commit))
    if end1 >= end2:
        # first covers second entirely (re-materialized sends overlap)
        return Message(
            type=MessageType.MsgApp, to=first.to, from_=first.from_,
            term=first.term, log_term=first.log_term, index=first.index,
            entries=list(first.entries), commit=max(a.commit, b.commit))
    take = end1 - second.index  # overlap length to skip in second
    return Message(
        type=MessageType.MsgApp, to=a.to, from_=a.from_, term=a.term,
        log_term=first.log_term, index=first.index,
        entries=list(first.entries) + list(second.entries[take:]),
        commit=max(a.commit, b.commit),
    )


class DeviceHashRand:
    """Replays the device's deterministic randomized-timeout hash
    (step.py _rand_timeout) through the host Config's ``rand`` seam:
    call n (0-based; init's randomize is call 0, matching device
    reset_count 0) returns ((iid+1)*7919 + n*104729) % et. With this,
    timer-driven elections fire on identical rounds in both engines —
    the risky masked path VERDICT r1 flagged as never differentially
    checked."""

    def __init__(self, iid: int):
        self.iid = iid
        self.n = 0

    def randrange(self, et: int) -> int:
        out = ((self.iid + 1) * 7919 + self.n * 104729) % et
        self.n += 1
        return out


def conf_change(code: int) -> ConfChangeV2:
    """The ConfChangeV2 a device ``state.conf_code`` stands for (the
    wide kinds too: a narrow code decodes as itself)."""
    kind, slot, slot2 = conf_decode(code)
    node, node2 = slot + 1, slot2 + 1
    if kind == CONF_LEAVE:
        return ConfChangeV2()
    if kind == CONF_ADD_LEARNER:  # a simple change: no joint configuration
        return ConfChangeV2(changes=[ConfChangeSingle(
            type=ConfChangeType.ConfChangeAddLearnerNode, node_id=node)])
    explicit = ConfChangeTransition.ConfChangeTransitionJointExplicit
    if kind == CONF_SWAP:
        return ConfChangeV2(transition=explicit, changes=[
            ConfChangeSingle(type=ConfChangeType.ConfChangeAddNode,
                             node_id=node),
            ConfChangeSingle(type=ConfChangeType.ConfChangeRemoveNode,
                             node_id=node2)])
    how = {CONF_DEMOTE: ConfChangeType.ConfChangeAddLearnerNode,
           CONF_PROMOTE: ConfChangeType.ConfChangeAddNode}[kind]
    return ConfChangeV2(
        transition=explicit,
        changes=[ConfChangeSingle(type=how, node_id=node)])


class _ReadView:
    """What the device's read lanes hold of one replica (read_seq,
    read_index, read_ready): one batch at a time, a request that finds
    one in flight waits for it (step._control; `waiting` is the
    device's ``read_req_latch``: a request asked in one round and not
    in the next still opens the batch after the one in flight)."""

    def __init__(self) -> None:
        self.seq, self.index, self.ready = 0, -1, False
        self.waiting = False

    def reset(self) -> None:
        self.index, self.ready, self.waiting = -1, False, False


class ShadowCluster:
    def __init__(
        self,
        num_replicas: int,
        election_timeout: int = 1 << 20,
        heartbeat_timeout: int = 1,
        max_inflight: int = 1 << 20,
        pre_vote: bool = False,
        check_quorum: bool = False,
        learners: Sequence[int] = (),
        group: int = 0,
        deterministic_timeouts: bool = False,
        auto_compact_window: int = 0,
        max_ents: Optional[int] = None,
        deliver_shape: str = "auto",
        max_props: int = 0,
        spare: Optional[int] = None,
        replace: bool = False,
    ):
        # `spare` and `replace` are the device's
        # ``BatchedConfig.replace_replicas`` (with init_state's
        # `spare`): slot `spare` starts as a RawNode over empty storage
        # that no configuration names; a snapshot is taken at the
        # applied index and states the configuration.
        # The delivery order. The program has one, "vectorized" (step.py
        # _deliver_vectorized; "auto" names it too, as in BatchedConfig):
        # see _deliver_vectorized_target below. "lanes" (kind-major,
        # senders ascending) and "merged" (sender-major within the
        # request and response halves) are orders no program runs any
        # more; they stay until ROADMAP D1b because tests/benchmark
        # steps this oracle against its frozen copy in each.
        if deliver_shape == "auto":
            deliver_shape = "vectorized"
        self.deliver_shape = deliver_shape
        self.r = num_replicas
        self.replace = replace
        lrn = {s + 1 for s in learners}
        seated = ConfState(
            voters=[i for i in range(1, num_replicas + 1)
                    if i not in lrn and i - 1 != spare],
            learners=sorted(lrn))

        def fresh_node(slot: int, conf_state=None) -> RawNode:
            storage = MemoryStorage()
            # Bootstrap the full-voter config the way the batched engine
            # does: membership is initial state, not replayed conf
            # changes. A fresh replica (the spare slot, a wiped one)
            # has none: its storage is empty.
            if conf_state is not None:
                storage._snapshot.metadata.conf_state = conf_state
            return RawNode(Config(
                id=slot + 1,
                election_tick=election_timeout,
                heartbeat_tick=heartbeat_timeout,
                storage=storage,
                max_size_per_msg=1 << 62,
                max_inflight_msgs=max_inflight,
                pre_vote=pre_vote,
                check_quorum=check_quorum,
                rand=(DeviceHashRand(group * num_replicas + slot)
                      if deterministic_timeouts else None),
            ))

        self._fresh_node = fresh_node
        self.nodes: List[RawNode] = [
            fresh_node(slot, None if slot == spare else seated)
            for slot in range(num_replicas)]
        # Slot numbers are reused by fresh replicas where upstream
        # gives a new member a new id: the votes each slot has cast,
        # term -> for whom, over every replica it has held (`round`
        # raises if a slot ever votes for two in one term).
        self.votes_cast: List[Dict[int, int]] = [
            {} for _ in range(num_replicas)]
        self.auto_compact_window = auto_compact_window
        # Device per-message entry cap E: etcd's MaxSizePerMsg counted
        # in entries. The sender's own log fetch is capped, so its
        # progress tracks what was sent (no truncation in the network).
        self.max_ents = max_ents
        # Device per-round proposal cap P: with the ring's W it bounds
        # what a leader admits of an `offer` (see `_admitted`).
        self.max_props = max_props
        # The control plane's view of each replica (round(reads=...,
        # conf=...)): its one read batch, which dies with raft.reset()
        # as the device's does, and how far it has applied the
        # configuration changes of its log.
        self.reads = [_ReadView() for _ in self.nodes]
        self.conf_applied_to = [0] * num_replicas
        self.conf_applied = [0] * num_replicas  # changes applied, counted
        for slot in range(num_replicas):
            self._wire(slot)
        # inbox[target][sender][kind]
        self.inbox: List[List[List[Optional[Message]]]] = self._empty_inbox()

    def _wire(self, slot: int) -> None:
        """What the emulation hangs on a node's raft, for the node the
        slot holds now."""
        r = self.nodes[slot].raft
        if self.max_ents is not None:
            self._one_lane_a_round(r)
        self._reset_kills_the_read(r, self.reads[slot])

    def _wipe(self, slot: int) -> None:
        """The device's replica reset (step._control's `wipe`): the
        slot's machine is gone and a fresh process over empty storage
        has its place."""
        self.nodes[slot] = self._fresh_node(slot)
        self.reads[slot] = _ReadView()
        self.conf_applied_to[slot] = 0
        self._wire(slot)

    @staticmethod
    def _reset_kills_the_read(r, view: "_ReadView") -> None:
        reset = r.reset

        def wrapped(term):
            view.reset()
            reset(term)

        r.reset = wrapped

    def _empty_inbox(self):
        return [
            [[None] * NUM_KINDS for _ in range(self.r)] for _ in range(self.r)
        ]

    def round(
        self,
        campaigns: Sequence[int] = (),
        proposals: Optional[Dict[int, int]] = None,
        tick: bool = False,
        isolate: Iterable[int] = (),
        transfers: Optional[Dict[int, int]] = None,
        drop_pairs: Iterable[Tuple[int, int]] = (),
        offer: int = 0,
        reads: bool = False,
        conf=0,
        drained: Optional[int] = None,
        transfer_to: Optional[int] = None,
        wipe: Optional[int] = None,
    ) -> None:
        """One round with the device's phase order:
        deliver → tick/campaign → control → propose → emit.
        `transfers` maps leader slot → target slot; `drop_pairs` drops
        (sender, target) directed edges at emit — partial partitions.
        `offer` hands that many proposals to every replica, as the
        closed loop does: whoever leads when the propose phase is
        reached takes them (`_admitted`). `reads`, `conf`, `drained`
        and `transfer_to` are a row of the engine's control schedule
        (engine.CTL_*): a read asked of every replica, the
        configuration change on offer (a ``state.conf_code``) to every
        replica but those of node `drained`, which is asked to hand
        its leaderships to slot `transfer_to` instead (`conf` as a
        dict offers slot -> code, as ``step_round(conf_req=...)`` can).
        `wipe` resets that slot's replica as the control phase begins
        (engine.CTL_WIPE; a retired node, engine.CTL_RETIRE, is one of
        `isolate` here)."""
        iso = set(isolate)
        proposals = dict(proposals or {})
        if self.max_ents is not None:
            for node in self.nodes:
                node.raft.lane_sent.clear()
        transfers = transfers or {}
        drops = set(drop_pairs)

        # Phase 1: deliver in the configured order (see __init__): the
        # program's ("vectorized", per target below), or one of the two
        # message-at-a-time orders.
        if self.deliver_shape == "merged":
            order = [
                (sender, kind)
                for kinds in (range(0, 3), range(3, NUM_KINDS))
                for sender in range(self.r)
                for kind in kinds
            ]
        else:  # "lanes"
            order = [
                (sender, kind)
                for kind in range(NUM_KINDS)
                for sender in range(self.r)
            ]
        inbox, self.inbox = self.inbox, self._empty_inbox()
        for target in range(self.r):
            if target in iso:
                continue
            if self.deliver_shape == "vectorized":
                self._deliver_vectorized_target(target, inbox[target])
                continue
            for sender, kind in order:
                m = inbox[target][sender][kind]
                if m is None:
                    continue
                try:
                    self.nodes[target].step(m)
                except RaftError:
                    pass

        # Phase 2: tick / explicit campaigns.
        if tick:
            for node in self.nodes:
                node.tick()
        for slot in campaigns:
            self.nodes[slot].campaign()

        # Phase 2b: host control ops, same slot order as the device's
        # _control phase (after tick, before propose): the apply point
        # of a configuration change, transfers, reads, the change on
        # offer.
        if wipe is not None:
            self._wipe(wipe)
        for slot in range(self.r):
            self._apply_conf_changes(slot)
        if drained is not None and transfer_to is not None:
            transfers = dict(transfers)
            transfers[drained] = transfer_to
        for slot, target in transfers.items():
            if self._leads(slot):  # a follower's forward has no lane
                self.nodes[slot].transfer_leader(target + 1)
        for slot in range(self.r):
            self._control_read(slot, reads)
            code = (conf.get(slot, 0) if isinstance(conf, dict)
                    else 0 if slot == drained else conf)
            if code:
                self._offer_conf(slot, code)

        # Phase 3: proposals (empty payloads; the batched engine carries
        # payloads in the host arena, so terms are the shared content).
        # All n entries ride one MsgProp — the batched engine appends
        # its per-round proposals as one batch with one broadcast.
        from ..raft.types import Entry

        if offer:
            for slot in range(self.r):
                proposals[slot] = self._admitted(slot, offer)
        for slot, n in proposals.items():
            if n <= 0:
                continue
            node = self.nodes[slot]
            try:
                node.raft.step(
                    Message(
                        type=MessageType.MsgProp,
                        from_=node.raft.id,
                        entries=[Entry(data=b"") for _ in range(n)],
                    )
                )
            except RaftError:
                pass

        # Phase 4a: persist — take every node's Ready and store
        # hardstate/snapshot/entries FIRST, so the compaction and the
        # send materialization below see this round's log.
        readys: List[Tuple[int, object]] = []
        for slot, node in enumerate(self.nodes):
            if not node.has_ready():
                continue
            rd = node.ready()
            storage = node.raft.raft_log.storage
            if rd.hard_state.term or rd.hard_state.vote or rd.hard_state.commit:
                storage.set_hard_state(rd.hard_state)
            if rd.snapshot.metadata.index > 0:
                # Installed snapshot persists before entries
                # (the production drain order, etcdserver/raft.go).
                storage.apply_snapshot(rd.snapshot)
            storage.append(rd.entries)
            readys.append((slot, rd))

        # Phase 4b: auto-compaction emulation — the device compacts at
        # the top of _emit with this round's commit and log, and its
        # append-vs-snapshot decision sees the new floor (step.py
        # _emit auto_compact then snap_needed).
        if self.auto_compact_window and self.replace:
            for slot in range(self.r):
                self._compact(slot)
        elif self.auto_compact_window:
            keep = self.auto_compact_window // 2
            for node in self.nodes:
                r = node.raft
                st = r.raft_log.storage
                target = min(
                    r.raft_log.committed, st.last_index() - keep
                )
                if target > st.first_index() - 1:
                    st.create_snapshot(target, None, b"")
                    st.compact(target)

        # Phase 4c: emit — bucket outbound messages, device-coalesced.
        for slot, rd in readys:
            node = self.nodes[slot]
            for m in rd.messages:
                if slot in iso:
                    continue
                if (m.to not in node.raft.prs.progress
                        and m.term == node.raft.term and self._leads(slot)):
                    # Queued (a tick's heartbeat, a commit's broadcast)
                    # before this round's apply point deleted the
                    # peer's row: the device's emit sends to the rows
                    # it has when the messages leave.
                    continue
                m = self._rematerialize(node, m)
                kind = _TYPE_TO_KIND.get(m.type)
                if kind is None:
                    raise AssertionError(f"unroutable message type {m.type}")
                target = m.to - 1
                if (slot, target) in drops:
                    continue
                prev = self.inbox[target][slot][kind]
                if prev is not None:
                    # The device coalesces same-round sends into one
                    # flag; the oracle may emit duplicates (hb-resp and
                    # app-resp both probing) or split one logical
                    # append across two messages (commit bcast +
                    # proposal bcast in one Ready). Coalesce both
                    # shapes; anything else is a real violation.
                    if _same_message(prev, m):
                        continue
                    merged = _merge_apps(prev, m)
                    if merged is not None:
                        # What the sender's Progress counts as sent fits
                        # the lane (`_one_lane_a_round`); entries past
                        # it are `_rematerialize`'s, and the lane drops
                        # them as the device's emit does.
                        if self.max_ents is not None:
                            del merged.entries[self.max_ents:]
                        self.inbox[target][slot][kind] = merged
                        continue
                    # A snapshot supersedes an append in the same lane,
                    # exactly like the device's emit (snap_needed
                    # overrides the append send).
                    kinds = {prev.type, m.type}
                    if MessageType.MsgSnap in kinds and kinds <= {
                        MessageType.MsgSnap, MessageType.MsgApp
                    }:
                        snaps = [x for x in (prev, m)
                                 if x.type == MessageType.MsgSnap]
                        best = max(snaps,
                                   key=lambda x: x.snapshot.metadata.index)
                        self.inbox[target][slot][kind] = best
                        continue
                    # MsgTimeoutNow shares the heartbeat lane and
                    # supersedes that peer's heartbeat (step._emit).
                    if kinds == {MessageType.MsgHeartbeat,
                                 MessageType.MsgTimeoutNow}:
                        if m.type == MessageType.MsgTimeoutNow:
                            self.inbox[target][slot][kind] = m
                        continue
                    raise AssertionError(
                        f"slot collision: {m.type} from {slot} to {target}; "
                        "schedule outside the differential envelope"
                    )
                self.inbox[target][slot][kind] = m
        for slot, rd in readys:
            self.nodes[slot].advance(rd)
        for slot, node in enumerate(self.nodes):
            r = node.raft
            if r.vote and self.votes_cast[slot].setdefault(
                    r.term, r.vote) != r.vote:
                raise AssertionError(
                    f"slot {slot} voted for {r.vote} in term {r.term} where "
                    f"it had voted for {self.votes_cast[slot][r.term]}: a "
                    "reused slot voted twice")

    def _applied(self, slot: int) -> int:
        """The device's `applied` after emit: the commit index, or the
        entry before a configuration change this replica has yet to
        apply."""
        log = self.nodes[slot].raft.raft_log
        lo = max(self.conf_applied_to[slot], log.first_index() - 1)
        if log.committed > lo:
            for e in log.slice(lo + 1, log.committed + 1, 1 << 62):
                if e.type == EntryType.EntryConfChangeV2:
                    return e.index - 1
        return log.committed

    def _compact(self, slot: int) -> None:
        """step._emit's compaction with `replace`: the snapshot a
        replica would send is taken at its applied index and states the
        configuration it holds there (etcd snapshots at the applied
        index and compacts behind it); the floor is auto_compact's."""
        r = self.nodes[slot].raft
        st = r.raft_log.storage
        applied = self._applied(slot)
        target = min(applied, st.last_index() - self.auto_compact_window // 2)
        if applied > st._snapshot.metadata.index:
            st.create_snapshot(applied, r.prs.conf_state(), b"")
        if target > st.first_index() - 1:
            st.compact(target)


    # -- the control phase (device: step._control) -----------------------------

    def _leads(self, slot: int) -> bool:
        from ..raft.raft import StateType

        return self.nodes[slot].raft.state == StateType.StateLeader

    def _apply_conf_changes(self, slot: int) -> None:
        """The replica's own apply point (step._conf_apply): every
        configuration change among the entries its commit has reached
        since it last looked, through RawNode.apply_conf_change."""
        node = self.nodes[slot]
        log = node.raft.raft_log
        lo, hi = self.conf_applied_to[slot], log.committed
        if hi <= lo:
            return
        # Where this is called for the first time the log begins at
        # the bootstrap snapshot, which holds no change.
        lo = max(lo, log.first_index() - 1)
        for e in log.slice(lo + 1, hi + 1, 1 << 62):
            if e.type == EntryType.EntryConfChangeV2:
                node.apply_conf_change(ConfChangeV2.unmarshal(e.data))
                self.conf_applied[slot] += 1
        self.conf_applied_to[slot] = hi

    def _control_read(self, slot: int, asked: bool) -> None:
        """step._control's ReadIndex rule on plain RawNode.read_index:
        a leader that has committed in its term opens a batch at its
        commit index when none is in flight, for a request of this
        round or one that has waited since an earlier one; the
        ReadState raft yields for its context confirms it."""
        node, view = self.nodes[slot], self.reads[slot]
        r = node.raft
        ctx = str(view.seq).encode()
        if any(rs.request_ctx == ctx for rs in r.read_states):
            view.ready = True
        pending = view.index >= 0 and not view.ready
        asked = asked or view.waiting
        view.waiting = False
        if not (asked and self._leads(slot) and not pending
                and r.committed_entry_in_current_term()):
            view.waiting = asked
            return
        view.seq += 1
        view.index, view.ready = r.raft_log.committed, False
        node.read_index(str(view.seq).encode())
        if any(rs.request_ctx == str(view.seq).encode()
               for rs in r.read_states):
            view.ready = True  # a quorum of one

    def _offer_conf(self, slot: int, code: int) -> None:
        """step._conf_propose's rule, then RawNode.propose_conf_change."""
        node = self.nodes[slot]
        r = node.raft
        cfg = r.prs.config
        kind, slot1, slot2 = conf_decode(code)
        who, whom = slot1 + 1, slot2 + 1
        joint = bool(cfg.voters.outgoing)
        if kind == CONF_LEAVE:
            fits = joint
        elif kind == CONF_DEMOTE:
            fits = not joint and who in cfg.voters.incoming
        elif kind == CONF_ADD_LEARNER:
            fits = not joint and who not in r.prs.progress
        elif kind == CONF_SWAP:
            # The stand-in for isLearnerReady: the leader's row for the
            # learner is REPLICATE.
            fits = (not joint and who in cfg.learners
                    and whom in cfg.voters.incoming
                    and r.prs.progress[who].state
                    == ProgressStateType.StateReplicate)
        else:
            fits = not joint and who in cfg.learners
        held = r.raft_log.last_index() - (
            r.raft_log.storage.first_index() - 1)
        room = (self.auto_compact_window - held - self.max_props
                if self.auto_compact_window else 1)
        if (self._leads(slot) and not r.lead_transferee
                and r.id in r.prs.progress and fits
                and r.pending_conf_index <= r.raft_log.applied
                and room > 0):
            node.propose_conf_change(conf_change(code))

    def _one_lane_a_round(self, r) -> None:
        """The device carries one append of at most E entries to a peer
        in a round. The oracle's leader is held to that where it sends
        (its log fetch is capped to the room left in the peer's lane,
        and a send to a full lane is refused like one to a paused
        peer), so its Progress tracks what the lane carries and no
        append is cut in the network."""
        r.lane_sent = {}  # peer id -> entries sent this round
        room = [self.max_ents]
        fetch, send = r.raft_log.entries, r.maybe_send_append

        def entries(i, max_size):
            return fetch(i, max_size)[:room[0]]

        def maybe_send_append(to, send_if_empty):
            room[0] = self.max_ents - r.lane_sent.get(to, 0)
            if room[0] <= 0:
                # The lane's one append has left already. Where it was
                # a probe and a heartbeat response handled since has
                # cleared `probe_sent` to send again, the device, whose
                # one send leaves at emit, ends the round waiting on
                # that probe.
                pr = r.prs.progress[to]
                if pr.state == ProgressStateType.StateProbe:
                    pr.probe_sent = True
                return False
            sent = send(to, send_if_empty)
            if sent and r.msgs[-1].type == MessageType.MsgApp:
                r.lane_sent[to] = (r.lane_sent.get(to, 0)
                                   + len(r.msgs[-1].entries))
            return sent

        r.raft_log.entries = entries
        r.maybe_send_append = maybe_send_append

    def _admitted(self, slot: int, n: int) -> int:
        """How many of `n` offered proposals the device's `_propose`
        appends on this replica: none unless it leads and is not handing
        leadership over (a follower's forwarded MsgProp has no lane on
        the device), and no more than the ring's headroom, W less the
        entries held less one round's proposals (the engine's admission
        control; W is known here under auto-compaction only, and P is
        `max_props`)."""
        from ..raft.raft import StateType

        r = self.nodes[slot].raft
        if r.state != StateType.StateLeader or r.lead_transferee:
            return 0
        if self.auto_compact_window and self.max_props:
            held = r.raft_log.last_index() - (
                r.raft_log.storage.first_index() - 1)
            n = min(n, self.max_props,
                    max(self.auto_compact_window - held - self.max_props, 0))
        return n

    def _deliver_vectorized_target(self, target: int, msgs) -> None:
        """One target's inbox in the vectorized shape's order contract
        (step.py _deliver_vectorized): lanes in kind order; within the
        vote lane every T_VOTE (term desc, sender asc) before every
        T_PREVOTE (prevotes never mutate state); within the other
        request lanes the winner (term desc, sender asc) first, losers
        after — a loser the winner has not made stale would apply here
        but is dropped on device, so it raises as an envelope
        violation (two leaders at one term cannot exist in-protocol);
        within response lanes same-term effects first (commutative),
        then deposing messages ascending by term."""
        node = self.nodes[target]

        def step(m: Message) -> None:
            try:
                node.step(m)
            except RaftError:
                pass

        def lane(kind):
            return [(s, msgs[s][kind]) for s in range(self.r)
                    if msgs[s][kind] is not None]

        votes = sorted(
            (x for x in lane(KIND_VOTE)
             if x[1].type == MessageType.MsgVote),
            key=lambda sm: (-sm[1].term, sm[0]))
        pres = [x for x in lane(KIND_VOTE)
                if x[1].type != MessageType.MsgVote]
        for _, m in votes + pres:
            step(m)

        for kind in (KIND_APP, KIND_HB):
            ordered = sorted(lane(kind),
                             key=lambda sm: (-sm[1].term, sm[0]))
            for i, (sender, m) in enumerate(ordered):
                if i > 0 and m.term >= node.raft.term:
                    raise AssertionError(
                        f"vectorized deliver: request-lane loser from "
                        f"{sender} at term {m.term} not stale against "
                        f"the winner (node term {node.raft.term}); "
                        "schedule outside the vectorized envelope")
                step(m)

        for kind in (KIND_VOTE_RESP, KIND_APP_RESP, KIND_HB_RESP):
            t0 = node.raft.term
            eff, dep = [], []
            for s, m in lane(kind):
                deposes = m.term > t0 and not (
                    m.type == MessageType.MsgPreVoteResp and not m.reject)
                (dep if deposes else eff).append((s, m))
            dep.sort(key=lambda sm: (sm[1].term, sm[0]))
            for _, m in eff + dep:
                step(m)

    def _rematerialize(self, node: RawNode, m: Message) -> Message:
        """The device remembers only a send FLAG per peer and derives
        append content at emit time (end of round); the oracle bakes
        content at queue time (mid-deliver). Re-slice outbound MsgApp
        entries and commit from the sender's end-of-round log so both
        models emit identical bytes (e.g. a probe queued before this
        round's proposals still carries them)."""
        from ..raft.raft import StateType

        r = node.raft
        if m.term != r.term or r.state != StateType.StateLeader:
            return m
        if m.type == MessageType.MsgSnap:
            # The same for a snapshot queued mid-deliver: the device
            # compacts at the top of emit and sends the floor it has
            # then, so the oracle's leader sends, and waits on, the
            # snapshot of the end of the round.
            snap = r.raft_log.storage.snapshot()
            pr = r.prs.progress[m.to]
            if pr.pending_snapshot == m.snapshot.metadata.index:
                pr.pending_snapshot = snap.metadata.index
            return Message(
                type=MessageType.MsgSnap, to=m.to, from_=m.from_,
                term=m.term, snapshot=snap,
            )
        if m.type == MessageType.MsgHeartbeat:
            # The device stamps a heartbeat where it emits it: the
            # read batch then open (the control phase may have opened
            # one since the tick queued this) and the commit then held.
            return Message(
                type=m.type, to=m.to, from_=m.from_, term=m.term,
                commit=min(r.prs.progress[m.to].match,
                           r.raft_log.committed),
                context=r.read_only.last_pending_request_ctx())
        if m.type != MessageType.MsgApp:
            return m
        # Below the (just-advanced) floor the device sends a snapshot
        # instead (step.py _emit snap_needed after auto-compaction).
        floor = r.raft_log.storage.first_index() - 1
        if m.index < floor:
            snap = r.raft_log.storage.snapshot()
            if self.replace:
                # The device's emit leaves the peer's row waiting on
                # the snapshot it sends.
                r.prs.progress[m.to].become_snapshot(snap.metadata.index)
            return Message(
                type=MessageType.MsgSnap, to=m.to, from_=m.from_,
                term=m.term, snapshot=snap,
            )
        last = r.raft_log.last_index()
        want = last - m.index
        if self.max_ents is not None:
            want = min(want, self.max_ents)
        if want <= len(m.entries) and m.commit == r.raft_log.committed:
            return m
        try:
            ents = r.raft_log.slice(m.index + 1, m.index + 1 + want, 1 << 62)
        except RaftError:
            return m
        return Message(
            type=m.type, to=m.to, from_=m.from_, term=m.term,
            log_term=m.log_term, index=m.index, entries=ents,
            commit=r.raft_log.committed,
        )

    # -- state vector for comparison ------------------------------------------

    def snapshot_state(self) -> List[Tuple[int, ...]]:
        """(term, role, lead, commit, last) per replica — the fields the
        batched engine must reproduce exactly."""
        out = []
        for node in self.nodes:
            r = node.raft
            out.append(
                (
                    r.term,
                    int(r.state),
                    r.lead,
                    r.raft_log.committed,
                    r.raft_log.last_index(),
                )
            )
        return out

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
        """(read_seq, read_index, read_ready) per replica."""
        return [(v.seq, v.index, v.ready) for v in self.reads]

    def log_terms(self, slot: int) -> List[Tuple[int, int]]:
        r = self.nodes[slot].raft
        lo = r.raft_log.first_index()
        hi = r.raft_log.last_index()
        return [(i, r.raft_log.term(i)) for i in range(lo, hi + 1)]
