"""BatchedRawNode: the RawNode plugin contract over G groups at once.

This is the piece that turns the device step kernel into a *backend*:
the same logical cycle as the reference's RawNode —

    stage inputs → advance_round() → BatchedReady →
    persist (WAL) → apply → send → advance()

(ref: raft/rawnode.go:125-179 HasReady/Ready/Advance and the production
ordering in server/etcdserver/raft.go:158-315) — but for every group in
one device program. Entry payload bytes never touch the device: the
host keeps them in a per-row arena keyed by log index, assigns indexes
to proposals from the phase watermarks the kernel reports (StepAux),
and re-attaches payloads when draining committed ranges or building
outbound MsgApp messages.

A *row* is one replica instance this process hosts: (group, slot).
Topologies:

* hosting process (one replica slot of every group): rows = G,
  ``slots[i] = s`` constant, messages travel over the wire;
* in-proc all-replica engine (tests, single-process demos): rows = G*R.

Persistence contract per round (must_sync mirrors raft MustSync,
ref: raft/node.go:588-595): the caller drains ``BatchedReady`` to its
WAL and fsyncs BEFORE handing messages to the transport, then applies
committed entries, then calls ``advance()``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.sentinels import warm_guard
from ..obs import spans
from ..raft.types import (
    Entry,
    EntryType,
    Message,
    MessageType,
    Snapshot,
    SnapshotMetadata,
)
from .msgblock import (
    MsgBlock,
    compact_records,
    merge_blocks,
    ragged_ranges,
    validate_block,
)
from .state import BatchedConfig, BatchedState, LEADER, I32, init_state
from .step import (
    KIND_APP,
    KIND_APP_RESP,
    KIND_HB,
    KIND_HB_RESP,
    KIND_VOTE,
    KIND_VOTE_RESP,
    NUM_KINDS,
    T_APP,
    T_APP_RESP,
    T_HB,
    T_HB_RESP,
    T_PREVOTE,
    T_PREVOTE_RESP,
    T_SNAP,
    T_TIMEOUT_NOW,
    T_VOTE,
    T_VOTE_RESP,
    MsgSlots,
    make_step_round,
    pack_outbox,
)

# Inbox lane for each wire type (lanes are capacity classes; handlers
# dispatch on the type field — see step.py).
_LANE = {
    T_VOTE: KIND_VOTE,
    T_PREVOTE: KIND_VOTE,
    T_APP: KIND_APP,
    T_SNAP: KIND_APP,
    T_HB: KIND_HB,
    T_TIMEOUT_NOW: KIND_HB,
    T_VOTE_RESP: KIND_VOTE_RESP,
    T_PREVOTE_RESP: KIND_VOTE_RESP,
    T_APP_RESP: KIND_APP_RESP,
    T_HB_RESP: KIND_HB_RESP,
}


@dataclass
class RowRestore:
    """Boot state for one row (from WAL replay / snapshot)."""

    term: int = 0
    vote: int = 0  # slot+1, 0 = none
    commit: int = 0
    applied: int = 0  # host app state watermark (snapshot index)
    snap_index: int = 0  # log floor
    snap_term: int = 0
    entries: List[Tuple[int, int, bytes]] = field(default_factory=list)
    # (index, term, data[, etype]) strictly ascending, > snap_index
    # Membership at the snapshot point (None → full-voter bootstrap;
    # committed conf entries in the tail re-apply through Ready).
    conf_state: Optional[object] = None
    # Durability fence (protocol-aware torn-tail recovery): the hosting
    # layer sets this when the recovered WAL tail fell below the
    # group's durable watermark — the row boots with campaigning and
    # vote-granting suppressed until set_fence(row, False) lifts it.
    fenced: bool = False


_EMPTY_I8 = np.empty(0, np.int64)

# The spans of advance_round in order (names under "rawnode."), and
# those that make up the phase the code has always called step.
PHASES = ("stage_lock", "stage", "edits", "h2d", "dispatch", "fence",
          "d2h", "extract", "collect")
STEP_PHASES = ("edits", "h2d", "dispatch", "fence", "d2h")
# The pure-Python phases: wall less thread CPU there is the interpreter
# lock or a mutex (``round.offcpu_pct``), so their CPU time is read.
CPU_PHASES = frozenset(("stage", "extract", "collect"))


class EntryBatch:
    """SoA batch of entry records to persist: parallel numpy arrays
    (row, index, term, etype) plus the payload list, in row-ascending
    index-ascending order. Iterates as (row, index, term, data, etype)
    tuples — the legacy consumer shape — while the arrays feed the
    hosting layer's batched WAL serialization directly (one numpy
    header array + one payload join per persistence batch, no
    per-entry struct.pack)."""

    __slots__ = ("rows", "idx", "term", "etype", "datas")

    def __init__(self, rows: np.ndarray = _EMPTY_I8,
                 idx: np.ndarray = _EMPTY_I8,
                 term: np.ndarray = _EMPTY_I8,
                 etype: np.ndarray = _EMPTY_I8,
                 datas: Optional[List[bytes]] = None) -> None:
        self.rows = rows
        self.idx = idx
        self.term = term
        self.etype = etype
        self.datas = datas if datas is not None else []

    def __len__(self) -> int:
        return len(self.datas)

    def __iter__(self):
        return iter(zip(self.rows.tolist(), self.idx.tolist(),
                        self.term.tolist(), self.datas,
                        self.etype.tolist()))


@dataclass
class BatchedReady:
    """One round's outstanding work (ref: raft/node.go:52-90 Ready,
    batched). Drain order: hardstates+entries+snapshots → WAL fsync →
    apply committed → messages → advance()."""

    hardstates: List[Tuple[int, int, int, int]]  # (row, term, vote, commit)
    entries: "EntryBatch"  # (row, index, term, data, etype) records
    # Device-installed snapshot restores this round: (row, index, term).
    # App-state restore happened host-side when the MsgSnap was staged.
    snapshots: List[Tuple[int, int, int]]
    committed: List[Tuple[int, List[Tuple[int, int, Optional[bytes]]]]]
    # (row, [(index, term, data or None for internal/empty)])
    messages: List[Tuple[int, Message]]
    must_sync: bool
    # Payload-free outbound messages as one SoA block (see msgblock.py);
    # `messages` then carries only MsgApp-with-entries / MsgSnap.
    msg_block: Optional[MsgBlock] = None
    # Quorum-confirmed ReadIndex batches this round: (row, seq, index)
    # (ref: Ready.ReadStates, read_only.go advance).
    read_states: List[Tuple[int, int, int]] = field(default_factory=list)
    # Sampled trace keys (etcd_tpu.obs): (group, term, index) of traced
    # entries persisted this round (the hosting layer stamps fsync/send
    # on them) and of traced entries newly committed this round (apply
    # stamp). Empty lists when tracing is off — zero per-round cost.
    traced_entries: List[Tuple[int, int, int]] = field(default_factory=list)
    traced_commit: List[Tuple[int, int, int]] = field(default_factory=list)
    # Batches that OPENED this round: (row, seq). Hosts bind waiters to
    # the open batch so a later waiter is never served an earlier
    # batch's (stale) index.
    read_opened: List[Tuple[int, int]] = field(default_factory=list)
    # Ring term rows captured AT ROUND TIME for rows with outbound
    # MsgSnap: a pipelined drain worker processing this Ready later
    # must price the snapshot term from THIS round's ring — by then
    # latest_ring() reflects newer rounds and (with auto_compact) the
    # slot may have wrapped to a different entry's term.
    snap_rings: Dict[int, np.ndarray] = field(default_factory=dict)
    # Rows whose role, as read back this round, left LEADER.
    leader_losses: int = 0
    # Set by the hosting layer: the member round's sequence number (the
    # id its spans share) and the instant the Ready was queued for the
    # drain worker (start of its member.ready_q span).
    round: int = -1
    t_queued: int = 0

    def contains_updates(self) -> bool:
        return bool(
            self.hardstates or self.entries or self.snapshots
            or self.committed or self.messages or self.read_states
            or (self.msg_block is not None and len(self.msg_block))
        )


class BatchedRawNode:
    """Thread-safe staging + single-threaded advance_round/advance.

    ``advance_round()`` runs one device round over the staged inputs and
    produces a BatchedReady; the caller persists/applies/sends, then
    calls ``advance()`` to commit the host mirrors. Only one
    round may be in flight at a time.
    """

    def __init__(
        self,
        cfg: BatchedConfig,
        groups: Optional[np.ndarray] = None,
        slots: Optional[np.ndarray] = None,
        restore: Optional[Dict[int, RowRestore]] = None,
        start_index: int = 0,
        mesh: Optional["object"] = None,
    ) -> None:
        # Resolve deliver_shape="auto" so the hosted path and the
        # closed-loop engine pick the same compiled round program for
        # one logical config.
        self.cfg = cfg = cfg.validate().resolved()
        if cfg.log_runs:
            raise ValueError(
                "log_runs on the hosting path: a member restores, persists "
                "and reads back each row's log as a ring of terms (the d2h "
                "extract, RowRestore); the run table is the closed-loop "
                "engine's")
        from .compile_cache import enable_compile_cache

        enable_compile_cache()
        r = cfg.num_replicas
        if groups is None:  # dense all-replica layout
            n = cfg.num_instances
            groups = np.arange(n, dtype=np.int32) // r
            slots = np.arange(n, dtype=np.int32) % r
        else:
            groups = np.asarray(groups, np.int32)
            slots = np.asarray(slots, np.int32)
        self.groups = groups
        self.slots = slots
        self.n = len(groups)
        iids = groups * r + slots
        # Row-axis sharding over a device mesh: rows (= groups for a
        # hosting member) are the data-parallel axis of multi-raft —
        # quorum reductions stay within a row, so the sharded step
        # needs NO cross-device collectives (SURVEY §2.1 parallelism
        # decomposition; the dryrun_multichip layout).
        self._shard = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            assert self.n % len(mesh.devices.flat) == 0, (
                f"rows {self.n} must divide the mesh "
                f"({len(mesh.devices.flat)} devices)")
            self._shard = NamedSharding(mesh, PartitionSpec("groups"))

        def dev(x):
            if self._shard is not None:
                # device_put accepts numpy directly and slices
                # host-side — no intermediate hop via the default
                # device before the mesh reshard.
                return jax.device_put(x, self._shard)
            return jnp.asarray(x)

        self._dev = dev
        self._slots_j = dev(slots)
        self._step = make_step_round(
            cfg, iids=dev(iids), slots=self._slots_j, with_aux=True,
            # Mesh-sharded rows must not pay a cross-shard collective
            # for the lane-occupancy skip (step._step_round_jit): the
            # sharded round's contract is ZERO collectives on the hot
            # path, and concurrent members' AllReduces deadlock.
            lane_skip=self._shard is None,
        )
        # Transfer-guard warmth is per (config, row count): the shared
        # round program recompiles per distinct row shape, and compiles
        # must run unguarded (they transfer host constants).
        self._wkey_step = f"round_step/{hash((cfg, True, self.n))}"

        self.state = init_state(cfg, start_index, iids=jnp.asarray(iids))
        if self._shard is not None:
            self.state = jax.tree.map(dev, self.state)
        # Host mirrors (updated in advance()).
        self.m_term = np.zeros(self.n, np.int64)
        self.m_vote = np.zeros(self.n, np.int64)
        self.m_commit = np.full(self.n, start_index, np.int64)
        self.m_last = np.full(self.n, start_index, np.int64)
        self.m_snap = np.full(self.n, start_index, np.int64)
        self.m_role = np.zeros(self.n, np.int64)
        self.m_lead = np.zeros(self.n, np.int64)
        # Consistent (term, role, lead) triple for observers: the
        # individual mirrors above are swapped by TWO statements in
        # advance(), so a foreign thread reading them pairwise can see
        # role from round k and term from round k-1 — a phantom
        # "leader at the old term". One tuple assignment is atomic.
        self.m_view: Tuple[np.ndarray, np.ndarray, np.ndarray] = (
            self.m_term, self.m_role, self.m_lead)
        self.m_ring = np.zeros((self.n, cfg.window), np.int64)
        # Leader-lease lane mirror (state.lease_ticks): the hosting
        # layer's lease-first read routing compares this against
        # cfg.lease_read_margin — one numpy read, zero device hops.
        self.m_lease_ticks = np.zeros(self.n, np.int64)
        self.applied = np.full(self.n, start_index, np.int64)
        self.stable = np.full(self.n, start_index, np.int64)

        # Payload arena: per row, index -> (term, data).
        self.arena: List[Dict[int, Tuple[int, bytes]]] = [
            {} for _ in range(self.n)
        ]
        # Sparse entry-type registry: index -> EntryType for the rare
        # non-Normal entries (conf changes); absent == EntryNormal.
        # The device only ever sees (term, index); types ride the host
        # arena like payloads do.
        self.etypes: List[Dict[int, int]] = [{} for _ in range(self.n)]

        # Monotone commit watermark guarding arena immutability (see
        # step(): inbound MsgApp must not overwrite committed payloads).
        self._commit_guard = np.full(self.n, start_index, np.int64)

        # Staging (guarded by _lock).
        self._lock = threading.Lock()
        self._pending: Dict[Tuple[int, int, int], deque] = {}
        self._blocks: deque = deque()  # staged MsgBlock record arrays
        self._props: List[deque] = [deque() for _ in range(self.n)]
        self._ticks = np.zeros(self.n, np.int64)
        self._campaign = np.zeros(self.n, bool)
        self._isolate = np.zeros(self.n, bool)
        self._transfer = np.zeros(self.n, np.int32)  # target slot+1
        self._read_req = np.zeros(self.n, bool)
        self._poked = False  # host staged send_append flags (poke_append)
        self._poke_rows = np.zeros(self.n, bool)
        # Staged device-state edits from foreign threads, applied at
        # the head of the next round ON the round thread (in-place
        # edits would race the round's state swap): row -> masks, and
        # row -> requested ring-floor index.
        self._pending_conf: Dict[int, Tuple] = {}
        self._pending_compact: Dict[int, int] = {}
        self._pending_fence: Dict[int, bool] = {}
        self._read_seen = np.zeros(self.n, np.int64)  # last surfaced seq
        self._read_seq_prev = np.zeros(self.n, np.int64)  # open detection
        self._snap_staged: Dict[int, Tuple[int, int]] = {}  # row->(idx,term)

        if restore:
            self._restore(restore)

        # In-flight round (between advance_round and advance).
        self._round: Optional[Tuple] = None

        # Where a round's host time goes (obs/spans.py, always on): each
        # phase of advance_round is one span of the recorder, and the
        # two fields below are set from the spans' own clock reads.
        # phase_last: the last round's wall-seconds in the four phases
        # the hosting layer folds into its histograms — stage (inbox
        # build under _lock), step (edits + h2d + dispatch + fence +
        # d2h: the device round and the host reads around it), extract
        # (post-round entry/commit extraction), collect (outbound block
        # assembly). phase_total: cumulative seconds by span name
        # (PHASES, plus "step" and the round count), read by the admin
        # prof op and tools/hosted_bench.
        self.phase_last: Dict[str, float] = {
            "stage": 0.0, "step": 0.0, "extract": 0.0, "collect": 0.0}
        self.phase_total: Dict[str, float] = {
            **{p: 0.0 for p in PHASES}, "step": 0.0, "rounds": 0}

        # Telemetry plane (cfg.telemetry): the round returns an extra
        # frame; advance_round fetches it with the other host reads and
        # folds it into the attached hub (hosting layer sets one).
        self.telemetry_hub = None  # TelemetryHub, optional
        self.last_frame: Optional[Tuple[np.ndarray, np.ndarray]] = None
        # Fleet observatory plane (cfg.fleet_summary): the round also
        # returns the flat SummaryFrame vector (obs/fleet.FleetLayout);
        # fetched with the round's other reads — no extra sync — and
        # folded into the attached FleetHub. Output position after
        # (state, outbox, aux[, telemetry]).
        self.fleet_hub = None  # obs.fleet.FleetHub, optional
        self.last_fleet: Optional[np.ndarray] = None
        self._fleet_idx = 3 + (1 if self.cfg.telemetry else 0)
        # Proposal-lifecycle tracer (etcd_tpu.obs.Tracer, optional —
        # hosting layer attaches one). Purely host-side: the device
        # program and protocol state are identical with it on or off;
        # the hot path pays one `is not None` per round when off.
        self.tracer = None

        # Device-resident apply plane (cfg.apply_plane, applyplane.py):
        # a SEPARATE jitted program folding each round's committed
        # entries into per-row KV/revision/watch/lease tensors —
        # dispatched right after committed-range extraction, where the
        # payload bytes are in hand. The round-step program is shared
        # with apply_plane=False by construction (make_step_round
        # strips the plane knobs from the compile key).
        self.plane = None
        if cfg.apply_plane:
            from .applyplane import init_plane, make_dispatch

            self.plane = init_plane(cfg, self.n)
            self._plane_step = make_dispatch(cfg, self.n)
            self._wkey_plane = f"apply_plane/{hash((cfg, self.n))}"
            # Watch events drained by the hosting layer (row, op,
            # key_hash, rev, wmask); bounded — watches are telemetry
            # consumers, and a stalled drain must not grow the heap.
            self.plane_events: deque = deque(maxlen=8192)
            # Host-accumulated plane stats (round thread writes, any
            # thread reads — GIL-atomic scalar swaps).
            self.plane_stats: Dict[str, int] = {
                "dispatches": 0, "puts": 0, "dels": 0, "expired": 0,
                "watch_events": 0, "slots_hw": 0, "overflow_rows": 0,
                "active_leases": 0,
            }
            # Staged plane edits from foreign threads, applied at the
            # head of advance_round ON the round thread (the staged-
            # edit idiom of _pending_conf): watch-slot arms and
            # snapshot-restored row images.
            self._pending_watch: Dict[Tuple[int, int], int] = {}
            self._pending_plane_rows: Dict[int, Tuple] = {}
            # Serializes the donated plane carry between the round
            # thread's dispatch and plane_capture's snapshot gather —
            # a gather racing a dispatch would read a donated (freed)
            # buffer.
            self._plane_mu = threading.Lock()
            # Host mirrors of the plane clock and the highest entry
            # index folded per row (round thread writes; any thread
            # reads — np scalar loads are GIL-atomic). The applied
            # watermark makes re-dispatch idempotent: a plane image
            # restored AHEAD of the host snapshot index (cadence
            # capture runs off the round thread's commit stream, which
            # leads the apply drain) must not double-fold the WAL tail
            # the host re-delivers on boot.
            self.m_plane_tick = np.zeros(self.n, np.int64)
            self.m_plane_applied = np.zeros(self.n, np.int64)
            # Exact host lessor mirror: (row, key bytes) -> absolute
            # plane-tick expiry, replayed from the same payload stream
            # at the same tick arithmetic as the device kernel. The
            # lease-read path masks host-tier bytes through it (the
            # device stores hashes only — byte honesty). Round thread
            # writes; readers do GIL-atomic gets.
            self.plane_lessor: Dict[Tuple[int, bytes], int] = {}

    # -- boot ------------------------------------------------------------------

    def _restore(self, restore: Dict[int, RowRestore]) -> None:
        """Rebuild device state from per-row WAL replay results."""
        cfg = self.cfg
        w = cfg.window
        term = np.zeros(self.n, np.int32)
        vote = np.zeros(self.n, np.int32)
        commit = np.zeros(self.n, np.int32)
        last = np.zeros(self.n, np.int32)
        snap_i = np.zeros(self.n, np.int32)
        snap_t = np.zeros(self.n, np.int32)
        ring = np.zeros((self.n, w), np.int32)
        fenced = np.zeros(self.n, bool)
        for row, rr in restore.items():
            fenced[row] = rr.fenced
            term[row] = rr.term
            vote[row] = rr.vote
            # A snapshot at snap_index proves snap_index was committed;
            # a stale persisted hardstate must not boot the row into
            # the illegal watermark order commit < snap_index.
            commit[row] = max(rr.commit, rr.snap_index)
            snap_i[row] = rr.snap_index
            snap_t[row] = rr.snap_term
            li = rr.snap_index
            for ent in rr.entries:
                idx, t, data = ent[0], ent[1], ent[2]
                ring[row, idx % w] = t
                self.arena[row][idx] = (t, data)
                if len(ent) > 3 and ent[3]:
                    self.etypes[row][idx] = int(ent[3])
                li = idx
            last[row] = li
            self.applied[row] = rr.applied
        st = self.state
        self.state = st._replace(
            term=self._dev(term),
            # vote is a narrow (int8) lane under cfg.narrow_lanes; keep
            # the restored field at the state's storage dtype so the
            # first round doesn't compile a second program.
            vote=self._dev(vote).astype(st.vote.dtype),
            commit=self._dev(commit),
            last=self._dev(last),
            snap_index=self._dev(snap_i),
            snap_term=self._dev(snap_t),
            log_term=self._dev(ring),
            fenced=self._dev(fenced),
            next=self._dev(
                np.repeat(last[:, None] + 1, cfg.num_replicas, axis=1)
            ),
        )
        self.m_term = term.astype(np.int64)
        self.m_vote = vote.astype(np.int64)
        self.m_commit = commit.astype(np.int64)
        self.m_last = last.astype(np.int64)
        self.m_snap = snap_i.astype(np.int64)
        self.m_ring = ring.astype(np.int64)
        self.stable = last.astype(np.int64)
        self._commit_guard = np.maximum(
            self._commit_guard, commit.astype(np.int64)
        )

    # -- staging ---------------------------------------------------------------

    def tick(self, rows: Optional[np.ndarray] = None) -> None:
        with self._lock:
            if rows is None:
                self._ticks += 1
            else:
                self._ticks[rows] += 1

    def campaign(self, rows) -> None:
        with self._lock:
            self._campaign[rows] = True

    def isolate(self, rows, on: bool = True) -> None:
        """Fault injection: cut rows off the network."""
        with self._lock:
            self._isolate[rows] = on

    def propose(self, row: int, data: bytes, etype: int = 0) -> None:
        """Queue a payload; it is appended (and assigned an index) in a
        round where this row is leader. `etype` tags non-Normal entries
        (conf changes) — the tag rides the host arena, never the
        device. Callers that need follower forwarding do it above this
        layer (see batched/node.py)."""
        # Enqueue timestamp rides the queue tuple only when tracing is
        # on (the span's propose stamp — sampling is decided later, at
        # index-assignment time, because the index IS the sample key).
        t_enq = 0 if self.tracer is None else time.monotonic_ns()
        with self._lock:
            self._props[row].append((data, int(etype), t_enq))

    def set_membership(self, row: int, voters, voters_out=(),
                       learners=(), joint: bool = False) -> None:
        """Upload new membership masks for one row — the confchange
        apply point (ref: confchange/confchange.go; the host computes
        slot sets, the device sees only masks).

        STAGED, not applied in place: callers run on apply/transport
        threads, and a read-modify-write of self.state here races the
        round thread's state swap in advance_round — the loser's
        update is silently lost (observed in the wild as a leader whose
        mask never admitted a new member, leaving the joiner dark
        forever). Masks are applied at the head of the next round, on
        the round thread, preserving the documented 'read by the next
        round' semantics."""
        r = self.cfg.num_replicas

        def mask(slots) -> np.ndarray:
            m = np.zeros((r,), bool)
            m[list(slots)] = True
            return m

        with self._lock:
            self._pending_conf[row] = (
                mask(voters), mask(voters_out), mask(learners),
                bool(joint),
            )

    def set_membership_many(self, rows, voter, voter_out, learner,
                            joint) -> None:
        """Bulk set_membership: stage mask planes for many rows under
        ONE lock acquisition — the conf-apply fast path when thousands
        of groups reconfigure in the same round (the hosting layer
        hands the GroupConfStore mask planes straight through). Same
        staged semantics: the device edit lands at the head of the next
        round on the round thread, as one vectorized ``.at[rows].set``.
        """
        rows = np.asarray(rows, np.int64)
        voter = np.asarray(voter, bool)
        voter_out = np.asarray(voter_out, bool)
        learner = np.asarray(learner, bool)
        joint = np.asarray(joint, bool)
        with self._lock:
            for i, row in enumerate(rows.tolist()):
                self._pending_conf[row] = (
                    voter[i], voter_out[i], learner[i], bool(joint[i]),
                )

    def transfer_leader(self, row: int, target_slot: int) -> None:
        """Stage a leadership handoff request on a leader row
        (ref: raft.go:1339 MsgTransferLeader; device _control phase)."""
        with self._lock:
            self._transfer[row] = target_slot + 1

    def read_index(self, row: int) -> None:
        """Stage a ReadIndex batch request on a leader row; the
        confirmed (seq, index) surfaces in BatchedReady.read_states
        (ref: raft.go:1078 MsgReadIndex → Ready.ReadStates)."""
        with self._lock:
            self._read_req[row] = True

    def set_fence(self, row: int, on: bool) -> None:
        """Stage a durability-fence flip for one row (hosting layer:
        lift when the durable log is back at the watermark, re-arm on
        a detected regression). STAGED like set_membership — the state
        edit lands at the head of the next round on the round thread,
        never racing the round's state swap."""
        with self._lock:
            self._pending_fence[row] = bool(on)

    def watch_set(self, row: int, wslot: int, key_hash: int) -> None:
        """Stage an exact-key watch into plane watch slot ``wslot`` of
        ``row`` (0 disarms). STAGED like set_fence: the device edit
        lands at the head of the next round on the round thread."""
        assert self.plane is not None, "apply plane is off"
        assert 0 <= wslot < self.cfg.apply_watch_slots
        with self._lock:
            self._pending_watch[(int(row), int(wslot))] = int(key_hash)

    def plane_restore_row(self, row: int, kv_key, kv_rev, kv_val,
                          kv_lease, rev: int, tick: int,
                          overflow: bool, applied: int = 0,
                          lessor=()) -> None:
        """Stage a full plane-row image (snapshot install / boot
        rebuild): fixed-width [C] i32 vectors + scalars, applied on the
        round thread before the next dispatch. ``applied`` is the
        highest entry index the image covers (dispatch skips at-or-
        below it); ``lessor`` is the row's (key bytes, expiry tick)
        mirror entries."""
        assert self.plane is not None, "apply plane is off"
        c = self.cfg.apply_capacity
        img = tuple(np.asarray(x, np.int32).reshape(c)
                    for x in (kv_key, kv_rev, kv_val, kv_lease))
        with self._lock:
            self._pending_plane_rows[int(row)] = img + (
                int(rev), int(tick), bool(overflow), int(applied),
                [(bytes(k), int(e)) for k, e in lessor])

    def drain_plane_events(self) -> List[Tuple[int, int, int, int, int]]:
        """Pop every pending (row, op, key_hash, rev, wmask) watch
        event (round thread appends; any thread drains — deque ops are
        GIL-atomic)."""
        if self.plane is None:
            return []
        evs = []
        try:
            while True:
                evs.append(self.plane_events.popleft())
        except IndexError:
            pass
        return evs

    def plane_capture(self, rows) -> List[Dict[str, object]]:
        """Snapshot-capture gather: ONE padded device gather for the
        whole build batch (hosting's _build_snapshots seam — the host
        dict walk does not survive large G). Returns one JSON-ready
        dict per requested row. Safe from any thread: _plane_mu
        excludes the dispatch that donates the plane carry."""
        assert self.plane is not None, "apply plane is off"
        from .applyplane import gather_rows

        rows = np.asarray(rows, np.int32).reshape(-1)
        m = len(rows)
        pad = np.zeros(max(m, 1), np.int32)
        pad[:m] = rows
        with self._plane_mu:
            g = gather_rows(self.plane, pad)
            jax.block_until_ready(g[0])
            parts = [np.asarray(x) for x in g]
            applied = self.m_plane_applied[rows].tolist()
            tick = self.m_plane_tick[rows].tolist()
            less = {int(r): [] for r in rows}
            for (r2, kb), exp in list(self.plane_lessor.items()):
                if r2 in less:
                    less[r2].append((kb, exp))
        kk, kr, kv, kl, rv, tk, ov = parts
        out = []
        for j, r in enumerate(rows.tolist()):
            out.append({
                "kv_key": kk[j].tolist(), "kv_rev": kr[j].tolist(),
                "kv_val": kv[j].tolist(), "kv_lease": kl[j].tolist(),
                "rev": int(rv[j]), "tick": int(tick[j]),
                "overflow": bool(ov[j]), "applied": int(applied[j]),
                "lessor": [[kb.hex(), int(e)] for kb, e in less[r]],
            })
        return out

    def pending_proposals(self, row: int) -> int:
        with self._lock:
            return len(self._props[row])

    def step(self, row: int, m: Message) -> None:
        """Stage an inbound wire message for `row`. MsgApp entry
        payloads go to the arena; MsgSnap app-state restore must already
        have happened (hosting layer) — here we stage the device-side
        ring restore."""
        t = int(m.type)
        lane = _LANE.get(t)
        if lane is None:
            raise ValueError(f"unroutable message type {m.type!r}")
        from_slot = m.from_ - 1
        if t == T_APP:
            with self._lock:
                ar = self.arena[row]
                et = self.etypes[row]
                for e in m.entries:
                    # Never clobber a committed entry's payload with a
                    # conflicting (necessarily stale) one — committed
                    # entries are immutable; only fill gaps there
                    # (post-snapshot resends).
                    if e.index > self._commit_guard[row] or e.index not in ar:
                        ar[e.index] = (e.term, e.data)
                        et.pop(e.index, None)
                        if int(e.type):
                            et[e.index] = int(e.type)
        if t == T_SNAP and m.index == 0:
            # Device ring-floor metadata normally rides in index/log_term
            # (the app snapshot in m.snapshot may sit at a HIGHER applied
            # index); fall back to the snapshot metadata when a caller
            # only filled the Snapshot (host-raft senders).
            m = Message(
                type=m.type, to=m.to, from_=m.from_, term=m.term,
                log_term=m.snapshot.metadata.term,
                index=m.snapshot.metadata.index,
            )
        with self._lock:
            self._pending.setdefault((row, from_slot, lane), deque()).append(m)

    def step_block(self, blk: MsgBlock) -> None:
        """Stage a batch of payload-free inbound messages (the SoA wire
        fast path — see msgblock.py). One lock acquisition per batch.

        Records are validated HERE, at ingest: row/frm/lane/type come
        straight off the wire, and a malformed record would otherwise
        crash the round loop (IndexError in _build_inbox) or scatter a
        forged message into another group's inbox slot via negative
        flat-index wraparound. Invalid records are dropped, matching
        the object path's corrupt-frame-drop semantics."""
        blk = validate_block(blk, self.n, self.cfg.num_replicas,
                             self.cfg.max_ents_per_msg)
        if len(blk) == 0:
            return
        with self._lock:
            self._blocks.append(blk)

    def install_snapshot_state(self, row: int, index: int,
                               applied_data_restored: bool = True) -> None:
        """Hosting layer notifies that app state for `row` was restored
        at `index` (from an inbound snapshot): advance the host applied
        watermark and drop arena entries at/below it."""
        with self._lock:
            if index > self.applied[row]:
                self.applied[row] = index
            ar = self.arena[row]
            for i in [i for i in ar if i <= index]:
                del ar[i]
                self.etypes[row].pop(i, None)

    def has_work(self) -> bool:
        with self._lock:
            if (
                self._pending or self._blocks or self._poked
                or self._pending_conf or self._pending_compact
                or self._pending_fence
                or (self.plane is not None
                    and (self._pending_watch
                         or self._pending_plane_rows))
                or self._ticks.any()
                or self._campaign.any()
                or self._transfer.any()
                or self._read_req.any()
            ):
                return True
            props = np.fromiter(
                (bool(q) for q in self._props), bool, count=self.n
            )
            return bool((props & (self.m_role == LEADER)).any())

    # -- the round -------------------------------------------------------------

    def advance_round(self) -> BatchedReady:
        """One round: stage, run the device round, read back, extract.
        Each phase is one span of the round-span recorder (PHASES;
        under the hosting layer they are children of ``member.round``
        and take its ``(member, round)``); ``phase_last`` and
        ``phase_total`` are set from the spans' own clock reads."""
        with spans.phases("rawnode.", 0, self.phase_total["rounds"],
                          cpu=CPU_PHASES) as ph:
            rd = self._advance_round(ph)
        dur, tot, last = ph.dur, self.phase_total, self.phase_last
        for p in PHASES:
            tot[p] += dur[p] / 1e9
        last["step"] = sum(dur[p] for p in STEP_PHASES) / 1e9
        tot["step"] += last["step"]
        tot["rounds"] += 1
        for p in ("stage", "extract", "collect"):
            last[p] = dur[p] / 1e9
        return rd

    def _advance_round(self, ph: "spans.Phases") -> BatchedReady:
        assert self._round is None, "previous round not advanced"
        cfg = self.cfg
        r, e, w = cfg.num_replicas, cfg.max_ents_per_msg, cfg.window
        tracer = self.tracer
        # The tracer's stage / dispatch / extract stamps are the starts
        # of the rawnode.stage / .h2d / .extract spans (one clock, one
        # reading: a sampled proposal joins its round by the instant).
        ph.next("stage_lock")
        self._lock.acquire()
        try:
            tr_stage = ph.next("stage").t0
            inbox = self._build_inbox()
            ticks = self._ticks > 0
            self._ticks = np.maximum(self._ticks - 1, 0)
            camp = self._campaign.copy()
            self._campaign[:] = False
            iso = self._isolate.copy()
            transfer = self._transfer.copy()
            self._transfer[:] = 0
            read_req = self._read_req.copy()
            self._read_req[:] = False
            poke_rows = (
                np.nonzero(self._poke_rows)[0] if self._poked else None
            )
            self._poke_rows[:] = False
            self._poked = False
            pend_conf = self._pending_conf
            self._pending_conf = {}
            pend_compact = self._pending_compact
            self._pending_compact = {}
            pend_fence = self._pending_fence
            self._pending_fence = {}
            pend_watch = pend_plane = None
            if self.plane is not None:
                pend_watch = self._pending_watch
                self._pending_watch = {}
                pend_plane = self._pending_plane_rows
                self._pending_plane_rows = {}
            props_n = np.fromiter(
                (min(len(q), cfg.max_props_per_round) for q in self._props),
                np.int32, count=self.n,
            )
        finally:
            self._lock.release()
        ph.next("edits")

        # Host-staged device-state edits (membership masks, ring-floor
        # compaction, bcastAppend pokes), applied here on the round
        # thread — the only writer of self.state.
        conf_rows = None  # rows whose membership masks flip this round
        if pend_conf:
            st0 = self.state
            rows2 = np.fromiter(pend_conf, np.int32, len(pend_conf))
            vin = np.stack([pend_conf[r2][0] for r2 in rows2])
            vout = np.stack([pend_conf[r2][1] for r2 in rows2])
            lrn = np.stack([pend_conf[r2][2] for r2 in rows2])
            jnt = np.fromiter(
                (pend_conf[r2][3] for r2 in rows2), bool, len(rows2))
            ridx = jnp.asarray(rows2)
            self.state = st0._replace(
                voter=st0.voter.at[ridx].set(jnp.asarray(vin)),
                voter_out=st0.voter_out.at[ridx].set(jnp.asarray(vout)),
                learner=st0.learner.at[ridx].set(jnp.asarray(lrn)),
                in_joint=st0.in_joint.at[ridx].set(jnp.asarray(jnt)),
            )
            conf_rows = rows2
        if pend_fence:
            st0 = self.state
            rows2 = np.fromiter(pend_fence, np.int32, len(pend_fence))
            vals = np.fromiter((pend_fence[int(r2)] for r2 in rows2),
                               bool, len(rows2))
            self.state = st0._replace(
                fenced=st0.fenced.at[jnp.asarray(rows2)]
                .set(jnp.asarray(vals)),
            )
        if pend_compact:
            for row2, want in pend_compact.items():
                # No round in flight here (asserted above): the commit
                # watermark and floor mirrors are current.
                idx = int(min(want, int(self.m_commit[row2])))
                if idx <= int(self.m_snap[row2]):
                    continue
                t2 = int(self.latest_ring()[row2, idx % cfg.window])
                st0 = self.state
                self.state = st0._replace(
                    snap_index=st0.snap_index.at[row2].set(idx),
                    snap_term=st0.snap_term.at[row2].set(t2),
                )
                self.m_snap[row2] = max(self.m_snap[row2], idx)
        if poke_rows is not None and len(poke_rows):
            st0 = self.state
            self.state = st0._replace(
                send_append=st0.send_append.at[jnp.asarray(poke_rows)]
                .set(True)
            )
        # Staged plane edits (watch arms, snapshot-restored row
        # images) — the round thread is the only writer of self.plane,
        # same contract as self.state above.
        if pend_watch:
            keys = list(pend_watch)
            wr = jnp.asarray([k[0] for k in keys], jnp.int32)
            wc = jnp.asarray([k[1] for k in keys], jnp.int32)
            wv = jnp.asarray([pend_watch[k] for k in keys], jnp.int32)
            self.plane = self.plane._replace(
                watch_key=self.plane.watch_key.at[wr, wc].set(wv))
        if pend_plane:
            pl = self.plane
            rows2 = np.fromiter(pend_plane, np.int32, len(pend_plane))
            imgs = [pend_plane[int(r2)] for r2 in rows2]
            ridx = jnp.asarray(rows2)
            as_j = lambda i: jnp.asarray(  # noqa: E731
                np.stack([im[i] for im in imgs]))
            sc = lambda i, dt=np.int32: jnp.asarray(  # noqa: E731
                np.fromiter((im[i] for im in imgs), dt, len(imgs)))
            with self._plane_mu:
                self.plane = pl._replace(
                    kv_key=pl.kv_key.at[ridx].set(as_j(0)),
                    kv_rev=pl.kv_rev.at[ridx].set(as_j(1)),
                    kv_val=pl.kv_val.at[ridx].set(as_j(2)),
                    kv_lease=pl.kv_lease.at[ridx].set(as_j(3)),
                    rev=pl.rev.at[ridx].set(sc(4)),
                    tick=pl.tick.at[ridx].set(sc(5)),
                    overflow=pl.overflow.at[ridx].set(sc(6, bool)),
                )
                for r2 in rows2.tolist():
                    im = pend_plane[int(r2)]
                    self.m_plane_tick[r2] = im[5]
                    self.m_plane_applied[r2] = im[7]
                    # Lessor swap: drop every entry for the row, then
                    # install the image's (built as a list first — no
                    # structural iteration over a dict readers get()
                    # from).
                    stale = [k for k in self.plane_lessor
                             if k[0] == int(r2)]
                    for k in stale:
                        del self.plane_lessor[k]
                    for kb, exp in im[8]:
                        self.plane_lessor[(int(r2), kb)] = exp
        tr_dispatch = ph.next("h2d").t0
        # Host->device staging happens OUTSIDE the transfer guard (it
        # is the intended, bulk transfer of the round); the guarded
        # region below is then pure warm device dispatch, where any
        # implicit transfer is a smuggled per-round sync and fails hard
        # under ETCD_TPU_TRANSFER_GUARD=disallow (analysis.sentinels).
        dev_in = (
            self._dev(ticks), self._dev(camp),
            self._dev(props_n), self._dev(iso),
            self._dev(transfer), self._dev(read_req),
        )
        ph.next("dispatch")
        with warm_guard(self._wkey_step):
            step_out = self._step(self.state, inbox, *dev_in)
            st, outbox, aux = step_out[:3]
            frame = step_out[3] if cfg.telemetry else None
            self.state = st
            # On-device outbox packing: a tiny second program turns the
            # [n, R, K] outbox fields into wire-width record words (rows
            # of msgblock.REC_DTYPE bytes) plus block/object masks, so
            # the host-side collect below is one view-cast + boolean
            # take instead of 14 fancy-indexed gathers.
            words_d, simple_d, cplx_d = pack_outbox(outbox, self._slots_j)

        # Device→host reads: one np.asarray per buffer after one fence.
        ph.next("fence")
        jax.block_until_ready(st.term)
        ph.next("d2h")
        (term, vote, commit, last, role, lead, snap_i, snap_t, ring,
         rd_seq, rd_idx, rd_ready,
         mid_seq, mid_idx, mid_ready, last_tick, lease_tk) = [
            np.asarray(x) for x in (
                st.term, st.vote, st.commit, st.last, st.role, st.lead,
                st.snap_index, st.snap_term, st.log_term,
                st.read_seq, st.read_index, st.read_ready,
                aux.read_seq, aux.read_index, aux.read_ready,
                aux.last_tick, st.lease_ticks,
            )
        ]
        words = np.asarray(words_d)
        simple = np.asarray(simple_d)
        cplx = np.asarray(cplx_d)
        if frame is not None:
            # Same host gather as the state reads above — the counters
            # were accumulated in-kernel; no extra sync happens here.
            tel_counters = np.asarray(frame.counters)
            tel_inv = np.asarray(frame.invariants)
            if conf_rows is not None and len(conf_rows):
                # Host-populated column (see telemetry.TM_NAMES): the
                # membership masks of these rows flipped at the head of
                # THIS round — count them where they were staged so the
                # flight recorder shows per-group conf applies in the
                # same frame stream as the device events.
                from .telemetry import TM_INDEX

                tel_counters = tel_counters.copy()
                tel_counters[np.asarray(conf_rows, np.int64),
                             TM_INDEX["conf_changes_applied"]] += 1
            self.last_frame = (tel_counters, tel_inv)
            if self.telemetry_hub is not None:
                from .telemetry import lane_summary

                self.telemetry_hub.ingest_round(
                    tel_counters, tel_inv,
                    extra={"outbox_lanes": lane_summary(
                        np.asarray(outbox.valid))})
        if cfg.fleet_summary:
            # Same host gather as the state reads above — the frame is
            # a round output already on device; no extra sync happens.
            fleet_vec = np.asarray(step_out[self._fleet_idx])
            self.last_fleet = fleet_vec
            if self.fleet_hub is not None:
                self.fleet_hub.ingest_round(fleet_vec)
        tr_extract = ph.next("extract").t0

        # Rows that left LEADER: the role read back against the mirror
        # of the round before.
        lost = int(((self.m_role == LEADER) & (role != LEADER)).sum())
        term = term.astype(np.int64)
        vote = vote.astype(np.int64)
        commit = commit.astype(np.int64)
        last = last.astype(np.int64)
        ring64 = ring.astype(np.int64)

        # Everything below reads/writes the arena, so it runs under
        # _lock: inbound transport threads (step) must neither clobber
        # payloads mid-drain nor observe half-assigned proposals.
        with self._lock:
            # Freeze arena immutability at this round's commit BEFORE
            # reading payloads out (see step()'s _commit_guard check).
            self._commit_guard = np.maximum(self._commit_guard, commit)

            # -- proposals: pop exactly as many as the device appended
            # and assign their indexes (the propose phase spans
            # (last_tick, last]).
            for row in np.nonzero(last > last_tick)[0].tolist():
                q = self._props[row]
                n_app = int(last[row] - last_tick[row])
                base = int(last_tick[row])
                t_row = int(term[row])
                g_row = int(self.groups[row])
                ar = self.arena[row]
                ets = self.etypes[row]
                for j in range(n_app):
                    data, et, t_enq = q.popleft()
                    idx = base + 1 + j
                    ar[idx] = (t_row, data)
                    ets.pop(idx, None)
                    if et:
                        ets[idx] = et
                    if (tracer is not None and t_enq
                            and tracer.sampled(g_row, idx)):
                        # The origin stamp: index just got assigned, so
                        # the sampling decision exists only now; the
                        # stamp's time is the client enqueue instant.
                        tracer.stamp(g_row, t_row, idx, "propose", t_enq)

            # -- entry records to persist: per row the contiguous range
            # (lo-1, last] where lo is the first ring-changed index
            # this round (or stable+1) — range math fully vectorized,
            # Python only touches the actual entries (payload lookups).
            snap64 = snap_i.astype(np.int64)
            snap_rows = np.nonzero(snap64 > self.m_last)[0]
            # Device installed snapshots past our old log: ring floor
            # jumped. Record them; entries beyond follow.
            snapshots: List[Tuple[int, int, int]] = [
                (row, int(snap_i[row]), int(snap_t[row]))
                for row in snap_rows.tolist()
            ]
            restored = np.zeros(self.n, bool)
            restored[snap_rows] = True
            changed = ring64 != self.m_ring
            rows_changed = np.nonzero(
                changed.any(axis=1) | (last > self.stable) | restored
            )[0]
            entries = EntryBatch()
            if len(rows_changed):
                lastc = last[rows_changed]
                snapc = snap64[rows_changed]
                wgrid = np.arange(w, dtype=np.int64)
                # Log index currently held by ring slot p of each row.
                idxs = lastc[:, None] - ((lastc[:, None] - wgrid) % w)
                big = np.int64(1) << 62
                cand = np.where(
                    changed[rows_changed] & (idxs > snapc[:, None]),
                    idxs, big)
                lo = np.minimum(
                    self.stable[rows_changed] + 1, cand.min(axis=1))
                lo = np.maximum(lo, snapc + 1)
                cnt = np.maximum(lastc - lo + 1, 0)
                sel = cnt > 0
                if sel.any():
                    rows2 = rows_changed[sel]
                    cnt2 = cnt[sel]
                    eb_rows = np.repeat(rows2, cnt2)
                    eb_idx = ragged_ranges(lo[sel], cnt2)
                    eb_term = ring64[eb_rows, eb_idx % w]
                    datas: List[bytes] = []
                    etys: List[int] = []
                    for row, i, t in zip(eb_rows.tolist(),
                                         eb_idx.tolist(),
                                         eb_term.tolist()):
                        a = self.arena[row].get(i)
                        if a is not None and a[0] == t:
                            datas.append(a[1])
                            etys.append(self.etypes[row].get(i, 0))
                        else:
                            datas.append(b"")
                            etys.append(0)
                    entries = EntryBatch(
                        eb_rows, eb_idx, eb_term,
                        np.asarray(etys, np.int64), datas)

            # Sampled trace keys among this round's persisted entries
            # (leader appends and follower appends alike — both sides'
            # fragments come from the same extraction path): stamp the
            # round phases, hand the keys to the hosting layer for the
            # fsync/send stamps.
            traced_entries: List[Tuple[int, int, int]] = []
            if tracer is not None and len(entries):
                hits = np.nonzero(tracer.sampled_arr(
                    self.groups[entries.rows], entries.idx))[0]
                if len(hits):
                    traced_entries = list(zip(
                        self.groups[entries.rows[hits]].tolist(),
                        entries.term[hits].tolist(),
                        entries.idx[hits].tolist()))
                    tracer.stamp_many(traced_entries, "stage", tr_stage)
                    tracer.stamp_many(traced_entries, "dispatch",
                                      tr_dispatch)
                    tracer.stamp_many(traced_entries, "extract",
                                      tr_extract)

            # -- hardstate deltas
            hardstates = [
                (int(row), int(term[row]), int(vote[row]), int(commit[row]))
                for row in np.nonzero(
                    (term != self.m_term) | (vote != self.m_vote)
                    | (commit != self.m_commit)
                )[0]
            ]

            # -- committed ranges (applied, commit]
            committed: List[
                Tuple[int, List[Tuple[int, int, Optional[bytes]]]]
            ] = []
            traced_commit: List[Tuple[int, int, int]] = []
            com_rows = np.nonzero(commit > self.applied)[0]
            if len(com_rows):
                loc = np.maximum(self.applied[com_rows], snap64[com_rows])
                cntc = np.maximum(commit[com_rows] - loc, 0)
                selc = cntc > 0
                rows3 = com_rows[selc]
                cnt3 = cntc[selc]
                c_rows = np.repeat(rows3, cnt3)
                c_idx = ragged_ranges(loc[selc] + 1, cnt3)
                c_term = ring64[c_rows, c_idx % w]
                idx_l = c_idx.tolist()
                term_l = c_term.tolist()
                pos = 0
                for row, end in zip(rows3.tolist(),
                                    np.cumsum(cnt3).tolist()):
                    ar = self.arena[row]
                    ets = self.etypes[row]
                    items: List[Tuple[int, int, Optional[bytes], int]] = []
                    for k in range(pos, end):
                        i, t = idx_l[k], term_l[k]
                        a = ar.get(i)
                        ok = a is not None and a[0] == t
                        items.append((
                            i, t,
                            a[1] if ok and a[1] else None,
                            ets.get(i, 0) if ok else 0,
                        ))
                    pos = end
                    committed.append((row, items))
                if tracer is not None and len(c_idx):
                    hits = np.nonzero(tracer.sampled_arr(
                        self.groups[c_rows], c_idx))[0]
                    if len(hits):
                        traced_commit = list(zip(
                            self.groups[c_rows[hits]].tolist(),
                            c_term[hits].tolist(),
                            c_idx[hits].tolist()))
                        # Commit became observable at extraction time.
                        tracer.stamp_many(traced_commit, "commit",
                                          tr_extract)

            # -- outbound messages (MsgApp payloads come from the arena)
            ph.next("collect")
            msg_block, messages = self._collect_messages(
                words, simple, cplx, outbox
            )
            ph.end()

        must_sync = bool(
            entries
            or any(
                term[row] != self.m_term[row] or vote[row] != self.m_vote[row]
                for row, *_ in hardstates
            )
        )

        # Batches opened this round, then newly quorum-confirmed ones
        # (each surfaces exactly once; ref: read_only.go advance →
        # Ready.ReadStates).
        read_opened: List[Tuple[int, int]] = []
        for row in np.nonzero(rd_seq > self._read_seq_prev)[0]:
            read_opened.append((int(row), int(rd_seq[row])))
            self._read_seq_prev[row] = int(rd_seq[row])
        read_states: List[Tuple[int, int, int]] = []
        # Mid-round confirmations first (a latched reopen in _control
        # may have already replaced them in the end-of-round state).
        for row in np.nonzero(mid_ready & (mid_seq > self._read_seen))[0]:
            read_states.append(
                (int(row), int(mid_seq[row]), int(mid_idx[row])))
            self._read_seen[row] = int(mid_seq[row])
        newly = np.nonzero(rd_ready & (rd_seq > self._read_seen))[0]
        for row in newly:
            read_states.append(
                (int(row), int(rd_seq[row]), int(rd_idx[row])))
            self._read_seen[row] = int(rd_seq[row])

        # Apply-plane dispatch: fold this round's committed entries
        # (payload bytes in hand from the extraction above) and staged
        # ticks into the device KV/watch/lease tensors. After the lock:
        # it reads only local extraction results and self.plane, whose
        # single writer is this thread.
        if self.plane is not None and (committed or ticks.any()):
            self._plane_dispatch(committed, ticks)

        self._round = (term, vote, commit, last, role, lead,
                       snap_i.astype(np.int64), ring64,
                       lease_tk.astype(np.int64))
        snap_rings = {
            row: ring64[row].copy()
            for row, m in messages if int(m.type) == T_SNAP
        }
        return BatchedReady(
            hardstates=hardstates,
            entries=entries,
            snapshots=snapshots,
            committed=committed,
            messages=messages,
            must_sync=must_sync,
            msg_block=msg_block,
            read_states=read_states,
            read_opened=read_opened,
            snap_rings=snap_rings,
            traced_entries=traced_entries,
            traced_commit=traced_commit,
            leader_losses=lost,
        )

    def advance(self) -> None:
        """Confirm the last Ready: host mirrors move to the new state
        (ref: rawnode.go:174-179 Advance)."""
        assert self._round is not None
        (term, vote, commit, last, role, lead, snap_i, ring64,
         lease_tk) = self._round
        with self._lock:
            # Under _lock: transport threads mutate self.applied via
            # install_snapshot_state, and read the mirrors.
            self.m_term, self.m_vote, self.m_commit = term, vote, commit
            self.m_last, self.m_role, self.m_lead = last, role, lead
            self.m_view = (term, role, lead)
            self.m_snap, self.m_ring = snap_i, ring64
            self.m_lease_ticks = lease_tk
            self.applied = np.maximum(self.applied, commit)
            self.stable = last.copy()
            # GC arena below the compaction floor.
            for row in range(self.n):
                fl = int(min(self.applied[row], snap_i[row]))
                ar = self.arena[row]
                if len(ar) > 2 * self.cfg.window:
                    for i in [i for i in ar if i <= fl]:
                        del ar[i]
                        self.etypes[row].pop(i, None)
            self._round = None

    # -- internals -------------------------------------------------------------

    def _plane_dispatch(self, committed, ticks: np.ndarray) -> None:
        """Fold one round's committed KV payloads + staged ticks into
        the device apply plane (round thread only). Rows committing
        more than A = cfg.apply_records entries redispatch the same
        compiled program with the next record chunk — shape-static by
        construction; the tick advance rides chunk 0 only."""
        from .applyplane import OP_PUT, fnv1a32, parse_payload

        cfg = self.cfg
        a, n = cfg.apply_records, self.n
        new_tick = self.m_plane_tick + ticks.astype(np.int64)
        recs: Dict[int, List[Tuple[int, int, int, int]]] = {}
        lessor = self.plane_lessor
        for row, items in committed:
            lst = []
            floor = int(self.m_plane_applied[row])
            top = floor
            for i, _t, d, et in items:
                if i <= floor:
                    # Already folded (a restored plane image can lead
                    # the host apply watermark; the boot replay and
                    # post-install tail re-deliver that span).
                    continue
                top = max(top, int(i))
                if et != 0 or not d:
                    # Conf entries and unknown payloads (arena holes)
                    # skip the KV tier — exactly the host loop's rule.
                    continue
                p = parse_payload(d)
                if p is None:
                    continue
                op, k, v, ttl = p
                lst.append((op, fnv1a32(k),
                            fnv1a32(v) if op == OP_PUT else 0,
                            ttl if op == OP_PUT else 0))
                # Lessor mirror: same record, same tick arithmetic as
                # the device kernel (chunk 0 advances the clock, so
                # every chunk applies at new_tick).
                if op == OP_PUT and ttl > 0:
                    lessor[(row, k)] = int(new_tick[row]) + ttl
                else:
                    lessor.pop((row, k), None)
            if top > floor:
                self.m_plane_applied[row] = top
            if lst:
                recs[row] = lst
        longest = max((len(v) for v in recs.values()), default=0)
        nchunks = max(1, -(-longest // a))
        stats = self.plane_stats
        self.m_plane_tick = new_tick
        frames = []
        with self._plane_mu:
            for ci in range(nchunks):
                ops = np.zeros((n, a), np.int32)
                keys = np.zeros((n, a), np.int32)
                vals = np.zeros((n, a), np.int32)
                ttls = np.zeros((n, a), np.int32)
                for row, lst in recs.items():
                    for j, (op, k, v, ttl) in enumerate(
                            lst[ci * a:(ci + 1) * a]):
                        ops[row, j] = op
                        keys[row, j] = k
                        vals[row, j] = v
                        ttls[row, j] = ttl
                ta = (ticks.astype(np.int32) if ci == 0
                      else np.zeros(n, np.int32))
                # Host→device staging outside the guard (the intended
                # bulk transfer); the guarded dispatch is pure warm
                # device work. Frame drain waits until AFTER the chunk
                # loop — one bulk sync per round, not one per chunk.
                din = tuple(jnp.asarray(x)
                            for x in (ops, keys, vals, ttls, ta))
                with warm_guard(self._wkey_plane):
                    self.plane, frame = self._plane_step(self.plane,
                                                         *din)
                frames.append(frame)
            jax.block_until_ready(self.plane.rev)
        got = jax.device_get(frames)
        for frame in got:
            stats["dispatches"] += 1
            stats["puts"] += int(frame.puts.sum())
            stats["dels"] += int(frame.dels.sum())
            stats["expired"] += int(frame.expired.sum())
            stats["slots_hw"] = max(
                stats["slots_hw"], int(frame.slots_used.max()))
            stats["overflow_rows"] = int(frame.overflow.sum())
            stats["active_leases"] = int(frame.leases.sum())
            hit = (frame.ev_op != 0) & (frame.ev_wmask != 0)
            rws, lanes = np.nonzero(hit)
            if len(rws):
                for r2, l2 in zip(rws.tolist(), lanes.tolist()):
                    self.plane_events.append((
                        int(r2), int(frame.ev_op[r2, l2]),
                        int(frame.ev_key[r2, l2]),
                        int(frame.ev_rev[r2, l2]),
                        int(frame.ev_wmask[r2, l2])))
                stats["watch_events"] += len(rws)

    # Residual block records are bounded: raft tolerates message loss,
    # so once the residual queue exceeds this many records per inbox
    # key on average, the OLDEST blocks are dropped (a key contested by
    # a busy object-path append stream would otherwise accumulate
    # residuals without bound — ADVICE r04).
    _RESIDUAL_RECORDS_PER_KEY = 4

    def _build_inbox(self):
        """Pop at most one pending message per (row, sender, lane) into
        a dense inbox. Caller holds _lock.

        Object-path messages are drained BEFORE queued blocks, so a
        block record can be overtaken by a later object-path message
        for the same (row, sender, lane). That cross-channel reordering
        is intentional — it mirrors the reference's two rafthttp
        channels, which give no cross-channel ordering either (ref:
        server/etcdserver/api/rafthttp/peer.go:337-349); raft tolerates
        reordering and loss on every link."""
        cfg = self.cfg
        r, e = cfg.num_replicas, cfg.max_ents_per_msg
        shape = (self.n, r, NUM_KINDS)
        valid = np.zeros(shape, bool)
        # Bounded lanes stage at their narrow storage dtypes under
        # cfg.narrow_lanes (step.NARROW_MSG_DTYPES: wire types < 32,
        # n_ents <= 255) so the staged inbox matches the dtype the
        # compiled round expects; the kernel widens at deliver entry.
        typ = np.zeros(shape,
                       np.int8 if cfg.narrow_lanes else np.int32)
        term = np.zeros(shape, np.int32)
        log_term = np.zeros(shape, np.int32)
        index = np.zeros(shape, np.int32)
        commit = np.zeros(shape, np.int32)
        reject = np.zeros(shape, bool)
        reject_hint = np.zeros(shape, np.int32)
        n_ents = np.zeros(shape,
                          np.int16 if cfg.narrow_lanes else np.int32)
        ctx = np.zeros(shape, np.int32)
        ent_terms = np.zeros(shape + (e,), np.int32)
        dead = []
        for key, q in self._pending.items():
            row, s, lane = key
            m: Message = q.popleft()
            if not q:
                dead.append(key)
            valid[row, s, lane] = True
            typ[row, s, lane] = int(m.type)
            term[row, s, lane] = m.term
            log_term[row, s, lane] = m.log_term
            index[row, s, lane] = m.index
            commit[row, s, lane] = m.commit
            reject[row, s, lane] = m.reject
            reject_hint[row, s, lane] = m.reject_hint
            n_ents[row, s, lane] = len(m.entries)
            if len(m.context) == 4:
                ctx[row, s, lane] = int.from_bytes(m.context, "little")
            for j, ent in enumerate(m.entries[:e]):
                ent_terms[row, s, lane, j] = ent.term
        for key in dead:
            del self._pending[key]
        if self._blocks:
            def land_entries(blk: MsgBlock, land: np.ndarray) -> None:
                # A block MsgApp's payloads enter the arena the moment
                # the record lands in the inbox — the block twin of
                # step()'s arena writes, same never-clobber-committed
                # rule (committed entries are immutable; only fill
                # gaps there, post-snapshot resends). One bulk call per
                # block: the arena slices come straight off the flat
                # entry arena (offset math, no per-entry parsing).
                rec = blk.rec
                rows_l = rec["row"][land].tolist()
                base_l = rec["index"][land].tolist()
                cnt = blk.ent_counts[land]
                # Gather ONLY the landed records' arena rows before the
                # Python conversion — a residual-heavy block re-merges
                # every round and must not pay for its deferred tail.
                eidx = ragged_ranges(blk._ent_starts()[land], cnt)
                term_l = blk.ent_term[eidx].tolist()
                ety_l = blk.ent_etype[eidx].tolist()
                len_l = blk.ent_len[eidx].tolist()
                ps_l = blk._pay_starts()[eidx].tolist()
                pay = blk.payload
                k = 0
                for row, base, c in zip(rows_l, base_l, cnt.tolist()):
                    ar = self.arena[row]
                    et = self.etypes[row]
                    guard = self._commit_guard[row]
                    for j in range(c):
                        i2 = base + 1 + j
                        if i2 > guard or i2 not in ar:
                            a = ps_l[k]
                            ar[i2] = (term_l[k], pay[a:a + len_l[k]])
                            et.pop(i2, None)
                            if ety_l[k]:
                                et[i2] = ety_l[k]
                        k += 1

            residual = merge_blocks(
                list(self._blocks), r, NUM_KINDS,
                {"valid": valid, "type": typ, "term": term,
                 "log_term": log_term, "index": index, "commit": commit,
                 "reject": reject, "reject_hint": reject_hint,
                 "ctx": ctx, "n_ents": n_ents, "ent_terms": ent_terms},
                land_entries=land_entries,
            )
            cap = self._RESIDUAL_RECORDS_PER_KEY * self.n * r * NUM_KINDS
            while len(residual) > 1 and sum(map(len, residual)) > cap:
                residual.pop(0)  # drop oldest whole block (loss is safe)
            self._blocks = deque(residual)
        inbox = MsgSlots(
            valid=self._dev(valid), type=self._dev(typ),
            term=self._dev(term), log_term=self._dev(log_term),
            index=self._dev(index), commit=self._dev(commit),
            reject=self._dev(reject), reject_hint=self._dev(reject_hint),
            n_ents=self._dev(n_ents), ctx=self._dev(ctx),
            ent_terms=self._dev(ent_terms),
        )
        return inbox

    def _collect_messages(self, words, simple, cplx, outbox):
        """Device-packed outbox → one SoA block for everything except
        MsgSnap (whose app-state payload the hosting layer attaches at
        send time). The record array is a view-cast of the packed word
        tensor (step.pack_outbox) compressed by the block mask; MsgApp
        entry payloads ride the block's flat arena, re-attached from
        the host arena with one ragged gather for the terms and one
        payload join."""
        e = self.cfg.max_ents_per_msg
        rec = compact_records(words, simple)
        block = MsgBlock(rec)
        napp = rec["n_ents"]
        app_sel = np.nonzero(napp)[0]
        if len(app_sel):
            counts = napp[app_sel].astype(np.int64)
            # Flat outbox slot of each entry-carrying record (for the
            # [M, E] ent_terms gather) and its per-entry offsets.
            flat_pos = np.nonzero(simple)[0][app_sel]
            offs = ragged_ranges(np.zeros(len(app_sel), np.int64),
                                 counts)
            etf = np.asarray(outbox.ent_terms).reshape(-1, e)
            terms = etf[np.repeat(flat_pos, counts), offs]
            idx_flat = (np.repeat(rec["index"][app_sel].astype(np.int64),
                                  counts) + 1 + offs)
            rows_rep = np.repeat(rec["row"][app_sel].astype(np.int64),
                                 counts)
            datas: List[bytes] = []
            etys = np.zeros(len(idx_flat), "<u1")
            k = 0
            for row, idx, et in zip(rows_rep.tolist(),
                                    idx_flat.tolist(), terms.tolist()):
                a = self.arena[row].get(idx)
                if a is not None and a[0] == et:
                    datas.append(a[1])
                    ety = self.etypes[row].get(idx, 0)
                    if ety:
                        etys[k] = ety
                else:
                    datas.append(b"")
                k += 1
            block = MsgBlock(
                rec, ent_term=terms.astype("<u4"), ent_etype=etys,
                ent_len=np.fromiter(map(len, datas), np.uint32,
                                    len(datas)),
                payload=b"".join(datas))
        msgs: List[Tuple[int, Message]] = []
        if cplx.any():
            # MsgSnap only (rare): materialize just the needed fields
            # for just these flat slots.
            p = np.nonzero(cplx)[0]
            fld = lambda name: (  # noqa: E731
                np.asarray(getattr(outbox, name)).reshape(-1)[p].tolist())
            k6 = NUM_KINDS
            r = self.cfg.num_replicas
            rows_c = (p // (r * k6)).tolist()
            tgts_c = ((p % (r * k6)) // k6).tolist()
            typs, terms_c, lts, idxs, cms, rejs, hints, ctxs = (
                fld("type"), fld("term"), fld("log_term"), fld("index"),
                fld("commit"), fld("reject"), fld("reject_hint"),
                fld("ctx"))
            for j, row in enumerate(rows_c):
                t = int(typs[j])
                m = Message(
                    type=MessageType(t),
                    to=tgts_c[j] + 1,
                    from_=int(self.slots[row]) + 1,
                    term=terms_c[j],
                    log_term=lts[j],
                    index=idxs[j],
                    commit=cms[j],
                    reject=bool(rejs[j]),
                    reject_hint=hints[j],
                )
                cw = ctxs[j]
                if cw:
                    # The device ctx word travels as 4 context bytes
                    # (the reference's Message.Context).
                    m.context = int(cw).to_bytes(4, "little")
                if t == T_SNAP:
                    # metadata only; the hosting layer attaches app
                    # data (at its applied watermark ≥ this floor)
                    # before the wire (see hosting.py / node.py).
                    m.snapshot = Snapshot(
                        metadata=SnapshotMetadata(
                            index=idxs[j], term=lts[j],
                        )
                    )
                msgs.append((row, m))
        return block, msgs

    # -- introspection ---------------------------------------------------------

    def peer_match(self) -> np.ndarray:
        """Leader-side [n, R] match snapshot — the promote catch-up
        gate's input (server.go:1446 isLearnerReady reads the same
        progress view). A plain np.asarray of the live device buffer:
        zero-copy on CPU, one bulk fetch elsewhere; called at admin
        cadence, never on the round hot path. Rows this process does
        not lead carry reset-stale values — callers gate on leadership
        first."""
        return np.asarray(self.state.match)

    def latest_ring(self) -> np.ndarray:
        """The newest known [n, W] term ring (in-flight round if any)."""
        return self._round[7] if self._round is not None else self.m_ring

    def latest_commit(self, row: int) -> int:
        arr = self._round[2] if self._round is not None else self.m_commit
        return int(arr[row])

    def compact(self, row: int, index: int) -> None:
        """Move the device ring floor to `index` (host took an app
        snapshot there). STAGED like set_membership: the state edit
        happens at the head of the next round on the round thread (an
        in-place edit here would race the round's state swap). The
        floor only rises; the clamp to the committed watermark and the
        ring-term read happen at apply time, against that round's
        state."""
        with self._lock:
            self._pending_compact[row] = max(
                self._pending_compact.get(row, 0), int(index))

    def poke_append(self, row: int) -> None:
        """Stage an immediate append/probe to every replication target
        of `row` — the device twin of the leader's bcastAppend on a
        config change (ref: raft.go switchToConfig → maybeSendAppend):
        a newly admitted member must be contacted now, not at the next
        heartbeat timeout. Staged host-side and applied to device state
        at the head of the next advance_round (on the round thread), so
        callers on other threads never race the round's state swap."""
        with self._lock:
            self._poke_rows[row] = True
            self._poked = True

    def leader_rows(self) -> np.ndarray:
        return np.nonzero(self.m_role == LEADER)[0]

    def is_leader(self, row: int) -> bool:
        return self.m_role[row] == LEADER

    def lead(self, row: int) -> int:
        """Leader member id (slot+1) as known by `row`, 0 if unknown."""
        return int(self.m_lead[row])
