"""SoA state for the batched multi-Raft engine.

Layout: one *replica instance* per row. Instance ``i`` is replica slot
``i % R`` of group ``i // R``; the dense layout makes the network router
a transpose (see step.py). All arrays are int32/bool — terms, indexes
and counts fit comfortably, and int32 keeps the VPU lanes full.

State fields mirror the reference raft struct (ref: raft/raft.go:243-316)
and tracker.Progress (ref: raft/tracker/progress.go:30-80), with the
reference's per-peer maps flattened to ``[N, R]`` and the log flattened
to a ``[N, W]`` term ring (entry payloads live in the host arena; commit
decisions only ever touch (term, index), ref: SURVEY.md §7 "payload
bytes don't belong on the TPU").
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Role encoding (matches etcd_tpu.raft.StateType).
FOLLOWER, CANDIDATE, LEADER, PRECANDIDATE = 0, 1, 2, 3

# Progress state encoding (matches tracker.ProgressStateType).
PROBE, REPLICATE, SNAPSHOT = 0, 1, 2

I32 = jnp.int32
I16 = jnp.int16
I8 = jnp.int8

# The SoA wire record (msgblock.REC_DTYPE) packs the per-message entry
# count as one byte; a config exceeding this would silently wrap the
# count on the wire (E=256 reads back as 0 entries).
MAX_WIRE_ENTS = 255
# Up to here (floor(sqrt(2^31)); powers of two apart) the sum of two
# products of residues stays below 2^32 (rand_timeout).
MAX_HASHED_TIMEOUT = 46340
# A snapshot that states its configuration (cfg.replace_replicas) packs
# two masks into one int32, 16 bits each, the sign bit left alone.
MAX_SNAPSHOT_SLOTS = 15
# The most terms held as rings, all replicas' together (1 GiB of
# int32; BatchedConfig.log_runs holds any window): every ring operation
# is a pass over all W slots of every row, an append's write a
# [N, W, E] compare. The live cells' rings are 0.3 to 100 M terms
# (W = 32), a hosted single-group member's 32,768 a row of a few rows.
MAX_RING_TERMS = 1 << 28


class BatchedConfig(NamedTuple):
    """Static (compile-time) engine configuration."""

    num_groups: int
    # R: replica slots per group (<= 8: the quorum index is R*R compares
    # a call, kernels.quorum_committed, and route() R*R shifted slices)
    num_replicas: int
    window: int  # W: log-ring capacity per instance
    max_ents_per_msg: int  # E: entries carried by one MsgApp
    max_props_per_round: int  # P: proposals appended per instance per round
    election_timeout: int = 10
    heartbeat_timeout: int = 1
    max_inflight: int = 256
    pre_vote: bool = False
    check_quorum: bool = False
    # Advance snap_index toward the applied watermark each round,
    # keeping window//2 entries of tail for follower catch-up; laggards
    # beyond that take the snapshot path (ref: etcdserver's
    # SnapshotCount / CatchUpEntries policy, server.go:73,80).
    auto_compact: bool = False
    # Run the kernel with the instance axis MINOR ([R, N] / [W, N]
    # internally): on TPU the (8, 128) vector lanes then fill with the
    # huge N axis instead of the tiny R/W/K dims. The public layout
    # stays [N, ...]; the jitted round transposes at entry/exit.
    # bench.py runs this layout on the chip.
    lanes_minor: bool = False
    # The name of the one delivery order (step.py _deliver_vectorized;
    # the shadow oracle mirrors it): "vectorized", or "auto", which
    # resolved() maps to it. Not a choice any more: the field stays
    # because the benchmark's drivers and its frozen reference
    # (benchmark/drivers, benchmark/reference/shadow.py) pass and print
    # it and tests/obs/test_spans.py builds a config with it; ROADMAP
    # D1b removes it once a `benchmark` PR has dropped those reads.
    deliver_shape: str = "auto"
    # Store the bounded hot lanes (role/vote/lead enums, vote tallies,
    # progress states, inflight counts) in int8/int16 between rounds:
    # the round kernel widens them to i32 at entry and narrows at exit,
    # so the protocol math is bit-identical while the per-round state
    # carry (HBM traffic on TPU) shrinks. Absolute term/index
    # watermarks (term, commit, last, match, next, log_term ring) stay
    # int32 — narrowing those would change wrap semantics.
    narrow_lanes: bool = False
    # Kernel telemetry plane (see batched/telemetry.py): the round
    # emits one extra SoA output block — per-instance event counters
    # plus an on-device invariant bitmap — accumulated in-kernel with
    # no extra host sync. Static (compile-time): with telemetry=False
    # the compiled round program is UNCHANGED (the telemetry code is
    # never traced); with telemetry=True protocol state is
    # bit-identical (the frame only reads state).
    telemetry: bool = False
    # Fleet observatory plane (see obs/fleet.py): the round also emits
    # one flat fixed-shape SummaryFrame — log-bucketed commit-progress/
    # backlog/inflight histograms, leader/role/progress censuses, term
    # spread, a bounded groups×time heat strip, and a lax.top_k of the
    # worst-backlogged rows with identities — aggregated ON DEVICE so
    # fleet visibility never costs G host-side series. Same contract
    # as `telemetry`: static, default off, fleet_summary=False compiles
    # the identical program, fleet_summary=True keeps protocol state
    # bit-identical (the frame is a pure read of round inputs/outputs).
    fleet_summary: bool = False
    # Device-resident apply plane (see batched/applyplane.py): the L2
    # storage layer — a fixed-capacity per-group KV/revision hash-slot
    # store, watch predicates as masked compares, client-lease TTL
    # expiry, and leader leases for quorum-free linearizable reads —
    # maintained as device tensors by a SEPARATE jitted apply program
    # dispatched over each round's committed entries. Static plane
    # contract, enforced structurally: none of the apply_* fields
    # enters the round-step compile key (make_step_round normalizes
    # them to defaults before keying), so apply_plane=False compiles
    # the identical round program and apply_plane=True keeps protocol
    # state bit-identical by construction.
    apply_plane: bool = False
    # KV slots per group row (C). A row whose live keys exceed C sets
    # its overflow flag: the host GroupKV tier (always byte-truth)
    # covers reads and snapshot capture for that row; device counters
    # record the spill (capacity/overflow contract, README).
    apply_capacity: int = 256
    # Watch predicate slots per group row (exact-key-hash compares over
    # the apply stream); <= 32 so the per-record match set packs into
    # one i32 bitmap lane of the event frame.
    apply_watch_slots: int = 8
    # Apply records per plane dispatch (A). A round that commits more
    # than A entries for one row dispatches the SAME compiled program
    # again — a batching granule, not a cap.
    apply_records: int = 8
    # Minimum remaining leader-lease ticks for the hosting layer to
    # serve a linearizable read locally (host-side routing threshold;
    # the lease lane itself is part of the round program regardless).
    lease_read_margin: int = 2
    # Configuration changes as entries of the device's log (see
    # ConfLanes): a leader appends a change offered to it in the
    # control phase and every replica flips its own membership masks in
    # the round in which it applies that entry, with no host upload.
    # Static, default off, the contract of `telemetry`: off, the state
    # the state is a BatchedState as ever (on, a ConfBatchedState), no
    # message carries the marker, and the compiled round and closed
    # loop are the programs they were.
    conf_entries: bool = False
    # Replicas that are born and retired (needs conf_entries): a group
    # may start with an empty slot (init_state's `spare`), a change may
    # add a slot that is in nobody's masks as a learner (initProgress)
    # or swap a learner for a voter in one joint change of two ops
    # (conf_code's wide kinds), a snapshot states the configuration of
    # the index it stands at and the replica that restores it takes
    # that configuration (step._handle_snapshot), and the control phase
    # can reset a replica to the empty one (the schedule's wipe).
    # Static, default off, the contract of `telemetry` and
    # `conf_entries`: off, the compiled round and closed loop are the
    # programs they were.
    replace_replicas: bool = False
    # The log as term runs (see batched/termlog.py): K > 0 holds each
    # replica's log as K (start index, term) runs, [N, 2, K], where the
    # ring holds a term an entry, [N, W]. A log's terms never decrease
    # with the index, so its runs say all the ring says, every question
    # the round asks of the log is an elementwise pass over K whatever
    # the window, and `window` is then what the ring's size stood for:
    # the entries a replica may hold above its floor, of which
    # auto_compact keeps window // 2 behind the applied index (etcd's
    # SnapshotCatchUpEntries, 5,000, at window 10,240). A run lives in
    # slot term mod K; where a new term's slot holds a run the window
    # still needs the floor moves up past that run (a shorter tail is
    # always legal), and where that would pass `applied` the invariant
    # bitmap says so (telemetry.INV_NAMES, runs_passed_applied).
    # Static, default 0 (off), the contract of `telemetry` and
    # `conf_entries`: off, the state, the compiled round and the closed
    # loop are what they were.
    log_runs: int = 0

    @property
    def num_instances(self) -> int:
        return self.num_groups * self.num_replicas

    def validate(self) -> "BatchedConfig":
        """Bounds the wire/state layouts rely on; every engine/rawnode
        entry point calls this so a bad config fails loudly at build
        time instead of corrupting silently at runtime."""
        if not 0 < self.max_ents_per_msg <= MAX_WIRE_ENTS:
            raise ValueError(
                f"max_ents_per_msg={self.max_ents_per_msg} out of range: "
                f"the wire record packs n_ents as one byte "
                f"(1..{MAX_WIRE_ENTS}); larger appends would wrap the "
                "entry count on the SoA block path")
        if not 0 < self.num_replicas <= 127:
            raise ValueError(
                f"num_replicas={self.num_replicas} out of range: member "
                "ids (slot+1) ride one-byte wire fields and int8 lanes")
        if self.narrow_lanes and self.max_inflight > 32767:
            raise ValueError(
                f"max_inflight={self.max_inflight} does not fit the "
                "int16 inflight lane; lower it or disable narrow_lanes")
        if self.deliver_shape not in ("auto", "vectorized"):
            raise ValueError(
                f"deliver_shape={self.deliver_shape!r}: deliver has one "
                "order, 'vectorized' ('auto' names it too)")
        if self.replace_replicas and not self.conf_entries:
            raise ValueError(
                "replace_replicas needs conf_entries: a replica enters and "
                "leaves through entries of the device's log")
        if self.replace_replicas and self.num_replicas > MAX_SNAPSHOT_SLOTS:
            raise ValueError(
                f"num_replicas={self.num_replicas} with replace_replicas: a "
                "snapshot states its four membership masks as two 16-bit "
                f"halves of two int32 fields (1..{MAX_SNAPSHOT_SLOTS})")
        if self.log_runs < 0:
            raise ValueError(f"log_runs={self.log_runs} must be >= 0")
        if (self.num_instances * self.window > MAX_RING_TERMS
                and not self.log_runs):
            raise ValueError(
                f"window={self.window} over {self.num_instances} replicas "
                f"without log_runs: a ring of more than {MAX_RING_TERMS} "
                "terms, and every ring operation a pass over all of it "
                "(an append's write a [N, W, E] compare); hold a log that "
                "deep as term runs (log_runs)")
        if self.log_runs and self.max_ents_per_msg > self.window:
            raise ValueError(
                f"max_ents_per_msg={self.max_ents_per_msg} exceeds "
                f"window={self.window}")
        if self.log_runs and self.fleet_summary:
            raise ValueError(
                "log_runs with fleet_summary: the fleet frame's "
                "ring-pressure histogram is bucketed for a ring of terms; "
                "no frame reads the run table yet")
        if self.log_runs and self.conf_entries:
            raise ValueError(
                "log_runs with conf_entries: a configuration change's mark "
                "is held to the ring's truncation by the differential "
                "tests, and none runs it over term runs yet")
        et = self.election_timeout
        if et < 1 or (et > MAX_HASHED_TIMEOUT and et & (et - 1)):
            raise ValueError(
                f"election_timeout={et} must be 1..{MAX_HASHED_TIMEOUT} "
                "or a power of two: the timeout "
                "hash (rand_timeout) multiplies residues modulo it on "
                "uint32 lanes, and beyond that a product passes 2^32 by "
                "other than a multiple of it")
        if self.apply_plane:
            if self.apply_capacity < 1:
                raise ValueError(
                    f"apply_capacity={self.apply_capacity} must be >= 1")
            if not 0 < self.apply_watch_slots <= 32:
                raise ValueError(
                    f"apply_watch_slots={self.apply_watch_slots} out of "
                    "range 1..32: watch matches pack into one i32 "
                    "bitmap lane of the event frame")
            if self.apply_records < 1:
                raise ValueError(
                    f"apply_records={self.apply_records} must be >= 1")
            if self.lease_read_margin < 1:
                raise ValueError(
                    f"lease_read_margin={self.lease_read_margin} must "
                    "be >= 1: a zero margin serves a read on the tick "
                    "the lease dies")
        return self

    def apply_plane_key(self) -> "BatchedConfig":
        """The round-step compile-key normalization: the apply plane is
        a SEPARATE jitted program (applyplane.py), so none of its knobs
        may fork the round-step program. make_step_round strips them to
        defaults before keying step._step_round_jit — apply_plane
        on/off therefore share ONE compiled round by construction (the
        static-plane contract, and the reason the conftest compile-
        shape budget does not move)."""
        return self._replace(
            apply_plane=False,
            apply_capacity=256,
            apply_watch_slots=8,
            apply_records=8,
            lease_read_margin=2,
        )

    def resolved(self) -> "BatchedConfig":
        """The config with deliver_shape="auto" spelled "vectorized".
        Every engine/rawnode/step builder resolves BEFORE keying a
        compile (step._step_round_jit caches per config), so the two
        spellings share one program."""
        if self.deliver_shape != "auto":
            return self
        return self._replace(deliver_shape="vectorized")


# A configuration change as the device holds it: one i32 code, the
# kind in the low two bits and the replica slot it names above them.
# Every kind is a ConfChangeV2 upstream: CONF_DEMOTE is
# {JointExplicit, [AddLearnerNode slot]} (a voter leaves the incoming
# half and waits in LearnersNext), CONF_PROMOTE is
# {JointExplicit, [AddNode slot]}, CONF_LEAVE is the empty change that
# leaves a joint configuration. 0 is no change.
CONF_NONE, CONF_DEMOTE, CONF_LEAVE, CONF_PROMOTE = 0, 1, 2, 3
# The wide kinds (cfg.replace_replicas): CONF_ADD_LEARNER is the simple
# change {[AddLearnerNode slot]} for a slot in nobody's masks (a fresh
# replica joins as a learner, no joint configuration), CONF_SWAP is
# {JointExplicit, [AddNode slot, RemoveNode slot2]}: the learner `slot`
# takes the voter `slot2`'s place in one joint change of two ops
# (upstream's confchange_v2_replace_leader.txt).
CONF_ADD_LEARNER, CONF_SWAP = 4, 5
_CONF_WIDE_BIT, _CONF_SLOT2_SHIFT, _CONF_SLOT_MASK = 9, 10, 127


def conf_code(kind: int, slot: int = 0, slot2: int = 0) -> int:
    """kind's low two bits | slot << 2 | kind's third bit << 9 |
    slot2 << 10: for the four narrow kinds `kind | slot << 2` as ever
    (nothing above bit 8 is set, so `code & 3` and `code >> 2` read
    them), for the wide kinds the same fields under masks
    (`conf_decode`). A slot is below 127 (validate())."""
    return ((kind & 3) | (slot << 2) | ((kind >> 2) << _CONF_WIDE_BIT)
            | (slot2 << _CONF_SLOT2_SHIFT))


def conf_decode(code):
    """(kind, slot, slot2) of a wide `conf_code`, on ints or arrays."""
    kind = (code & 3) | (((code >> _CONF_WIDE_BIT) & 1) << 2)
    return (kind, (code >> 2) & _CONF_SLOT_MASK,
            code >> _CONF_SLOT2_SHIFT)


class ConfLanes(NamedTuple):
    """What a replica knows of configuration changes in its log
    (cfg.conf_entries). Entry types never reach the device, so the one
    unapplied change an instance's log may hold is marked here: where
    it is and what it says. A leader sets the mark when it appends the
    change; a follower learns it with the append that carries the
    entry (the APP lane's `reject_hint` and `ctx`, which an append
    leaves unused) and forgets it if a conflict truncates the log
    below it; each replica applies it, flipping its own masks, when
    its commit reaches the index (step._control), and clears the mark.
    One mark an instance: a second change reaches a follower only once
    its leader has applied the first, a round after the follower heard
    of that commit (the scan counts a mark overwritten unapplied)."""

    index: jnp.ndarray  # [N] i32: index of the unapplied change; 0 none
    # [N] i32: its conf_code; with cfg.replace_replicas the wide code
    # (conf_decode: two slots, six kinds), which still rides an append's
    # `ctx` as it is.
    op: jnp.ndarray
    # raft.pendingConfIndex (ref: raft.go:1043-1077, becomeLeader): a
    # leader takes no change while this lies above `applied`. The
    # index of the change it last appended, its last index as it won
    # the term, 0 on every reset.
    pending: jnp.ndarray  # [N] i32
    # tracker.Config.LearnersNext: voters of the outgoing half that
    # become learners when the joint configuration is left.
    learner_next: jnp.ndarray  # [N, R] bool


class BatchedState(NamedTuple):
    """Per-instance consensus state, all leading dim N = G*R."""

    # HardState + role (ref: raft.go:246-247,259,267)
    term: jnp.ndarray  # [N] i32
    vote: jnp.ndarray  # [N] i32, replica slot + 1; 0 = None
    role: jnp.ndarray  # [N] i32 (FOLLOWER/CANDIDATE/LEADER/PRECANDIDATE)
    lead: jnp.ndarray  # [N] i32, slot + 1; 0 = None

    # Log (ref: raft/log.go raftLog) — ring of terms plus watermarks.
    # [N, W] i32; term of entry i at ring slot i % W. With cfg.log_runs
    # [N, 2, K] i32: the run table (termlog.py), starts then terms.
    log_term: jnp.ndarray
    snap_index: jnp.ndarray  # [N] i32: index covered by snapshot (= first-1)
    snap_term: jnp.ndarray  # [N] i32
    last: jnp.ndarray  # [N] i32: last log index
    commit: jnp.ndarray  # [N] i32
    applied: jnp.ndarray  # [N] i32

    # Ticks (ref: raft.go:285-303)
    election_elapsed: jnp.ndarray  # [N] i32
    heartbeat_elapsed: jnp.ndarray  # [N] i32
    randomized_timeout: jnp.ndarray  # [N] i32
    reset_count: jnp.ndarray  # [N] i32 (drives the deterministic timeout hash)

    # Leader-side per-peer progress (ref: tracker/progress.go)
    match: jnp.ndarray  # [N, R] i32
    next: jnp.ndarray  # [N, R] i32
    pr_state: jnp.ndarray  # [N, R] i32 (PROBE/REPLICATE/SNAPSHOT)
    probe_sent: jnp.ndarray  # [N, R] bool
    pending_snapshot: jnp.ndarray  # [N, R] i32
    recent_active: jnp.ndarray  # [N, R] bool
    inflight: jnp.ndarray  # [N, R] i32 — count+watermark degeneration of
    # the reference's ring buffer (ref: SURVEY.md §2.1 Inflights)

    # Votes (ref: tracker.go Votes): -1 not voted, 0 rejected, 1 granted
    votes: jnp.ndarray  # [N, R] i32

    # Membership (ref: tracker.Config / quorum/joint.go): incoming
    # voters, outgoing voters (joint), learners. in_joint gates the
    # second quorum half. With cfg.conf_entries each replica flips its
    # own masks when it applies the change (ConfLanes, step._control);
    # otherwise masks are uploaded by the host at the confchange apply
    # point (SURVEY §2.1 "host-side control plane"):
    # on the hosting path that is batched/membership.GroupConfStore —
    # committed EntryConfChangeV2 entries flip these lanes via one
    # bulk staged upload (rawnode.set_membership_many), enter-joint at
    # the joint entry's apply, auto-leave once the joint config
    # commits. voter_out nonzero while in_joint is false is illegal
    # (kernels.invariant_bits bit 8, voter_out_no_joint).
    voter: jnp.ndarray  # [N, R] bool
    voter_out: jnp.ndarray  # [N, R] bool (only meaningful when in_joint)
    learner: jnp.ndarray  # [N, R] bool
    in_joint: jnp.ndarray  # [N] bool

    # Durability fence (protocol-aware recovery, FAST'18): set at boot
    # for instances whose recovered WAL tail fell below the durable
    # watermark (acked bytes destroyed). A fenced instance neither
    # campaigns nor grants votes — its log/vote state can no longer
    # back the promises it made — but still accepts appends/heartbeats,
    # re-converging as a de-facto learner until the hosting layer lifts
    # the fence (durable log back at the watermark).
    fenced: jnp.ndarray  # [N] bool

    # Leader transfer (ref: raft.go:1339-1372; raft.leadTransferee).
    transferee: jnp.ndarray  # [N] i32, slot+1; 0 = no transfer pending
    transfer_sent: jnp.ndarray  # [N] bool — TimeoutNow already emitted

    # ReadIndex (ref: read_only.go:39-112, ReadOnlySafe): one pending
    # read batch per group; heartbeats carry read_seq as ctx, acks
    # accumulate until quorum.
    read_seq: jnp.ndarray  # [N] i32, incremented per accepted batch
    read_index: jnp.ndarray  # [N] i32, commit at request time; -1 none
    read_acks: jnp.ndarray  # [N, R] bool
    read_ready: jnp.ndarray  # [N] bool — quorum confirmed for read_seq
    # Request latch: a read asked for while a batch is in flight (or
    # before first commit-in-term) opens the next batch as soon as the
    # current one confirms — the device form of read_only.go's pending
    # queue (requests are never dropped).
    read_req_latch: jnp.ndarray  # [N] bool

    # Pending send flags consumed by the emit phase.
    send_append: jnp.ndarray  # [N, R] bool
    send_heartbeat: jnp.ndarray  # [N, R] bool
    send_vote_req: jnp.ndarray  # [N] bool
    vote_req_is_pre: jnp.ndarray  # [N] bool
    # Vote requests carry the transfer-campaign context flag
    # (ref: raft.go campaignTransfer → ignore leader lease).
    vote_req_transfer: jnp.ndarray  # [N] bool
    send_timeout_now: jnp.ndarray  # [N] bool (target = transferee)

    # Leader lease (ROADMAP item 5; the fence lane's clock-bound
    # tick-lane compare turned outward): remaining ticks for which this
    # leader may serve linearizable reads locally. Armed to
    # election_timeout whenever check_quorum proves a live quorum
    # (cq_fire & alive — the same evidence the reference's lease-based
    # read path leans on) or commit/ReadIndex progress confirms the
    # term; decremented each tick; zeroed on transfer/step-down. Safety
    # argument: a peer cannot be elected before ITS election_elapsed
    # reaches randomized_timeout >= election_timeout ticks of leader
    # silence, so a lease armed at election_timeout and counted in the
    # SAME tick currency expires no later than the first tick a rival
    # could win — ticks are per-member host time, not a synchronized
    # clock, which is exactly the reference caveat (clock drift bounds
    # apply; reads fall back to ReadIndex when the lane is cold).
    # Computed UNCONDITIONALLY (no apply_plane branch — the lane rides
    # every program, keeping on/off bit-identical); write-only w.r.t.
    # every protocol branch.
    lease_ticks: jnp.ndarray  # [N] i32

    # Where a leader's own term begins in its log: the index of the
    # empty entry it appended as it won (step._become_leader), 0 on
    # every row that is not a leader (step._reset clears it; a fresh, a
    # wiped, a restarted or a restored replica is a follower, so it is
    # never persisted). A leader's log is append-only from that entry,
    # so for a leader term_at(i) == term exactly when own_from <= i <=
    # last: what _maybe_commit, _control's committed-in-term and emit
    # ask of an entry they answer from this field and not from the
    # ring.
    own_from: jnp.ndarray  # [N] i32


# The state of a configuration with cfg.conf_entries: every field of
# BatchedState and, last, `conf`, its ConfLanes. A type of its own, so
# that a configuration that does not ask for the lanes carries, donates
# and compiles exactly the fields it did, and whoever walks
# BatchedState._fields meets arrays only.
ConfBatchedState = NamedTuple(
    "ConfBatchedState",
    [*BatchedState.__annotations__.items(), ("conf", ConfLanes)])


# Narrow storage dtype per hot lane (cfg.narrow_lanes). Values are
# bounded: roles 0..3, member ids 0..R+1 (R <= 127), vote tallies
# -1..1, progress states 0..2, inflight <= max_inflight (validated
# <= int16 max). Everything else keeps its wide dtype.
NARROW_DTYPES = {
    "role": I8,
    "vote": I8,
    "lead": I8,
    "transferee": I8,
    "votes": I8,
    "pr_state": I8,
    "inflight": I16,
}


def narrow_state(st: BatchedState) -> BatchedState:
    """Cast the bounded lanes to their narrow storage dtypes."""
    return st._replace(**{
        f: getattr(st, f).astype(dt) for f, dt in NARROW_DTYPES.items()
    })


def widen_state(st: BatchedState) -> BatchedState:
    """Cast narrow storage lanes back to i32 for the round kernel."""
    return st._replace(**{
        f: getattr(st, f).astype(I32) for f in NARROW_DTYPES
    })


def _slot_ids(cfg: BatchedConfig) -> np.ndarray:
    return np.arange(cfg.num_instances, dtype=np.int32) % cfg.num_replicas


def instance_slot(cfg: BatchedConfig) -> jnp.ndarray:
    """[N] replica slot of each instance (used as `self id - 1`)."""
    return jnp.asarray(_slot_ids(cfg))


def rand_timeout(cfg: BatchedConfig, iid, reset_count):
    """Deterministic stand-in for lockedRand: [et, 2et-1], reproducible
    by the host oracle (shadow.DeviceHashRand computes the same formula,
    ((iid+1)*7919 + reset_count*104729) % et, in Python integers). The
    operands are reduced first, on unsigned lanes: nothing passes 2^32
    for et up to MAX_HASHED_TIMEOUT, a power of two divides the wrap,
    and every `%` by one is a mask (validate() admits no other)."""
    et = cfg.election_timeout
    t = jnp.uint32(et)
    h = ((jnp.asarray(iid + 1).astype(jnp.uint32) % t) * jnp.uint32(7919 % et)
         + (jnp.asarray(reset_count).astype(jnp.uint32) % t)
         * jnp.uint32(104729 % et)) % t
    return et + h.astype(I32)


def init_state(cfg: BatchedConfig, start_index: int = 0,
               iids=None, spare=None) -> BatchedState:
    """All groups bootstrapped as followers at term 0 with R voters, log
    beginning at start_index (mirrors add-nodes bootstrap-from-snapshot,
    ref: rafttest/interaction_env_handler_add_nodes.go). With
    cfg.replace_replicas and `spare` (a slot, or one a group as [G]; -1
    none) a group has R - 1 voters and one empty slot: the spare is in
    nobody's masks, its own included, and its replica is what a fresh
    RawNode over empty storage is (no log whatever `start_index`, term
    0, no configuration): the rows `empty_replica` gives, which is also
    what the control phase's wipe leaves behind.

    `iids` (optional) gives each row its global instance id
    (group*R + slot): a hosting process that owns one replica slot of
    every group passes its own subset so the deterministic
    randomized-timeout hash matches the dense all-replica layout."""
    r, w = cfg.num_replicas, cfg.window
    # jitlint: waive(tracer-branch) -- an engine placed over nodes builds its state under jit: None is the argument left out, tested at trace time, never a device value
    if iids is None:
        iids = jnp.arange(cfg.num_instances, dtype=I32)
    else:
        iids = jnp.asarray(iids, I32)
    n = iids.shape[0]
    # Fresh buffers per field (no sharing): a buffer aliased into two
    # state fields cannot be donated to the round kernel ("attempt to
    # donate the same buffer twice"), and the round loop donates its
    # state carry so XLA reuses the SoA buffers between rounds.
    zeros_n = lambda: jnp.zeros((n,), I32)  # noqa: E731
    start = lambda: jnp.full((n,), start_index, I32)  # noqa: E731
    start0 = start()
    st = BatchedState(
        term=zeros_n(),
        vote=zeros_n(),
        role=jnp.full((n,), FOLLOWER, I32),
        lead=zeros_n(),
        # The ring, or with cfg.log_runs the run table (all zero: no run).
        log_term=jnp.zeros(
            (n, 2, cfg.log_runs) if cfg.log_runs else (n, w), I32),
        snap_index=start(),
        snap_term=jnp.where(start0 > 0, jnp.ones((n,), I32), zeros_n()),
        last=start(),
        commit=start(),
        applied=start(),
        election_elapsed=zeros_n(),
        heartbeat_elapsed=zeros_n(),
        # Per-instance randomized [et, 2et) from the start (reset_count
        # 0 of the deterministic hash) — a uniform value would make
        # every boot election a guaranteed split vote.
        randomized_timeout=rand_timeout(cfg, iids, 0),
        reset_count=zeros_n(),
        match=jnp.zeros((n, r), I32),
        next=jnp.ones((n, r), I32) * (start0[:, None] + 1),
        pr_state=jnp.full((n, r), PROBE, I32),
        probe_sent=jnp.zeros((n, r), bool),
        pending_snapshot=jnp.zeros((n, r), I32),
        recent_active=jnp.zeros((n, r), bool),
        inflight=jnp.zeros((n, r), I32),
        votes=jnp.full((n, r), -1, I32),
        voter=jnp.ones((n, r), bool),
        voter_out=jnp.zeros((n, r), bool),
        learner=jnp.zeros((n, r), bool),
        in_joint=jnp.zeros((n,), bool),
        fenced=jnp.zeros((n,), bool),
        transferee=zeros_n(),
        transfer_sent=jnp.zeros((n,), bool),
        read_seq=zeros_n(),
        read_index=jnp.full((n,), -1, I32),
        read_acks=jnp.zeros((n, r), bool),
        read_ready=jnp.zeros((n,), bool),
        read_req_latch=jnp.zeros((n,), bool),
        send_append=jnp.zeros((n, r), bool),
        send_heartbeat=jnp.zeros((n, r), bool),
        send_vote_req=jnp.zeros((n,), bool),
        vote_req_is_pre=jnp.zeros((n,), bool),
        vote_req_transfer=jnp.zeros((n,), bool),
        send_timeout_now=jnp.zeros((n,), bool),
        lease_ticks=zeros_n(),
        own_from=zeros_n(),
    )
    if cfg.conf_entries:
        st = ConfBatchedState(*st, conf=ConfLanes(
            index=zeros_n(), op=zeros_n(), pending=zeros_n(),
            learner_next=jnp.zeros((n, r), bool)))
    # jitlint: waive(tracer-branch) -- as above
    if spare is not None:
        if not cfg.replace_replicas:
            raise ValueError(
                "a spare slot needs a configuration with replace_replicas")
        spare = jnp.broadcast_to(
            jnp.asarray(spare, I32), (cfg.num_groups,))[iids // r]  # [n]
        empty = empty_replica(cfg, st, iids)
        is_spare = (iids % r) == spare
        seated = jnp.arange(r, dtype=I32)[None, :] != spare[:, None]
        st = st._replace(voter=st.voter & seated)
        st = jax.tree.map(
            lambda e, x: jnp.where(
                is_spare.reshape((n,) + (1,) * (x.ndim - 1)), e, x),
            empty, st)
    if cfg.narrow_lanes:
        st = narrow_state(st)
    return st


def empty_replica(cfg: BatchedConfig, like: BatchedState, iid):
    """The state of a replica that holds nothing, in the shapes and
    dtypes of `like` (a whole state, or one instance's slice of it
    under the round's vmap) for the instance ids `iid`: a fresh RawNode
    over empty storage. Term 0, no log, no configuration (so it neither
    campaigns nor is counted), every lane as init_state makes it but
    the masks, which are empty, and the timeout, drawn at reset count 0
    as a new process draws its first."""
    fresh = {
        "role": FOLLOWER, "read_index": -1, "votes": -1, "next": 1,
        "pr_state": PROBE,
    }
    st = like._replace(**{
        f: jnp.full_like(getattr(like, f), fresh.get(f, 0))
        for f in BatchedState._fields})
    st = st._replace(
        randomized_timeout=rand_timeout(cfg, iid, 0).astype(
            like.randomized_timeout.dtype))
    if cfg.conf_entries:
        st = st._replace(conf=jax.tree.map(jnp.zeros_like, like.conf))
    return st
