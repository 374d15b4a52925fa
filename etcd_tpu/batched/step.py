"""The batched step kernel: branch-free message handlers vmapped over the
instance axis, with an all-device message router.

Semantics mirror the single-group oracle (etcd_tpu.raft.raft, ref:
raft/raft.go Step/stepLeader/stepCandidate/stepFollower): appends,
append responses with reject-hint probing, heartbeats, elections (vote +
optional pre-vote), commit-index advancement, snapshot fallback for
lagging followers, proposals — and, since round 2, the former cold
paths as well: ReadIndex (heartbeat-ack quorum, see the readindex
handling around the heartbeat-response lane below), joint-config
membership changes (per-instance voter/learner masks with joint
commit/vote kernels), learners, and leader transfer all execute on
device. The host uploads mask/config rows (set_membership) but does not
step the protocol for any of these — see SURVEY.md §2.1. With
``BatchedConfig.conf_entries`` the masks are not uploaded either: a
configuration change is an entry of the device's log, appended by the
leader in the control phase and applied by each replica, itself, in
the round its commit reaches it (_conf_propose, _conf_apply).

Network model: per round each replica sends at most one message of each
KIND to each peer, so an inbox is a dense ``[N, R, K]`` slot array and
routing between instances of the same group is an exchange of the
(sender, target) axes inside the group's R adjacent rows — row shifts
and selects along N (see route()), no scatters, no host round-trips. A
round is one jitted program:

    deliver (each inbox lane one fold over the sender axis, no scan)
    → tick → control → propose → emit → route

Where an append's E entries outweigh the lane's scalar fields
(``app_head``: E=64 with P=2, not the engine family's E=4) the append
lane travels, is written and is read in two halves: a head of P + 1
columns in every round, the rest only in the rounds some append of the
batch states more (``lane_occupancy``'s BULK_APP; deliver's three-way
append lane, emit's tail, route()'s switch for it).

Determinism: randomized election timeouts use a per-instance hash of
(instance id, reset count), reproducible by the host oracle for
differential testing (ref: raft.go:1718-1720 resetRandomizedElectionTimeout).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import termlog
from .kernels import (
    VOTE_LOST,
    VOTE_WON,
    invariant_bits,
    joint_committed,
    joint_vote_result,
    log_bucket_counts,
    log_bucket_counts_masked,
)
from ..analysis.sentinels import note_compile_key
from ..obs.fleet import FLEET_BUCKETS, FleetLayout
from .telemetry import NUM_COUNTERS
from .state import (
    CANDIDATE,
    CONF_ADD_LEARNER,
    CONF_DEMOTE,
    CONF_LEAVE,
    CONF_PROMOTE,
    CONF_SWAP,
    FOLLOWER,
    LEADER,
    PRECANDIDATE,
    PROBE,
    REPLICATE,
    SNAPSHOT,
    BatchedConfig,
    BatchedState,
    I32,
    conf_decode,
    empty_replica,
    narrow_state,
    rand_timeout as _rand_timeout,
    widen_state,
)

# Message kinds = inbox slot layout (capacity classes, not semantics: a
# slot of a response kind may carry a stale-term MsgAppResp; handlers
# dispatch on the type field).
#
# THE INBOX LANE-ORDER CONTRACT (one constant, three consumers): the
# first NUM_REQ_KINDS lanes carry requests, and the response to a
# kind-k request routes back in lane ``k + NUM_REQ_KINDS`` — lane 3
# carries vote responses, lane 4 append responses, lane 5 heartbeat
# responses. Everything that splits or scatters lanes derives from
# NUM_REQ_KINDS: deliver's request/response split, the
# round's outbox (emit's request lanes, then deliver's response lanes:
# ``out + req_resps`` in _step_round_jit), and route()'s no-op on lane
# indexes (responses are
# already placed in their response lane BEFORE the sender/target
# exchange, which moves whole [K] lane vectors and never a lane). The
# msgblock↔step differential test pins the contract
# (tests/batched/test_msgblock.py), so a drifted call site fails a
# test instead of silently crossing lanes.
KIND_VOTE, KIND_APP, KIND_HB, KIND_VOTE_RESP, KIND_APP_RESP, KIND_HB_RESP = range(6)
NUM_KINDS = 6
NUM_REQ_KINDS = 3
assert (KIND_VOTE_RESP, KIND_APP_RESP, KIND_HB_RESP) == tuple(
    k + NUM_REQ_KINDS for k in (KIND_VOTE, KIND_APP, KIND_HB)
), "response lanes must sit exactly NUM_REQ_KINDS above their requests"

# Wire types (values match etcd_tpu.raft.types.MessageType).
T_APP, T_APP_RESP = 3, 4
T_VOTE, T_VOTE_RESP = 5, 6
T_SNAP = 7
T_HB, T_HB_RESP = 8, 9
T_TIMEOUT_NOW = 14
T_PREVOTE, T_PREVOTE_RESP = 17, 18

# Wire type -> inbox lane, as a lookup table usable both host-side
# (msgblock codec validation) and on device (pack_outbox); -1 marks
# unroutable types (mirrors rawnode._LANE).
NUM_WIRE_TYPES = 32
LANE_OF = np.full(NUM_WIRE_TYPES, -1, np.int8)
for _t, _lane in (
    (T_VOTE, KIND_VOTE), (T_PREVOTE, KIND_VOTE),
    (T_APP, KIND_APP), (T_SNAP, KIND_APP),
    (T_HB, KIND_HB), (T_TIMEOUT_NOW, KIND_HB),
    (T_VOTE_RESP, KIND_VOTE_RESP), (T_PREVOTE_RESP, KIND_VOTE_RESP),
    (T_APP_RESP, KIND_APP_RESP),
    (T_HB_RESP, KIND_HB_RESP),
):
    LANE_OF[_t] = _lane


class MsgSlots(NamedTuple):
    """SoA message batch; every field has the same leading shape, plus
    ent_terms with a trailing [E]. As a kind lane (``split_lanes``) it
    holds None for the fields the lane does not carry (LANE_FIELDS)."""

    valid: jnp.ndarray  # bool
    type: jnp.ndarray  # i32
    term: jnp.ndarray  # i32
    log_term: jnp.ndarray  # i32
    index: jnp.ndarray  # i32
    commit: jnp.ndarray  # i32
    reject: jnp.ndarray  # bool
    reject_hint: jnp.ndarray  # i32
    n_ents: jnp.ndarray  # i32
    # Context word (the reference's Message.Context bytes, reduced to
    # what rides it: campaign-transfer flag on votes, read_seq on
    # heartbeats/acks — ref: raft.go campaignTransfer, read_only.go ctx).
    ctx: jnp.ndarray  # i32
    ent_terms: jnp.ndarray  # i32 [..., E]


# The append lane of a configuration whose entries travel in two halves
# (``app_head``), in the lane form alone: MsgSlots' fields with
# ``ent_terms`` the head's columns, [..., Wn], and the columns past
# them apart in ``ent_tail``, [..., E - Wn]. Whatever takes a lane by
# its fields' names takes this one; the slot form never holds it.
BulkLane = NamedTuple(
    "BulkLane", [(f, jnp.ndarray) for f in MsgSlots._fields]
    + [("ent_tail", jnp.ndarray)])


def _tail(lane):
    """A split append lane's tail; None of any other lane or message."""
    return getattr(lane, "ent_tail", None)


def _untailed(lane) -> MsgSlots:
    """`lane` without a tail: a split append lane's head as MsgSlots."""
    return MsgSlots(*lane[:len(MsgSlots._fields)])


# The fields each kind lane carries, beside NUM_REQ_KINDS' lane-order
# contract and as static: what some message type of the lane states
# (raftpb's MsgVote, MsgApp and MsgSnap, MsgHeartbeat and MsgTimeoutNow
# and their responses state no field more), read off the writers, _emit
# and the response builders of deliver. One table for every
# configuration: KIND_APP keeps ``reject_hint`` and ``ctx`` (an
# append's configuration mark with cfg.conf_entries, a snapshot's
# configuration with cfg.replace_replicas) whether or not the flag is
# on. Every other field of a lane is zero in every slot a writer makes,
# so the lane form (``split_lanes``) holds None for it, an empty pytree
# that no exchange, wipe, slice or carry touches, and a handler reads
# the zero it always read (``_whole``). tests/batched/test_lane_fields.py
# holds the table to the writers.
LANE_FIELDS = (
    ("valid", "type", "term", "log_term", "index", "ctx"),  # KIND_VOTE
    ("valid", "type", "term", "log_term", "index", "commit", "reject_hint",
     "n_ents", "ctx", "ent_terms"),  # KIND_APP
    ("valid", "type", "term", "commit", "ctx"),  # KIND_HB
    ("valid", "type", "term", "reject"),  # KIND_VOTE_RESP
    ("valid", "type", "term", "log_term", "index", "reject",
     "reject_hint"),  # KIND_APP_RESP
    ("valid", "type", "term", "ctx"),  # KIND_HB_RESP
)
assert len(LANE_FIELDS) == NUM_KINDS and all(
    set(fs) <= set(MsgSlots._fields) for fs in LANE_FIELDS)


# Narrow storage dtype per bounded message lane (cfg.narrow_lanes),
# the MsgSlots twin of state.NARROW_DTYPES: wire types are < 32 (int8),
# per-message entry counts are <= MAX_WIRE_ENTS = 255 (int16; int8 is
# signed and would wrap at 128). valid/reject are already bool. The
# unbounded protocol words (term/index/commit/log_term/reject_hint/
# ent_terms, plus ctx which carries read_seq) stay int32 — narrowing a
# watermark would change wrap semantics. Narrow lanes live ONLY in the
# between-rounds carry (the routed inbox / emitted outbox); the round
# kernel widens at deliver entry and narrows at emit exit, so handler
# math is bit-identical to the wide layout (the jitlint narrow-lane
# contract, mirroring state.widen_state/narrow_state).
NARROW_MSG_DTYPES = {
    "type": jnp.int8,
    "n_ents": jnp.int16,
}


def narrow_msgs(m: MsgSlots) -> MsgSlots:
    """Cast the bounded message lanes to their narrow storage dtypes (of
    the fields `m` carries: a lane holds None for the rest)."""
    return m._replace(**{
        f: getattr(m, f).astype(dt) for f, dt in NARROW_MSG_DTYPES.items()
        if getattr(m, f) is not None
    })


def widen_msgs(m: MsgSlots) -> MsgSlots:
    """Cast narrow message lanes back to i32 for the round kernel."""
    return m._replace(**{
        f: getattr(m, f).astype(I32) for f in NARROW_MSG_DTYPES
        if getattr(m, f) is not None
    })


def empty_msgs(shape: Tuple[int, ...], num_ents: int,
               narrow: bool = False) -> MsgSlots:
    # One fresh buffer per field (no aliasing): the round loop donates
    # its inbox, and a buffer appearing under two leaves of a donated
    # pytree is a runtime error ("attempt to donate the same buffer
    # twice"). Inside a trace these are constants either way.
    z = lambda: jnp.zeros(shape, I32)  # noqa: E731
    m = MsgSlots(
        valid=jnp.zeros(shape, bool),
        type=z(),
        term=z(),
        log_term=z(),
        index=z(),
        commit=z(),
        reject=jnp.zeros(shape, bool),
        reject_hint=z(),
        n_ents=z(),
        ctx=z(),
        ent_terms=jnp.zeros(shape + (num_ents,), I32),
    )
    return narrow_msgs(m) if narrow else m


def _carried(k: int, m: MsgSlots) -> MsgSlots:
    """`m` as lane `k` carries it: the fields of ``LANE_FIELDS[k]``
    (a split append lane's tail with its head), None for the rest."""
    return m._replace(**{f: None for f in MsgSlots._fields
                         if f not in LANE_FIELDS[k]})


def _whole(k: int, m: MsgSlots) -> MsgSlots:
    """A message of lane `k` with every field: what the lane does not
    carry is the zero a writer would have put there (no entries, as a
    handler's own ``empty_msgs(..., 0)``). Constants, which the compiler
    folds into whatever reads them. A split append lane's tail stays
    beside its head (``_joined`` puts the two together)."""
    whole = empty_msgs(m.valid.shape, 0)._replace(
        **{f: getattr(m, f) for f in LANE_FIELDS[k]})
    # jitlint: waive(tracer-branch) -- on the lane's type, at trace time
    if _tail(m) is None:
        return whole
    return BulkLane(*whole, m.ent_tail)


def app_head(cfg) -> int:
    """Wn, the columns of an append's entries that travel, are written
    and are read in every round, or 0 where the append lane is not
    split. Static, of E = ``max_ents_per_msg`` and P =
    ``max_props_per_round`` alone: a steady append carries the round's
    P proposals, a new leader's first its empty entry beside them, so
    the head is P + 1 columns; the E - Wn past it (the *tail*) are
    used by the appends that carry a replica that fell behind, and
    travel as a part of the lane of its own (``BulkLane.ent_tail``)
    only in the rounds in which some append of the batch states more
    than Wn entries (``lane_occupancy``'s BULK_APP). The lane is split
    only where the tail outweighs the nine scalar fields beside it
    (E=64, P=2: 61 words; not at E=4, P=2 nor at E=8, P=4, whose
    programs are what they were)."""
    head = cfg.max_props_per_round + 1
    scalars = len(LANE_FIELDS[KIND_APP]) - 1
    return head if cfg.max_ents_per_msg - head > scalars else 0


def _joined(m) -> MsgSlots:
    """A split append (lane) with its entries in one piece again."""
    # jitlint: waive(tracer-branch) -- on the lane's type, at trace time, never on a device value
    if _tail(m) is None:
        return m
    return _untailed(m)._replace(
        ent_terms=jnp.concatenate([m.ent_terms, m.ent_tail], axis=-1))


def _states_bulk(app: BulkLane):
    """Per slot of a split append lane: a valid MsgApp that states more
    entries than the head holds, so that its tail is read."""
    return (app.valid & (app.type == T_APP)
            & (app.n_ents > app.ent_terms.shape[-1]))


def settled(lanes: Tuple[MsgSlots, ...]) -> Tuple[MsgSlots, ...]:
    """Kind lanes with a split append lane's tail as the public form
    states it. In the lane form the tail means something only while
    some valid append of the lane states more than the head holds
    (BULK_APP): in every other round emit hands the spent inbox's tail
    on unread and unwritten (``_emit``), and here that is the zeros a
    writer of slots would have stated."""
    app = lanes[KIND_APP]
    # jitlint: waive(tracer-branch) -- as in _joined
    if _tail(app) is None:
        return lanes
    tail = jnp.where(jnp.any(_states_bulk(app)), app.ent_tail, 0)
    return (lanes[:KIND_APP] + (app._replace(ent_tail=tail),)
            + lanes[KIND_APP + 1:])


def split_lanes(m: MsgSlots, head: int = 0) -> Tuple[MsgSlots, ...]:
    """[N, R, K] slots as K kind lanes of [N, R], each with the fields
    of ``LANE_FIELDS`` alone (entries travel in KIND_APP, ``reject`` in
    two response lanes, ...): no writer puts anything but zero in the
    others, so nothing of an inbox a writer can make is lost, and what
    a lane does not hold is not there to move. With `head`
    (``app_head(cfg)``, static; 0: no split) the append lane's entries
    come in two halves (``BulkLane``), ``ent_terms`` the first `head`
    columns and ``ent_tail`` the rest."""
    lanes = tuple(
        jax.tree.map(lambda x, _k=k: x[:, :, _k], _carried(k, m))
        for k in range(NUM_KINDS))
    if head:
        app = lanes[KIND_APP]
        lanes = lanes[:KIND_APP] + (BulkLane(
            *app._replace(ent_terms=app.ent_terms[..., :head]),
            app.ent_terms[..., head:]),) + lanes[KIND_APP + 1:]
    return lanes


def stack_lanes(lanes: Tuple[MsgSlots, ...]) -> MsgSlots:
    """K kind lanes of [N, R] as [N, R, K] slots, the public form: the
    fields a lane does not carry come back as the zeros they always
    were (shape and dtype of a lane that carries the field), and a
    split append lane's entries in one piece, ``ent_terms [.., E]``
    (``settled``)."""
    lanes = settled(lanes)
    lanes = (lanes[:KIND_APP] + (_joined(lanes[KIND_APP]),)
             + lanes[KIND_APP + 1:])

    def stacked(f):
        xs = [getattr(lane, f) for lane in lanes]
        zeros = jnp.zeros_like(next(x for x in xs if x is not None))
        return jnp.stack([zeros if x is None else x for x in xs], axis=2)

    return MsgSlots(*map(stacked, MsgSlots._fields))


def lane_slot_bytes(num_ents: int, head: int = 0) -> np.ndarray:
    """[K] ints: the bytes of one slot of each kind lane as it is
    carried and exchanged (``LANE_FIELDS`` and the fields' dtypes; E =
    `num_ents` entries' terms in KIND_APP). Times the rows, the R
    slots and the rounds a lane ran (``lane_rounds()``, between nodes
    ``lane_exchanges()``) it is what a window's exchange moved. With
    `head` (``app_head(cfg)``; 0: no split) [K + 1]: KIND_APP's slot
    with the head's columns alone and, last, the bytes of the tail's,
    which moved in the rounds ``bulk_rounds()`` counts."""
    like = jax.eval_shape(lambda: empty_msgs((), head or num_ents))
    lanes = [
        sum(x.dtype.itemsize * x.size
            for x in jax.tree.leaves(_carried(k, like)))
        for k in range(NUM_KINDS)]
    if head:
        lanes.append((num_ents - head) * like.ent_terms.dtype.itemsize)
    return np.array(lanes)


# A batch's occupancy vector (``lane_occupancy``): the K kind lanes, then
# one bit for each message type that is rare inside a lane that is not —
# a MsgTimeoutNow in the heartbeat lane (there only while a leadership
# is handed over), a MsgAppResp in the heartbeat-response lane (there
# only as the nudge a stale leader's heartbeat draws). Deliver gives the
# handling of each a branch of its own (_deliver_vectorized). Where the
# append lane is split (``app_head``), and only there, one bit more:
# BULK_APP, a valid MsgApp that states more entries than the head holds,
# on which deliver runs the lane at its whole width, emit builds the
# tail and route() moves it.
RARE_TIMEOUT_NOW, RARE_APP_RESP = NUM_KINDS, NUM_KINDS + 1
NUM_OCC = NUM_KINDS + 2
BULK_APP = NUM_OCC


def lane_occupancy(lanes: Tuple[MsgSlots, ...]) -> jnp.ndarray:
    """[NUM_OCC] bool: which kind lanes hold a message for any instance
    and, after the K lanes, whether the heartbeat lane holds a
    MsgTimeoutNow and the heartbeat-response lane a MsgAppResp for any
    (RARE_TIMEOUT_NOW, RARE_APP_RESP); of a split append lane
    [NUM_OCC + 1], last whether any valid MsgApp states more entries
    than the head holds (BULK_APP)."""
    hb, hb_resp = lanes[KIND_HB], lanes[KIND_HB_RESP]
    app = lanes[KIND_APP]
    # jitlint: waive(tracer-branch) -- on the lane's type, at trace time, never on a device value
    bulk = [] if _tail(app) is None else [jnp.any(_states_bulk(app))]
    return jnp.stack(
        [jnp.any(lanes[k].valid) for k in range(NUM_KINDS)]
        + [jnp.any(hb.valid & (hb.type == T_TIMEOUT_NOW)),
           jnp.any(hb_resp.valid & (hb_resp.type == T_APP_RESP))]
        + bulk)


def _sel(cond, a, b):
    """Tree-select: where(cond, a, b) leafwise (cond is scalar here)."""
    return jax.tree.map(lambda x, y: jnp.where(cond, x, y), a, b)


def _pick_b(vec, at):
    """Bool vec[s] for a traced s, as compare+reduce (at = peers == s):
    traced-index gathers serialize on TPU, one-hot reads don't."""
    return jnp.any(vec & at, axis=-1)


# -----------------------------------------------------------------------------
# Per-instance primitive transitions (scalars + [R]/[W] vectors; used
# under vmap). Each returns a full BatchedState slice.
# -----------------------------------------------------------------------------


def _reset(cfg: BatchedConfig, st: BatchedState, iid, slot, term) -> BatchedState:
    """ref: raft.go:590-619 reset()."""
    r = st.match.shape[-1]
    changed = st.term != term
    rc = st.reset_count + 1
    peers = jnp.arange(r, dtype=I32)
    if cfg.conf_entries:
        # pendingConfIndex dies with the term or the role; what the log
        # holds of a change (index, op) does not.
        st = st._replace(conf=st.conf._replace(
            pending=jnp.zeros_like(st.conf.pending)))
    return st._replace(
        term=term,
        vote=jnp.where(changed, 0, st.vote),
        lead=jnp.zeros_like(st.lead),
        election_elapsed=jnp.zeros_like(st.election_elapsed),
        heartbeat_elapsed=jnp.zeros_like(st.heartbeat_elapsed),
        reset_count=rc,
        randomized_timeout=_rand_timeout(cfg, iid, rc),
        votes=jnp.full((r,), -1, I32),
        match=jnp.where(peers == slot, st.last, 0),
        next=jnp.full((r,), 1, I32) * (st.last + 1),
        pr_state=jnp.full((r,), PROBE, I32),
        probe_sent=jnp.zeros((r,), bool),
        pending_snapshot=jnp.zeros((r,), I32),
        recent_active=jnp.zeros((r,), bool),
        inflight=jnp.zeros((r,), I32),
        # abortLeaderTransfer + read state dies with the term/role
        # (ref: raft.go:590-619 reset).
        transferee=jnp.zeros_like(st.transferee),
        transfer_sent=jnp.zeros_like(st.transfer_sent),
        read_index=jnp.full_like(st.read_index, -1),
        read_acks=jnp.zeros((r,), bool),
        read_ready=jnp.zeros_like(st.read_ready),
        read_req_latch=jnp.zeros_like(st.read_req_latch),
        # Every way out of leadership, and into it, comes through here:
        # _become_leader sets it again after this.
        own_from=jnp.zeros_like(st.own_from),
    )


def _become_follower(cfg, st, iid, slot, term, lead) -> BatchedState:
    st = _reset(cfg, st, iid, slot, term)
    return st._replace(role=jnp.full_like(st.role, FOLLOWER), lead=lead)


def _append_own(cfg: BatchedConfig, st: BatchedState, slot, n,
                cols: int = 0) -> BatchedState:
    """Leader appends n entries of its own term (ref: raft.go:621-642
    appendEntry): the log's write, self progress, maybe_commit. The
    ring's write is `cols` term columns wide (static; n <= cols),
    max_props_per_round where the caller names none (0): see _tick for
    the one that does."""
    st = termlog.append_own(cfg, st, n, cols or cfg.max_props_per_round)
    last = st.last + n
    r = st.match.shape[-1]
    peers = jnp.arange(r, dtype=I32)
    match = jnp.where(peers == slot, jnp.maximum(st.match, last), st.match)
    nxt = jnp.where(peers == slot, jnp.maximum(st.next, last + 1), st.next)
    st = st._replace(last=last, match=match, next=nxt)
    return _maybe_commit(st)


def _maybe_commit(st: BatchedState) -> BatchedState:
    """Quorum commit-index advancement — THE replica-axis reduction
    (ref: raft.go:585-588 + quorum/majority.go:126, joint.go:49-56)."""
    mci = joint_committed(st.match, st.voter, st.voter_out, st.in_joint)
    # Only an entry of the leader's own term commits by counting
    # (raft.go maybeCommit's term check): for a leader that is every
    # entry from own_from on (BatchedState.own_from), so the ring is not
    # read. On a row that is no leader own_from is 0 and nothing
    # commits; every caller keeps the result for a leader alone.
    ok = (mci > st.commit) & (st.own_from > 0) & (mci >= st.own_from)
    return st._replace(commit=jnp.where(ok, mci, st.commit))


def _repl_targets(st: BatchedState) -> jnp.ndarray:
    """[R] replication set: every tracked progress — voters of both
    configs plus learners (ref: tracker.go Visit over the full
    progress map)."""
    return st.voter | st.voter_out | st.learner


def _vote_targets(st: BatchedState) -> jnp.ndarray:
    """[R] electorate: voters of both halves, never learners."""
    return st.voter | st.voter_out


def _become_leader(cfg, st, iid, slot, cols: int = 0) -> BatchedState:
    """ref: raft.go:724-758 (reset, self replicate, append empty entry).
    `cols` is _append_own's."""
    st = _reset(cfg, st, iid, slot, st.term)
    r = st.match.shape[-1]
    peers = jnp.arange(r, dtype=I32)
    st = st._replace(
        role=jnp.full_like(st.role, LEADER),
        lead=slot + 1,
        pr_state=jnp.where(peers == slot, REPLICATE, st.pr_state),
        # The empty entry appended below: where this term begins.
        own_from=st.last + 1,
    )
    if cfg.conf_entries:
        # The tail may hold a change nobody has applied: no new one
        # until all of it is (ref: raft.go becomeLeader).
        st = st._replace(conf=st.conf._replace(pending=st.last))
    return _append_own(cfg, st, slot, jnp.asarray(1, I32), cols)


def _record_vote_and_tally(st: BatchedState, from_slot, granted):
    """ref: tracker.go RecordVote (setdefault) + TallyVotes."""
    r = st.votes.shape[-1]
    peers = jnp.arange(r, dtype=I32)
    new_vote = jnp.where(granted, 1, 0)
    votes = jnp.where(
        (peers == from_slot) & (st.votes == -1), new_vote, st.votes
    )
    st = st._replace(votes=votes)
    return st, joint_vote_result(votes, st.voter, st.voter_out, st.in_joint)


def _campaign(cfg: BatchedConfig, st: BatchedState, iid, slot, pre: bool,
              transfer: bool = False, cols: int = 0) -> BatchedState:
    """ref: raft.go:785-835; `pre`/`transfer` are static bools
    (config.pre_vote; campaignTransfer skips pre-vote and marks its
    vote requests to pierce leader leases). `cols` is _append_own's."""
    if pre:
        # becomePreCandidate: no term bump, no vote change.
        st1 = st._replace(
            role=jnp.full_like(st.role, PRECANDIDATE),
            lead=jnp.zeros_like(st.lead),
            votes=jnp.full_like(st.votes, -1),
        )
    else:
        st1 = _reset(cfg, st, iid, slot, st.term + 1)
        st1 = st1._replace(
            role=jnp.full_like(st.role, CANDIDATE), vote=slot + 1
        )
    st1, res = _record_vote_and_tally(st1, slot, jnp.asarray(True))
    won = res == VOTE_WON
    if pre:
        # Single-voter group: pre-vote win chains into the real election.
        st_won = _campaign(cfg, st1, iid, slot, False, cols=cols)
    else:
        st_won = _become_leader(cfg, st1, iid, slot, cols)
    st_lost = st1._replace(
        send_vote_req=jnp.ones_like(st.send_vote_req),
        vote_req_is_pre=jnp.full_like(st.vote_req_is_pre, pre),
        vote_req_transfer=jnp.full_like(st.vote_req_transfer, transfer),
    )
    return _sel(won, st_won, st_lost)


def _paused(cfg: BatchedConfig, st: BatchedState):
    """[R] bool — ref: tracker/progress.go:201-212 IsPaused."""
    return jnp.where(
        st.pr_state == PROBE,
        st.probe_sent,
        jnp.where(
            st.pr_state == REPLICATE,
            st.inflight >= cfg.max_inflight,
            True,
        ),
    )


# -----------------------------------------------------------------------------
# Per-message delivery (one request for one instance): what a request
# lane's winner runs through (_vec_lane_request)
# -----------------------------------------------------------------------------


def _term_gate(cfg: BatchedConfig, iid, slot, st: BatchedState, m: MsgSlots,
               from_slot):
    """raft.Step's term handling (ref: raft.go:849-920), shared by the
    two request handlers below. Returns (st1, dead, lower) where st1
    is post-become-follower state, `dead` kills the message entirely,
    `lower` routes to the stale path."""
    higher = m.term > st.term
    lower = m.term < st.term

    from_leader_type = (m.type == T_APP) | (m.type == T_HB) | (m.type == T_SNAP)
    is_vote_req = (m.type == T_VOTE) | (m.type == T_PREVOTE)

    in_lease = (
        jnp.asarray(cfg.check_quorum)
        & (st.lead != 0)
        & (st.election_elapsed < cfg.election_timeout)
    )
    # Transfer-campaign votes pierce the lease (ref: raft.go:870-880
    # force = Context == campaignTransfer).
    ignore_lease = higher & is_vote_req & in_lease & ~(m.ctx == 1)

    keep_term = (m.type == T_PREVOTE) | ((m.type == T_PREVOTE_RESP) & ~m.reject)
    do_become = higher & ~keep_term & ~ignore_lease
    st_b = _become_follower(
        cfg, st, iid, slot, m.term,
        jnp.where(from_leader_type, from_slot + 1, 0),
    )
    st1 = _sel(do_become, st_b, st)
    dead = ~m.valid | ignore_lease
    return st1, dead, lower


# -- request handlers: each processes ONE message of its inbox lane for
# one instance, implementing only the types that can land in that lane
# (lanes are capacity classes — the specialization is what keeps the
# per-slot cost low; ref: raft.go:991-1473 step* dispatch).


def _leader_traffic_prelude(cfg, iid, slot, st1, m, from_slot):
    """Candidate step-down + follower bookkeeping shared by the APP and
    HB lanes (ref: raft.go:1390-1398, 1433-1444)."""
    is_cand = (st1.role == CANDIDATE) | (st1.role == PRECANDIDATE)
    st_f = _sel(
        is_cand,
        _become_follower(cfg, st1, iid, slot, m.term, from_slot + 1),
        st1,
    )
    return st_f._replace(
        election_elapsed=jnp.zeros_like(st1.election_elapsed),
        lead=from_slot + 1,
    )


def _lane_app(cfg: BatchedConfig, iid, slot, st: BatchedState, m: MsgSlots,
              from_slot):
    """Lane KIND_APP: T_APP / T_SNAP (ref: raft.go:1475-1614). A
    response carries no entries: its ``ent_terms`` has no columns, and
    its lane none at all (LANE_FIELDS)."""
    no_resp = empty_msgs((), 0)
    st1, dead, lower = _term_gate(cfg, iid, slot, st, m, from_slot)

    fol = _leader_traffic_prelude(cfg, iid, slot, st1, m, from_slot)
    st_app, app_resp = _handle_append(cfg, fol, m)
    st_snap, snap_resp = _handle_snapshot(cfg, fol, m, slot)
    is_snap = m.type == T_SNAP
    leader_traffic_ok = st1.role != LEADER
    st_live = _sel(is_snap, st_snap, st_app)
    resp_live = _sel(is_snap, snap_resp, app_resp)
    st_live = _sel(leader_traffic_ok, st_live, st1)
    resp_live = _sel(leader_traffic_ok, resp_live, no_resp)

    # Stale leader: nudge with an empty MsgAppResp carrying our term
    # (ref: raft.go:885-905).
    stale = lower & jnp.asarray(cfg.check_quorum or cfg.pre_vote)
    resp_stale = no_resp._replace(
        valid=stale, type=jnp.asarray(T_APP_RESP, I32), term=st.term
    )
    st_out = _sel(dead | lower, st, st_live)
    resp = _sel(dead, no_resp, _sel(lower, resp_stale, resp_live))
    return st_out, resp


def _lane_hb(cfg: BatchedConfig, iid, slot, st: BatchedState, m: MsgSlots,
             from_slot, timeout_now: bool = True):
    """Lane KIND_HB: T_HB + T_TIMEOUT_NOW (ref: raft.go:1513;
    :1465-1472 MsgTimeoutNow → immediate transfer campaign). Without
    `timeout_now` (static) the campaign is not built: the handler of a
    batch that holds no valid MsgTimeoutNow, which then neither reads
    nor writes the log ring (_deliver_vectorized)."""
    no_resp = empty_msgs((), 0)
    st1, dead, lower = _term_gate(cfg, iid, slot, st, m, from_slot)

    fol = _leader_traffic_prelude(cfg, iid, slot, st1, m, from_slot)
    st_hb = fol._replace(
        commit=jnp.maximum(fol.commit, jnp.minimum(m.commit, fol.last))
    )
    hb_resp = no_resp._replace(
        valid=True, type=jnp.asarray(T_HB_RESP, I32), term=fol.term,
        ctx=m.ctx,  # ReadIndex ack context echo (read_only.go recvAck)
    )
    leader_traffic_ok = st1.role != LEADER

    # MsgTimeoutNow: campaign at once regardless of timers; only
    # promotable instances honor it (raft.go:1465-1472 + hup gating) —
    # and never a durability-fenced one (the fence exists to keep this
    # replica out of elections until its durable log is whole again).
    is_ton = m.type == T_TIMEOUT_NOW
    if timeout_now:
        r = st1.match.shape[-1]
        promotable = _pick_b(
            _vote_targets(st1), jnp.arange(r, dtype=I32) == slot)
        st_ton = _campaign(cfg, st1, iid, slot, False, transfer=True)
        st_hb = _sel(is_ton & promotable & ~st1.fenced, st_ton, st_hb)

    st_live = _sel(leader_traffic_ok, st_hb, st1)
    resp_live = _sel(leader_traffic_ok & ~is_ton, hb_resp, no_resp)

    stale = lower & jnp.asarray(cfg.check_quorum or cfg.pre_vote) & ~is_ton
    resp_stale = no_resp._replace(
        valid=stale, type=jnp.asarray(T_APP_RESP, I32), term=st.term
    )
    st_out = _sel(dead | lower, st, st_live)
    resp = _sel(dead, no_resp, _sel(lower, resp_stale, resp_live))
    return st_out, resp


def _handle_append(cfg: BatchedConfig, st: BatchedState, m: MsgSlots):
    """Follower append handling (ref: raft.go:1475-1511 +
    log.go maybeAppend/findConflict). As wide as the entries it is
    handed: E, or a split lane's head in a batch none of whose appends
    states more (_deliver_vectorized)."""
    e = m.ent_terms.shape[-1]
    no_resp = empty_msgs((), 0)
    prev = m.index

    # Fast path: stale append below commit acks the commit index.
    below_commit = prev < st.commit
    resp_below = no_resp._replace(
        valid=True, type=jnp.asarray(T_APP_RESP, I32), index=st.commit,
        term=st.term,
    )

    ta = lambda i: termlog.term_at(cfg, st, i)  # noqa: E731
    match_ok = ta(prev) == m.log_term

    j = jnp.arange(e, dtype=I32)
    idx = prev + 1 + j
    have = j < m.n_ents
    existing = ta(idx)
    conflict = have & ((idx > st.last) | (existing != m.ent_terms))
    any_conflict = jnp.any(conflict)
    ci = jnp.argmax(conflict)  # first conflicting offset

    write_mask = have & (j >= ci) & any_conflict
    st_ok = termlog.append_entries(
        cfg, st, prev, m.ent_terms, write_mask, ci, any_conflict)
    last = jnp.where(any_conflict, prev + m.n_ents, st.last)
    lastnewi = prev + m.n_ents
    commit = jnp.maximum(st.commit, jnp.minimum(m.commit, lastnewi))
    st_ok = st_ok._replace(last=last, commit=commit)
    if cfg.conf_entries:
        # The append says which of its entries is a configuration
        # change (emit: reject_hint its index, ctx its code). A mark at
        # or past the first entry a conflict rewrote names an entry
        # that is gone.
        c = st.conf
        carried = m.reject_hint > 0
        gone = any_conflict & (c.index >= prev + 1 + ci) & (
            prev + 1 + ci <= st.last)
        st_ok = st_ok._replace(conf=c._replace(
            index=jnp.where(carried, m.reject_hint,
                            jnp.where(gone, 0, c.index)),
            op=jnp.where(carried, m.ctx, jnp.where(gone, 0, c.op))))
    resp_ok = no_resp._replace(
        valid=True, type=jnp.asarray(T_APP_RESP, I32), index=lastnewi,
        term=st.term,
    )

    # Reject with a term-skipping hint (ref: raft.go:1487-1509).
    hint0 = jnp.minimum(prev, st.last)
    hint = termlog.find_conflict(cfg, st, hint0, m.log_term)
    resp_rej = no_resp._replace(
        valid=True,
        type=jnp.asarray(T_APP_RESP, I32),
        index=prev,
        reject=True,
        reject_hint=hint,
        log_term=ta(hint),
        term=st.term,
    )

    st_out = _sel(below_commit, st, _sel(match_ok, st_ok, st))
    resp = _sel(below_commit, resp_below, _sel(match_ok, resp_ok, resp_rej))
    return st_out, resp


def _snapshot_conf_words(st: BatchedState):
    """The configuration a snapshot states (cfg.replace_replicas), in
    the two int32 fields a T_SNAP leaves unused: `reject_hint` holds the
    voters (low half, slot s worth 1 << s) and the outgoing voters (high
    half), `ctx` the learners and LearnersNext. A configuration is joint
    exactly when it has outgoing voters (upstream's own definition), so
    `in_joint` does not travel."""
    bits = 1 << jnp.arange(st.voter.shape[-1], dtype=I32)
    word = lambda lo, hi: (  # noqa: E731
        jnp.sum(jnp.where(lo, bits, 0)) | (jnp.sum(jnp.where(hi, bits, 0)) << 16))
    return (word(st.voter, st.voter_out & st.in_joint),
            word(st.learner, st.conf.learner_next))


def _handle_snapshot(cfg: BatchedConfig, st: BatchedState, m: MsgSlots,
                     slot=None):
    """Follower snapshot install (ref: raft.go:1518-1614 restore).
    m.index/m.log_term carry the snapshot (index, term). Without
    cfg.replace_replicas the conf state rides host-side (or, with
    conf_entries alone, no snapshot is ever sent across a change) and
    the membership masks are taken to be current. With it the snapshot
    states the configuration as of its index (_snapshot_conf_words; the
    sender takes it at its applied index, _emit) and the replica that
    restores it takes the four masks, `in_joint` and LearnersNext from
    the message, as raft.restore rebuilds the tracker from the
    ConfState (confchange.Restore, restore.go:155); like upstream it
    refuses a snapshot whose configuration does not hold it."""
    no_resp = empty_msgs((), 0)
    ignore = m.index <= st.commit
    fast_forward = termlog.term_at(cfg, st, m.index) == m.log_term

    st_ff = st._replace(commit=jnp.maximum(st.commit, m.index))
    st_restore = st._replace(
        log_term=jnp.zeros_like(st.log_term),
        snap_index=m.index,
        snap_term=m.log_term,
        last=m.index,
        commit=m.index,
    )
    if cfg.conf_entries:
        # A log replaced by a snapshot holds no entry to mark. (Without
        # replace_replicas the masks stay as they are: the ConfState a
        # snapshot carries upstream does not travel then.)
        st_restore = st_restore._replace(conf=st.conf._replace(
            index=jnp.zeros_like(st.conf.index),
            op=jnp.zeros_like(st.conf.op)))
    if cfg.replace_replicas:
        peers = jnp.arange(st.voter.shape[-1], dtype=I32)
        half = lambda word, sh: ((word >> (peers + sh)) & 1) == 1  # noqa: E731
        voter, voter_out = half(m.reject_hint, 0), half(m.reject_hint, 16)
        learner, learner_next = half(m.ctx, 0), half(m.ctx, 16)
        ignore = ignore | ~_pick_b(voter | voter_out | learner, peers == slot)
        st_restore = st_restore._replace(
            voter=voter, voter_out=voter_out, learner=learner,
            in_joint=jnp.any(voter_out),
            conf=st_restore.conf._replace(learner_next=learner_next))
    restored = ~ignore & ~fast_forward
    st_out = _sel(ignore, st, _sel(fast_forward, st_ff, st_restore))
    resp = no_resp._replace(
        valid=True,
        type=jnp.asarray(T_APP_RESP, I32),
        index=jnp.where(restored, m.index, st_out.commit),
        term=st.term,
    )
    return st_out, resp


# -----------------------------------------------------------------------------
# Phases: deliver / tick / control / propose / emit
# -----------------------------------------------------------------------------


# -----------------------------------------------------------------------------
# Deliver (the order named cfg.deliver_shape == "vectorized"): each
# inbox lane is ONE fold over the sender axis, no per-sender loop. The
# protocol structure this rests on: per round each sender contributes
# at most ONE message per lane, the response lanes' effects are
# order-invariant reductions over distinct progress columns (sender s
# only ever touches column s; commit/read-quorum are single global
# recomputes), and request lanes admit at most one effective winner
# after term gating (one leader per term; votes record at most one
# grant). Where the order of a lane's messages DOES matter — a
# higher-term message deposing the receiver mid-lane — this is the
# ORDER CONTRACT, which the shadow oracle steps message by message
# (shadow.ShadowCluster._deliver_vectorized_target) and every
# differential test holds the program to:
#
#   * lanes process in kind order 0..5;
#   * request lanes: the winner (highest term, lowest sender) delivers
#     first through the full handler; losers then answer against the
#     post-winner state (stale nudges; equal-term losers cannot exist
#     in-protocol — the shadow raises on them);
#   * the vote lane orders T_VOTE (term desc, sender asc) before every
#     T_PREVOTE (prevotes never change state, so they all evaluate
#     against the post-vote state);
#   * response lanes: same-term effects first (commutative), then the
#     single highest-term depose, re-gated against the post-effect
#     term.
# -----------------------------------------------------------------------------


def _argfirst(mask):
    """Index of the first set bit of a [R] bool mask (0 if none)."""
    return jnp.argmax(mask).astype(I32)


def _gather_msg(msgs: MsgSlots, at, chain: bool = False) -> MsgSlots:
    """msgs[w] for a traced winner index, as one-hot compare+reduce per
    field (at = senders == w): traced-index gathers serialize on TPU,
    one-hot reads don't (the _pick_b discipline, tree-wide). With
    `chain` (static) the entries' terms are picked by R - 1 selects and
    no reduce: at E = 64 the reduce over the senders of a [R, E] field
    makes the TPU compiler lay the lane's whole cond out instance-major
    (every [N, R] plane of the state copied there and back in both
    branches, the entries relaid E-minor: 56 M estimated cycles for 25 M
    in the append lane's branch, PERF.md section 6, PR 50; E = 16 does
    not flip, E = 32 does)."""
    def pick(x):
        sel = at if x.ndim == 1 else at[:, None]
        if x.dtype == jnp.bool_:
            return jnp.any(x & sel, axis=0)
        if chain and x.ndim == 2:
            out = x[0]
            for s in range(1, x.shape[0]):
                out = jnp.where(at[s], x[s], out)
            return out
        return jnp.sum(jnp.where(sel, x, 0), axis=0)

    return jax.tree.map(pick, msgs)


def _vec_lane_request(cfg: BatchedConfig, iid, slot, st: BatchedState,
                      m: MsgSlots, handler, k: int):
    """One request lane (KIND_APP / KIND_HB), vectorized: at most one
    in-protocol message can take effect per (instance, lane) per round
    (there is one leader per term, and only the highest term survives
    the gate), so the winner — highest term, lowest sender — runs the
    full per-message handler once, and every loser is answered with
    the stale-leader nudge it would have received anyway, computed
    against the post-winner state (ref: raft.go:885-905).

    Returns the state and the lane's answer in its SMALL form, (the
    winner's response with the fields its lane, `k` + NUM_REQ_KINDS,
    carries, the winner's sender, the losers' nudge mask):
    ``_vec_request_resps`` widens it to the [R] response slots. The
    two are apart because this half runs under the lane's lax.cond:
    slots built inside a branch from per-instance scalars alone have
    no operand to take a layout from, and the TPU compiler then lays
    them out instance-major (R=3 padded to a 4x128 tile, 512 times
    the bytes) and copies them back at the branch's edge."""
    r = cfg.num_replicas
    senders = jnp.arange(r, dtype=I32)
    t_max = jnp.max(jnp.where(m.valid, m.term, -1))
    w = _argfirst(m.valid & (m.term == t_max))
    at_w = senders == w
    # (Of a split append lane's two halves the winner's are picked
    # apart and put together after: [E] a row and not [R, E].)
    mw = _joined(_gather_msg(m, at_w, chain=bool(cfg.log_runs)))
    st2, wresp = handler(cfg, iid, slot, st, mw, w)

    nudge = (
        m.valid & ~at_w & (m.term < st2.term)
        & jnp.asarray(cfg.check_quorum or cfg.pre_vote)
    )
    if k == KIND_HB:
        # A losing MsgTimeoutNow never draws a response
        # (ref: raft.go:885-905 applies to leader traffic only).
        nudge = nudge & (m.type != T_TIMEOUT_NOW)
    return st2, (_carried(k + NUM_REQ_KINDS, wresp), w, nudge)


def _vec_request_resps(cfg: BatchedConfig, st: BatchedState, answer,
                       occupied, k: int) -> MsgSlots:
    """[R] response slots of request lane `k` from
    ``_vec_lane_request``'s answer and the post-lane state, as their
    lane carries them; an unoccupied lane answers nothing."""
    wresp, w, nudge = answer
    wresp = _whole(k + NUM_REQ_KINDS, wresp)
    r = cfg.num_replicas
    at_w = jnp.arange(r, dtype=I32) == w
    resp = empty_msgs((r,), 0)
    return _carried(k + NUM_REQ_KINDS, _sel(occupied, resp._replace(
        valid=jnp.where(at_w, wresp.valid, nudge),
        type=jnp.where(at_w, wresp.type, T_APP_RESP),
        term=jnp.where(at_w, wresp.term, st.term),
        log_term=jnp.where(at_w, wresp.log_term, 0),
        index=jnp.where(at_w, wresp.index, 0),
        commit=jnp.where(at_w, wresp.commit, 0),
        reject=at_w & wresp.reject,
        reject_hint=jnp.where(at_w, wresp.reject_hint, 0),
        n_ents=jnp.where(at_w, wresp.n_ents, 0),
        ctx=jnp.where(at_w, wresp.ctx, 0),
    ), resp))


def _vec_lane_vote(cfg: BatchedConfig, iid, slot, st: BatchedState,
                   m: MsgSlots, last_term):
    """Lane KIND_VOTE, vectorized. State effects come only from T_VOTE
    at the highest surviving term: one depose (become_follower) and at
    most one recorded grant — if the vote is already cast only its
    holder can re-grant; if it is free the first up-to-date sender
    takes it (sender-ascending, as the oracle stepping the lane's
    votes one at a time grants it).
    Prevotes never mutate state, so all prevote responses evaluate
    against the post-vote state in one masked shot. ``last_term`` is
    the term of the receiver's last log entry; the caller reads it,
    so ``st.log_term`` is not looked at here (_deliver_vectorized
    hands in a state without its ring)."""
    r = cfg.num_replicas
    senders = jnp.arange(r, dtype=I32)
    is_vote = m.type == T_VOTE
    is_pre = m.type == T_PREVOTE

    # Leases block higher-term requests unless transfer-flagged
    # (ref: raft.go:870-880); evaluated against lane-entry state for
    # T_VOTE (the winner is the first message delivered).
    def lease_block(stx):
        in_lease = (
            jnp.asarray(cfg.check_quorum)
            & (stx.lead != 0)
            & (stx.election_elapsed < cfg.election_timeout)
        )
        return (m.term > stx.term) & in_lease & ~(m.ctx == 1)

    vmask = m.valid & is_vote & ~lease_block(st)
    t_hi = jnp.max(jnp.where(vmask, m.term, -1))
    st1 = _sel(
        t_hi > st.term,
        _become_follower(cfg, st, iid, slot, jnp.maximum(t_hi, st.term),
                         jnp.zeros_like(st.lead)),
        st,
    )

    eq = vmask & (m.term == st1.term)
    up_to_date = (m.log_term > last_term) | (
        (m.log_term == last_term) & (m.index >= st1.last)
    )
    can_vote = (st1.vote == senders + 1) | (
        (st1.vote == 0) & (st1.lead == 0)
    )
    grantable = eq & can_vote & up_to_date & ~st1.fenced
    has_grant = jnp.any(grantable)
    granted = grantable & (senders == _argfirst(grantable))
    st2 = st1._replace(
        vote=jnp.where(has_grant, _argfirst(grantable) + 1, st1.vote),
        election_elapsed=jnp.where(has_grant, 0, st1.election_elapsed),
    )

    # Prevote responses against the post-vote state (no state change:
    # grants never record, ref: raft.go:960-972 m.Type == MsgPreVote).
    pv = m.valid & is_pre & ~lease_block(st2)
    lower_p = m.term < st2.term
    # can_vote above read st1.vote; a grant recorded this lane changes
    # it, so prevotes re-derive against st2.
    can_pre = (st2.vote == senders + 1) | (
        (st2.vote == 0) & (st2.lead == 0)
    ) | (m.term > st2.term)
    grant_p = pv & ~lower_p & can_pre & up_to_date & ~st2.fenced

    resp = empty_msgs((r,), 0)
    resp = resp._replace(
        valid=eq | pv,
        type=jnp.where(is_vote, T_VOTE_RESP, T_PREVOTE_RESP),
        term=jnp.where(grant_p, m.term,
                       jnp.broadcast_to(st2.term, (r,))),
        reject=jnp.where(is_vote, ~granted, ~grant_p),
    )
    return st2, _carried(KIND_VOTE_RESP, resp)


def _vec_app_resp_effects(cfg: BatchedConfig, st: BatchedState,
                          m: MsgSlots, eq):
    """Leader MsgAppResp handling (ref: raft.go:1106-1283) for every
    same-term MsgAppResp at once — sender s's message only ever
    touches progress column s, so stepping the R messages one by one
    collapses to masked column updates plus ONE commit recompute and
    one bcast/resend fold. `eq` gates to valid same-term T_APP_RESP
    on a leader."""
    prog = _repl_targets(st)
    ok = eq & prog
    # recent_active is recorded for every handled message, progress row
    # or not (raft.go sets it before looking the progress up).
    st_in = st._replace(recent_active=st.recent_active | eq)

    # --- rejected: move next back using the hint (raft.go:1130-1236) ---
    hint = jax.vmap(
        lambda idx, t: termlog.find_conflict(cfg, st, idx, t)
    )(m.reject_hint, m.log_term)
    hint = jnp.where(m.log_term > 0, hint, m.reject_hint)
    in_repl = st.pr_state == REPLICATE
    stale_rej = jnp.where(
        in_repl, m.index <= st.match, st.next - 1 != m.index
    )
    dec_next = jnp.where(
        in_repl,
        st.match + 1,
        jnp.maximum(jnp.minimum(m.index, hint + 1), 1),
    )
    rej = ok & m.reject & ~stale_rej
    # On a genuine rejection a replicating peer drops to probing
    # (becomeProbe: next=match+1, reset probe bookkeeping).
    #
    # Stale-high match repair: a follower that rejects the probe at
    # next-1 with a hint BELOW our recorded match has verifiably lost
    # entries it once acked — reachable only when durability was
    # violated under it (torn WAL tail). The reference keeps match
    # untouched (its Next >= Match+1 invariant makes this state
    # unreachable in-model), but keeping it here pins next <= match and
    # the accept path then drops every re-ack at-or-below match
    # (`updated` false) — the restarted-member progress wedge: next
    # frozen, the missing suffix never re-sent. Lowering match is
    # always safe (commit is monotone and never re-derived), so take
    # the follower's own evidence and let normal probing re-heal.
    match_repair = rej & (dec_next <= st.match)

    # --- accepted: MaybeUpdate + state transitions ---
    old_paused = _paused(cfg, st)
    updated = st.match < m.index
    accu = ok & ~m.reject & updated
    new_match = jnp.maximum(st.match, m.index)
    was_probe = st.pr_state == PROBE
    was_snap = (st.pr_state == SNAPSHOT) & (
        new_match >= st.pending_snapshot
    )
    to_repl = accu & (was_probe | was_snap)

    match1 = jnp.where(match_repair, dec_next - 1, st.match)
    match1 = jnp.where(accu, new_match, match1)
    next1 = jnp.where(rej, dec_next, st.next)
    next1 = jnp.where(accu, jnp.maximum(st.next, m.index + 1), next1)
    next1 = jnp.where(to_repl, new_match + 1, next1)
    pr1 = jnp.where(rej & in_repl, PROBE, st.pr_state)
    pr1 = jnp.where(to_repl, REPLICATE, pr1)
    st2 = st_in._replace(
        match=match1,
        next=next1,
        pr_state=pr1,
        probe_sent=st.probe_sent & ~rej & ~accu,
        pending_snapshot=jnp.where(
            (rej & in_repl) | to_repl, 0, st.pending_snapshot),
        inflight=jnp.where((rej & in_repl) | accu, 0, st.inflight),
        send_append=st.send_append | rej,
    )
    # ONE commit recompute: commit is monotone in match and the
    # per-message recomputes' fixpoint equals the recompute on the
    # final match plane (leader log terms above an own-term entry stay
    # own-term, so the term gate cannot flip between prefix and final).
    commit0 = st.commit
    st2 = _maybe_commit(st2)
    advanced = st2.commit > commit0
    # bcastAppend on commit advance; per-column resend to previously
    # paused peers / peers with entries remaining (raft.go:1259-1276).
    resend = accu & (old_paused | (st2.last >= next1))
    st2 = st2._replace(
        send_append=jnp.where(
            advanced,
            st2.send_append | _repl_targets(st2),
            st2.send_append | resend,
        )
    )
    return _sel(jnp.any(eq), st2, st)


def _vec_depose(cfg: BatchedConfig, iid, slot, st: BatchedState,
                m: MsgSlots):
    """The response-lane depose tail: become follower at the highest
    term carried by any deposing message, re-gated against the
    post-effect state (a candidacy won this lane may have raised the
    term past the depose)."""
    keep = (m.type == T_PREVOTE_RESP) & ~m.reject
    deposing = m.valid & (m.term > st.term) & ~keep
    dep_t = jnp.max(jnp.where(deposing, m.term, -1))
    return _sel(
        dep_t > st.term,
        _become_follower(cfg, st, iid, slot, jnp.maximum(dep_t, st.term),
                         jnp.zeros_like(st.lead)),
        st,
    )


def _vec_lane_vote_resp(cfg: BatchedConfig, iid, slot, st: BatchedState,
                        m: MsgSlots):
    """Lane KIND_VOTE_RESP, vectorized: record every same-term tally
    vote at once (distinct senders → distinct slots; the verdict a
    decisive prefix of them gives one at a time equals the full
    tally's, since
    grants can only keep a won verdict and rejections a lost one),
    then resolve won/lost once, then the depose tail."""
    keep = (m.type == T_PREVOTE_RESP) & ~m.reject
    is_cand = (st.role == CANDIDATE) | (st.role == PRECANDIDATE)
    my_resp_type = jnp.where(
        st.role == PRECANDIDATE, T_PREVOTE_RESP, T_VOTE_RESP
    )
    tally = (
        m.valid
        & ~(m.term < st.term)
        & ~((m.term > st.term) & ~keep)
        & (m.type == my_resp_type)
        & is_cand
    )
    votes = jnp.where(
        tally & (st.votes == -1), jnp.where(m.reject, 0, 1), st.votes
    )
    st_t = st._replace(votes=votes)
    res = joint_vote_result(votes, st.voter, st.voter_out, st.in_joint)
    won, lost = res == VOTE_WON, res == VOTE_LOST
    if cfg.pre_vote:
        st_won_pre = _campaign(cfg, st_t, iid, slot, False)
    else:
        st_won_pre = st_t
    st_won_real = _become_leader(cfg, st_t, iid, slot)
    peers_mask = _repl_targets(st_won_real) & (
        jnp.arange(st.match.shape[-1], dtype=I32) != slot
    )
    st_won_real = st_won_real._replace(
        send_append=st_won_real.send_append | peers_mask
    )
    st_won = _sel(st.role == PRECANDIDATE, st_won_pre, st_won_real)
    st_lost = _become_follower(cfg, st_t, iid, slot, st_t.term,
                               jnp.zeros_like(st.lead))
    st_dec = _sel(won, st_won, _sel(lost, st_lost, st_t))
    st1 = _sel(jnp.any(tally), st_dec, st)
    return _vec_depose(cfg, iid, slot, st1, m)


def _vec_lane_app_resp(cfg: BatchedConfig, iid, slot, st: BatchedState,
                       m: MsgSlots):
    """Lane KIND_APP_RESP, vectorized: the masked column fold above,
    then the depose tail (a stale-leader nudge carrying a higher term
    lands here — raft.go:885-905)."""
    eq = (
        m.valid & (m.term == st.term) & (m.type == T_APP_RESP)
        & (st.role == LEADER)
    )
    st1 = _vec_app_resp_effects(cfg, st, m, eq)
    return _vec_depose(cfg, iid, slot, st1, m)


def _vec_lane_hb_resp(cfg: BatchedConfig, iid, slot, st: BatchedState,
                      m: MsgSlots, app_resps: bool = True):
    """Lane KIND_HB_RESP, vectorized: heartbeat acks are a masked OR
    into probe_sent/inflight/recent_active plus ONE ReadIndex quorum
    recompute (acks are monotone; quorum on the full set equals the
    checks after each ack); T_APP_RESP stale-leader probes that
    route back in this lane reuse the column fold; then the depose
    tail. Without `app_resps` (static) the column fold is not built:
    the lane of a batch that holds no valid MsgAppResp here, which then
    neither reads nor writes the log ring (_deliver_vectorized)."""
    is_leader = st.role == LEADER
    eqterm = m.valid & (m.term == st.term) & is_leader
    prog = _repl_targets(st)
    okh = eqterm & (m.type == T_HB_RESP) & prog
    apr = eqterm & (m.type == T_APP_RESP)

    full = st.inflight >= cfg.max_inflight
    st_h = st._replace(
        recent_active=st.recent_active | okh,
        probe_sent=st.probe_sent & ~okh,
        inflight=jnp.where(
            okh & (st.pr_state == REPLICATE) & full,
            jnp.maximum(st.inflight - 1, 0),
            st.inflight,
        ),
        send_append=st.send_append | (okh & (st.match < st.last)),
    )
    # ReadIndex acks (read_only.go recvAck/advance). Stepped one at a
    # time, senders ascending — the order the oracle takes a lane's
    # same-term messages in — acks stop being RECORDED once one
    # confirms the quorum (pending drops with read_ready). The fold
    # records the same set: the sender-ascending prefix up to and
    # including the quorum-confirming ack. conf_at[s] = "quorum with
    # acks from senders <= s folded in" is monotone in s, so its first
    # set bit is that ack. Bits past it are dead state either way
    # (cleared at the next batch open), and the rule costs R quorums
    # where one would confirm the same reads: ROADMAP D13 says what to
    # find out before collapsing it.
    senders = jnp.arange(st.match.shape[-1], dtype=I32)
    pending = (st_h.read_index >= 0) & ~st_h.read_ready
    inc = okh & pending & (m.ctx == st_h.read_seq) & (m.ctx > 0)
    prefix = st_h.read_acks[None, :] | (
        inc[None, :] & (senders[None, :] <= senders[:, None])
    )  # [R prefixes, R]
    conf_at = jax.vmap(
        lambda a: joint_vote_result(
            jnp.where(a, 1, -1), st_h.voter, st_h.voter_out,
            st_h.in_joint) == VOTE_WON
    )(prefix)
    confirmed = jnp.any(conf_at)  # == quorum over the full fold
    rec = inc & (~confirmed | (senders <= _argfirst(conf_at)))
    st_h = st_h._replace(
        read_acks=st_h.read_acks | rec,
        read_ready=st_h.read_ready
        | (pending & confirmed & jnp.any(okh)),
    )
    if app_resps:
        st_h = _vec_app_resp_effects(cfg, st_h, m, apr)
    return _vec_depose(cfg, iid, slot, st_h, m)


def _deliver_vectorized(cfg: BatchedConfig, iid, slot, st: BatchedState,
                        inbox: Tuple[MsgSlots, ...], lane_any=None):
    """Deliver this instance's inbox, K kind lanes of [R] slots each
    (as carried: a lane's LANE_FIELDS, or whole messages): lanes in
    kind order, each
    lane one fold over the sender axis (the order contract is in the
    section comment above). Returns the state and the responses to the
    three request lanes, each a lane of [R] slots as the round hands it
    on, with the fields it carries: the outbox's lanes ``k +
    NUM_REQ_KINDS``. No lax.scan sits
    anywhere in the round, so
    deliver→tick→control→propose→emit trace into ONE straight-line
    fused region, and the named_scope annotations (DEVICE_SCOPES)
    are attribution labels inside it.

    ``lane_any`` ([K] bool, optional) is the batch-level lane-occupancy
    vector: the CALLER reduces each lane's ``valid`` over the batch
    OUTSIDE the instance vmap so each lane's fold sits under a lax.cond
    with an UNMAPPED predicate — a lane nobody used this round (votes
    in steady state, heartbeat lanes off-cadence) costs nothing instead
    of a full masked no-op. An all-invalid lane is an exact identity,
    so the skip is bit-equivalent; None falls back to per-instance
    occupancy (the cond degrades to a select under a mapped predicate
    — correct, just unskipped).

    The vector's last two bits (``lane_occupancy``: RARE_TIMEOUT_NOW,
    RARE_APP_RESP) are the lane skip one level down, for a message type
    that is rare inside a lane that is not. The heartbeat lane's handler
    builds a whole campaign for every row (two resets, a tally,
    become_leader, its append and commit) and selects it where the
    winner is a MsgTimeoutNow; the heartbeat-response lane ends in the
    leader's whole MsgAppResp column fold, for the stale-leader nudges
    that come back in that lane. Each lane is therefore two conds: one
    on ``occupied & ~rare`` that builds neither, one on ``occupied &
    rare`` with the whole handler. Exact by construction: with no valid
    T_TIMEOUT_NOW in the batch every row's winner has ``is_ton`` false
    or is ``dead``, so the campaign is never selected; with no valid
    T_APP_RESP in the heartbeat-response lane ``apr`` is all false and
    ``_vec_app_resp_effects`` returns its input. The bits may be a
    superset (the engine takes them from the outbox, before ``isolate``
    masks ``valid``): the whole handler is exact for any batch. Nothing
    on the plain branches reads or writes the log ring (the term gate,
    become_follower and reset go by ``st.last``), so the ring goes round
    them as it goes round the vote cond. With ``lane_any=None`` the two
    lanes keep their one cond each: under a mapped predicate a cond is a
    select, and a lane split in two would compute both halves.

    The append lane of a configuration that splits it (``app_head``)
    comes as head and tail and is the same move for an append's
    entries, on the vector's BULK_APP: a ``lax.switch`` three ways,
    skipped / at the head's width Wn / whole. The winner's handler
    takes E from the entries it is handed (_handle_append, the run
    table's passes under it), so at Wn its [E, K] passes are [Wn, K].
    Exact by construction: with the bit clear every valid append has
    ``have[j] = j < n_ents`` false for j >= Wn, so ``conflict``,
    ``write_mask`` and the run table's ``first`` are false there and
    ``last``, ``commit``, the response and the log are what the whole
    handler gives; the tail is then neither read nor, by the lane
    form's invariant, anything but stale (``settled``). The bit may be
    a superset. With ``lane_any=None`` the lane keeps its one whole
    handler."""
    # A lane enters its cond as it is carried and its handler sees whole
    # messages: a field the lane does not carry is, inside the branch,
    # the zero constant a writer would have stated (_whole).
    no_resp = _carried(KIND_VOTE_RESP, empty_msgs((cfg.num_replicas,), 0))
    ringless = lambda stx: stx._replace(  # noqa: E731
        log_term=jnp.zeros((0,), I32))

    def occupied(k, m):
        if lane_any is None:
            return jnp.any(m.valid)
        return lane_any[k]

    def votes(stx):
        # No vote touches the log, so the ring goes round this cond
        # and not through it, and the one ring read the lane needs
        # (the term of the receiver's last entry) is made before it,
        # behind a barrier that keeps the compiler from sinking it
        # into the branch: a ring the taken branch reads enters the
        # cond ring-minor on TPU, and both branches then relayout all
        # of it, every round (two 25 MB copies at G=65,536; PERF.md
        # section 6, PR 29).
        m = inbox[KIND_VOTE]
        last_term = jax.lax.optimization_barrier(
            termlog.term_at(cfg, stx, stx.last))
        sty, resp = jax.lax.cond(
            occupied(KIND_VOTE, m),
            lambda sty, mx: _vec_lane_vote(
                cfg, iid, slot, sty, _whole(KIND_VOTE, mx), last_term),
            lambda sty, mx: (sty, no_resp),
            ringless(stx), m,
        )
        return sty._replace(log_term=stx.log_term), resp

    def request_cond(k, handler, pred, stx):
        # The cond holds the winner's handler; the [R] response slots
        # are widened after it (see _vec_lane_request on why).
        no_answer = (_carried(k + NUM_REQ_KINDS, empty_msgs((), 0)),
                     jnp.zeros((), I32),
                     jnp.zeros((cfg.num_replicas,), bool))
        return jax.lax.cond(
            pred,
            lambda sty, mx: _vec_lane_request(
                cfg, iid, slot, sty, _whole(k, mx), handler, k),
            lambda sty, mx: (sty, no_answer),
            stx, inbox[k],
        )

    def request(k, handler, stx):
        occ = occupied(k, inbox[k])
        stx, answer = request_cond(k, handler, occ, stx)
        return stx, _vec_request_resps(cfg, stx, answer, occ, k)

    def appends(stx):
        # A split lane three ways on (occupied, BULK_APP): skipped, at
        # the head's width, whole (the docstring's last paragraph).
        # Both live branches take the same operands, the log among
        # them, so one switch does where the heartbeat lanes' ringless
        # plain branch needed a cond of its own.
        m = inbox[KIND_APP]
        if _tail(m) is None or lane_any is None:
            return request(KIND_APP, _lane_app, stx)
        occ, bulk = lane_any[KIND_APP], lane_any[BULK_APP]
        lane = lambda sty, mx: _vec_lane_request(  # noqa: E731
            cfg, iid, slot, sty, _whole(KIND_APP, mx), _lane_app, KIND_APP)
        no_answer = (_carried(KIND_APP_RESP, empty_msgs((), 0)),
                     jnp.zeros((), I32),
                     jnp.zeros((cfg.num_replicas,), bool))
        stx, answer = jax.lax.switch(
            occ.astype(I32) + (occ & bulk).astype(I32),
            (lambda sty, mx: (sty, no_answer),
             lambda sty, mx: lane(sty, _untailed(mx)),
             lane),
            stx, m)
        return stx, _vec_request_resps(cfg, stx, answer, occ, KIND_APP)

    def state_cond(k, fn, pred, stx):
        return jax.lax.cond(
            pred,
            lambda sty, mx: fn(sty, _whole(k, mx)),
            lambda sty, mx: sty,
            stx, inbox[k],
        )

    def state_only(k, fn, stx):
        return state_cond(k, fn, occupied(k, inbox[k]), stx)

    def heartbeats(stx):
        # The lane in its two conds (the docstring's last paragraph);
        # the two small answers joined on the unmapped bit.
        if lane_any is None:
            return request(KIND_HB, _lane_hb, stx)
        occ, ton = lane_any[KIND_HB], lane_any[RARE_TIMEOUT_NOW]
        sty, plain = request_cond(
            KIND_HB, functools.partial(_lane_hb, timeout_now=False),
            occ & ~ton, ringless(stx))
        stx, full = request_cond(
            KIND_HB, _lane_hb, occ & ton,
            sty._replace(log_term=stx.log_term))
        return stx, _vec_request_resps(
            cfg, stx, _sel(ton, full, plain), occ, KIND_HB)

    def heartbeat_resps(stx):
        whole = lambda s, m: _vec_lane_hb_resp(cfg, iid, slot, s, m)  # noqa: E731
        if lane_any is None:
            return state_only(KIND_HB_RESP, whole, stx)
        occ, apr = lane_any[KIND_HB_RESP], lane_any[RARE_APP_RESP]
        sty = state_cond(
            KIND_HB_RESP,
            lambda s, m: _vec_lane_hb_resp(
                cfg, iid, slot, s, m, app_resps=False),
            occ & ~apr, ringless(stx))
        return state_cond(
            KIND_HB_RESP, whole, occ & apr,
            sty._replace(log_term=stx.log_term))

    # No lane writes send_heartbeat (tick and control set it, emit
    # clears it), so it goes round the six conds like the ring round
    # the vote cond: with the lanes arrays of their own the TPU
    # compiler threads this one pass-through field through the three
    # response conds a second time, instance-major, and copies it
    # there and back every round (three relayouts of pred[N, R], 5%
    # of the R=3 append round by its own cost; PERF.md section 6,
    # PR 31).
    send_heartbeat = st.send_heartbeat
    st = st._replace(send_heartbeat=jnp.zeros((0,), bool))
    if cfg.conf_entries:
        # LearnersNext too: only the control phase reads or writes it,
        # and with replace_replicas a snapshot's restore, so there it
        # goes through the append lane's cond and round the other five.
        learner_next = st.conf.learner_next
        without = lambda stx: stx._replace(conf=stx.conf._replace(  # noqa: E731
            learner_next=jnp.zeros((0,), bool)))
        st = without(st)
    st, r0 = votes(st)
    if cfg.replace_replicas:
        st = st._replace(conf=st.conf._replace(learner_next=learner_next))
    st, r1 = appends(st)
    if cfg.replace_replicas:
        learner_next = st.conf.learner_next
        st = without(st)
    st, r2 = heartbeats(st)
    st = state_only(
        KIND_VOTE_RESP,
        lambda s, m: _vec_lane_vote_resp(cfg, iid, slot, s, m), st)
    st = state_only(
        KIND_APP_RESP,
        lambda s, m: _vec_lane_app_resp(cfg, iid, slot, s, m), st)
    st = heartbeat_resps(st)
    st = st._replace(send_heartbeat=send_heartbeat)
    if cfg.conf_entries:
        st = st._replace(conf=st.conf._replace(learner_next=learner_next))
    return st, (r0, r1, r2)


def _tick(cfg: BatchedConfig, iid, slot, st: BatchedState, do_tick,
          do_campaign):
    """ref: raft.go:645-684 tickElection/tickHeartbeat."""
    r = cfg.num_replicas
    peers = jnp.arange(r, dtype=I32)
    is_leader = st.role == LEADER

    ee = st.election_elapsed + jnp.where(do_tick, 1, 0)
    he = st.heartbeat_elapsed + jnp.where(do_tick & is_leader, 1, 0)

    # Leader heartbeat firing.
    hb_fire = is_leader & (he >= cfg.heartbeat_timeout)
    cq_fire = is_leader & (ee >= cfg.election_timeout)
    st1 = st._replace(
        election_elapsed=jnp.where(cq_fire, 0, ee),
        heartbeat_elapsed=jnp.where(hb_fire, 0, he),
        send_heartbeat=st.send_heartbeat
        | (hb_fire & _repl_targets(st) & (peers != slot)),
        # A transfer that outlives one election timeout is aborted
        # (ref: raft.go:670-678 tickHeartbeat abortLeaderTransfer).
        transferee=jnp.where(cq_fire, 0, st.transferee),
        transfer_sent=jnp.where(cq_fire, False, st.transfer_sent),
        # Leader lease decays in the same tick currency the electorate
        # measures leader silence in (see BatchedState.lease_ticks for
        # the safety argument); quorum evidence re-arms it below and in
        # the post-emit freshness check.
        lease_ticks=jnp.maximum(
            st.lease_ticks - jnp.where(do_tick & is_leader, 1, 0), 0),
    )
    if cfg.check_quorum:
        # Leader self-check every election timeout: step down when a
        # quorum hasn't been heard from, then re-arm the activity bits
        # (ref: raft.go:997-1018 MsgCheckQuorum).
        active = jnp.where(peers == slot, True, st1.recent_active)
        votes = jnp.where(active, 1, 0)
        alive = joint_vote_result(
            votes, st1.voter, st1.voter_out, st1.in_joint
        ) == VOTE_WON
        st_down = _become_follower(cfg, st1, iid, slot, st1.term, 0)
        st1 = _sel(cq_fire & ~alive, st_down, st1)
        st1 = st1._replace(
            recent_active=jnp.where(
                cq_fire, peers == slot, st1.recent_active
            ),
            # A passed quorum self-check is exactly the evidence the
            # lease leans on: a quorum heard from us within the last
            # election_timeout, so no rival can assemble a quorum for
            # at least that long again.
            lease_ticks=jnp.where(
                cq_fire & alive & (st1.transferee == 0),
                cfg.election_timeout, st1.lease_ticks),
        )

    # Follower/candidate election firing (hup gated on promotability —
    # learners never campaign, ref: raft.go:760-784). Durability-fenced
    # instances never fire: campaigning on a log that verifiably lost
    # acked entries is how a torn member forces a survivor to overwrite
    # a committed entry (the out-of-contract divergence the fence
    # closes); the fence also swallows host-staged campaign nudges.
    promotable = _pick_b(_vote_targets(st), peers == slot)
    fire = (
        (~is_leader & (ee >= st.randomized_timeout)) | do_campaign
    ) & promotable & (st.role != LEADER) & ~st.fenced
    st1 = st1._replace(
        election_elapsed=jnp.where(fire & ~is_leader, 0, st1.election_elapsed)
    )
    # A campaign that wins here (a single-voter group) appends ONE entry,
    # so its ring write is one column wide: [N, W, 1] is a reshape of the
    # ring and fuses into tick. The P-column write's [N, W, P] compare is
    # the first ring-sized value after deliver, computed from the last
    # lane cond's output alone, and XLA's conditional code motion may
    # sink it into both branches of that cond (PERF.md section 6, PR 48
    # and PR 49). Tick's alone: a campaign under a lane cond
    # (_lane_hb's, _vec_lane_vote_resp's) needs the P-column write's
    # reduce for the ring's layout (kernels.ring_write_masked).
    st_camp = _campaign(cfg, st1, iid, slot, cfg.pre_vote, cols=1)
    return _sel(fire, st_camp, st1)


def _conf_fields(cfg: BatchedConfig, code, peers):
    """(kind, the slot a state.conf_code names as a mask over `peers`,
    its second slot as one): the narrow word as it has always been read
    (kind in two bits, one slot; the second mask is None), and with
    cfg.replace_replicas the wide one (state.conf_decode)."""
    if not cfg.replace_replicas:
        return code & 3, peers == (code >> 2), None
    kind, s1, s2 = conf_decode(code)
    return kind, peers == s1, peers == s2


def _conf_apply(cfg: BatchedConfig, slot, st: BatchedState):
    """The apply point of a configuration change (cfg.conf_entries):
    the replica whose commit has reached the change its log holds
    unapplied flips its own masks, leader and follower alike, each in
    its own round (ref: raft.go applyConfChange -> confchange.Changer
    EnterJoint / LeaveJoint / Simple, then switchToConfig). Returns the
    state and whether a change was applied.

    With cfg.replace_replicas the two wide kinds besides, which let a
    replica enter and leave (the branches on `wide` below; without it
    nothing of them is traced). CONF_ADD_LEARNER, a simple change,
    makes a learner of a slot in nobody's masks and gives it upstream's
    initProgress (match 0, next the applier's last index, PROBE,
    recently active: CheckQuorum must not count a replica against the
    leader before it could answer). CONF_SWAP enters a joint
    configuration whose incoming half has the learner `slot` for the
    voter `slot2`; `slot2` stays in the outgoing half, and its row
    stays, until LeaveJoint, which deletes the row of every slot the
    configuration no longer names."""
    wide = cfg.replace_replicas
    r = cfg.num_replicas
    peers = jnp.arange(r, dtype=I32)
    c = st.conf
    due = (c.index > st.applied) & (st.commit >= c.index)
    kind, at, at2 = _conf_fields(cfg, c.op, peers)
    demote, promote, leave = (
        kind == CONF_DEMOTE, kind == CONF_PROMOTE, kind == CONF_LEAVE)
    enter = demote | promote
    # Who leaves `learner` for the incoming half, and what the masks
    # are where no joint kind says otherwise.
    seated, voter_else, learner_else = promote, st.voter, st.learner
    if wide:
        swap = kind == CONF_SWAP
        enter, seated = enter | swap, promote | swap
        tracked = _repl_targets(st)
        born = at & (kind == CONF_ADD_LEARNER) & ~tracked
        voter_else = jnp.where(swap, (st.voter | at) & ~at2, st.voter)
        learner_else = st.learner | born
    # EnterJoint copies the voters to the outgoing half, then makes the
    # one change: AddLearnerNode takes a voter out of the incoming half
    # and, as the outgoing half still counts it, parks it in
    # LearnersNext (a slot that was no voter is a learner at once);
    # AddNode makes a voter of a learner. LeaveJoint turns LearnersNext
    # into learners and drops the outgoing half.
    voter = jnp.where(demote, st.voter & ~at,
                      jnp.where(promote, st.voter | at, voter_else))
    voter_out = jnp.where(enter, st.voter, st.voter_out & ~leave)
    learner = jnp.where(
        demote, st.learner | (at & ~st.voter),
        jnp.where(seated, st.learner & ~at,
                  jnp.where(leave, st.learner | c.learner_next,
                            learner_else)))
    in_joint = (st.in_joint | enter) & ~leave
    parked = c.learner_next | (at & st.voter)
    waiting = c.learner_next & ~(at & seated)
    if wide:
        waiting = waiting & ~(at2 & swap)
    st_new = st._replace(
        voter=voter, voter_out=voter_out, learner=learner,
        in_joint=in_joint,
        conf=c._replace(
            learner_next=jnp.where(demote, parked, waiting & ~leave)),
    )
    if wide:
        gone = tracked & ~_repl_targets(st_new)
        fresh = born | gone
        st_new = st_new._replace(
            match=jnp.where(fresh, 0, st_new.match),
            next=jnp.where(born, st.last, jnp.where(gone, 1, st_new.next)),
            pr_state=jnp.where(fresh, PROBE, st_new.pr_state),
            probe_sent=st_new.probe_sent & ~fresh,
            pending_snapshot=jnp.where(fresh, 0, st_new.pending_snapshot),
            recent_active=(st_new.recent_active | born) & ~gone,
            inflight=jnp.where(fresh, 0, st_new.inflight),
        )
    return _switch_to_config(cfg, slot, peers, st, st_new, due), due


def _switch_to_config(cfg: BatchedConfig, slot, peers, st: BatchedState,
                      st_new: BatchedState, due):
    """raft.switchToConfig at a replica's apply point: `st` as it
    stood, `st_new` with the change made, `due` whether there was one
    to make."""
    # switchToConfig on a leader that is still a voter of the new
    # configuration: the quorum may have shrunk, so commit and tell
    # everyone, else send what a peer lacks (sendIfEmpty false); a
    # transfer to a slot that is no voter any more is off. A leader
    # demoted or removed stays as it is until it steps down.
    at_self = peers == slot
    electorate = _vote_targets(st_new)
    leads_on = (st.role == LEADER) & _pick_b(electorate, at_self)
    st_lead = _maybe_commit(st_new)
    behind = _repl_targets(st_lead) & ~at_self & (st_lead.last >= st_lead.next)
    keep_transfer = _pick_b(electorate, peers == st_lead.transferee - 1)
    st_lead = st_lead._replace(
        send_append=st_lead.send_append | jnp.where(
            st_lead.commit > st.commit,
            _repl_targets(st_lead) & ~at_self, behind),
        transferee=jnp.where(keep_transfer, st_lead.transferee, 0),
        transfer_sent=st_lead.transfer_sent & keep_transfer,
    )
    return _sel(due, _sel(leads_on, st_lead, st_new), st)


def _conf_propose(cfg: BatchedConfig, slot, st: BatchedState, conf_req):
    """A leader takes the configuration change on offer (`conf_req`, a
    state.conf_code; 0 none) as an entry of its log and marks it (ref:
    raft.go:1043-1077 stepLeader MsgProp): not while an earlier one may
    be unapplied, not with a transfer in flight, not into or out of a
    joint configuration from the wrong side. Upstream turns a refused
    change into an empty entry; here it is not appended at all, and a
    change that would change nothing (demoting a slot that is no
    voter, promoting one that is no learner) is not taken either: an
    offer stands round after round until a leader has appended it, so
    it has to be idempotent. The ring's back-pressure holds it as it
    holds a proposal."""
    r = cfg.num_replicas
    peers = jnp.arange(r, dtype=I32)
    c = st.conf
    kind, at, at2 = _conf_fields(cfg, conf_req, peers)
    leave, outside = kind == CONF_LEAVE, ~st.in_joint
    enters = (((kind == CONF_DEMOTE) & _pick_b(st.voter, at))
              | ((kind == CONF_PROMOTE) & _pick_b(st.learner, at)))
    if cfg.replace_replicas:
        # The wide kinds too: a learner is added where the slot is in
        # nobody's masks, and the swap is taken only by a leader whose
        # row for the learner is REPLICATE: the stand-in for etcd's
        # isLearnerReady (server.go:1446), which a member promote has
        # to pass.
        ready = _pick_b(st.pr_state == REPLICATE, at)
        enters = (enters
                  | ((kind == CONF_ADD_LEARNER)
                     & ~_pick_b(_repl_targets(st), at))
                  | ((kind == CONF_SWAP) & _pick_b(st.learner, at)
                     & _pick_b(st.voter, at2) & ready))
    fits = jnp.where(leave, st.in_joint, outside & enters)
    room = cfg.window - (st.last - st.snap_index) - cfg.max_props_per_round
    accept = (
        (st.role == LEADER) & (st.transferee == 0)
        & _pick_b(_repl_targets(st), peers == slot)
        & (conf_req > 0) & fits & (c.pending <= st.applied) & (room > 0)
    )
    st2 = _append_own(cfg, st, slot, jnp.asarray(1, I32))
    st2 = st2._replace(
        conf=c._replace(index=st2.last, op=conf_req, pending=st2.last),
        send_append=st2.send_append
        | (_repl_targets(st2) & (peers != slot)),
    )
    return _sel(accept, st2, st)


def _control(cfg: BatchedConfig, slot, st: BatchedState, transfer_to,
             read_req, conf_req=None, wipe=None, iid=None):
    """Host control plane: leader-transfer requests and ReadIndex
    rounds (ref: raft.go:1339-1372 stepLeader MsgTransferLeader;
    raft.go:1078-1096 MsgReadIndex + read_only.go addRequest) and,
    with cfg.conf_entries, configuration changes: first the apply
    point of one this replica's commit has reached (_conf_apply), last
    the one on offer (_conf_propose).

    `transfer_to` is slot+1 (0 = none); `read_req` asks the leader to
    open a read batch at its current commit index; `conf_req` is the
    change offered. All are no-ops on non-leaders (the host routes
    requests to the leader instance). With cfg.replace_replicas the
    phase begins with the replica reset: where `wipe` says so the
    instance becomes the empty replica (state.empty_replica: a retired
    machine's slot handed to a fresh process over empty storage), and
    the rest of the phase finds nothing to do on it. Returns the state
    and, with cfg.conf_entries, whether a change was applied (else
    None)."""
    r = cfg.num_replicas
    peers = jnp.arange(r, dtype=I32)
    conf_applied = None
    if cfg.replace_replicas:
        st = _sel(wipe, empty_replica(cfg, st, iid), st)
    if cfg.conf_entries:
        st, conf_applied = _conf_apply(cfg, slot, st)
    is_leader = st.role == LEADER

    # --- leader transfer -----------------------------------------------------
    target = transfer_to - 1
    valid_target = (
        is_leader
        & (transfer_to > 0)
        & (transfer_to != slot + 1)          # self-transfer is a no-op
        & (transfer_to != st.transferee)     # dup request ignored
        & _pick_b(_vote_targets(st), peers == target)  # learners can't lead
    )
    st_tr = st._replace(
        transferee=transfer_to,
        transfer_sent=jnp.zeros_like(st.transfer_sent),
        election_elapsed=jnp.zeros_like(st.election_elapsed),
        # Last-chance catch-up append (raft.go:1367-1371 sendAppend).
        send_append=st.send_append
        | ((peers == target) & (st.match < st.last)),
        # A transferring leader stops serving lease reads NOW: the
        # target may campaign (TimeoutNow pierces leases) before our
        # lease would have decayed.
        lease_ticks=jnp.zeros_like(st.lease_ticks),
    )
    st = _sel(valid_target, st_tr, st)

    # --- ReadIndex -----------------------------------------------------------
    # Leader must have committed in its own term before serving reads
    # (ref: raft.go:1813-1825 pending queue until first commit), and a
    # batch in flight must not be clobbered (its in-flight acks would
    # be orphaned). Unserviceable requests latch and open the next
    # batch when the blocker clears — read_only.go's pending queue.
    committed_in_term = (st.own_from > 0) & (st.commit >= st.own_from)
    batch_pending = (st.read_index >= 0) & ~st.read_ready
    want = read_req | st.read_req_latch
    accept = is_leader & want & committed_in_term & ~batch_pending
    acks0 = peers == slot
    votes0 = jnp.where(acks0, 1, -1)
    solo = joint_vote_result(
        votes0, st.voter, st.voter_out, st.in_joint
    ) == VOTE_WON
    st_rd = st._replace(
        read_seq=st.read_seq + 1,
        read_index=st.commit,
        read_acks=acks0,
        read_ready=solo,  # single-voter group confirms instantly
        # Confirmation heartbeats to the electorate (bcastHeartbeat-
        # WithCtx, raft.go:1827-1843); emit stamps ctx = read_seq.
        send_heartbeat=st.send_heartbeat
        | (_repl_targets(st) & (peers != slot)),
    )
    st = _sel(accept, st_rd, st)
    st = st._replace(read_req_latch=want & ~accept)
    if cfg.conf_entries:
        st = _conf_propose(cfg, slot, st, conf_req)
    return st, conf_applied


def _propose(cfg: BatchedConfig, slot, st: BatchedState, n_new):
    """Append n_new proposals on leader instances; payload bytes stay in
    the host arena keyed by (group, index) (ref: v3_server.go Propose →
    appendEntry → bcastAppend)."""
    r = cfg.num_replicas
    peers = jnp.arange(r, dtype=I32)
    # Proposals are dropped while a leadership transfer is in flight
    # (ref: raft.go:1048-1053 ErrProposalDropped on leadTransferee) and
    # on a leader that has been removed from the config — no progress
    # for self means no proposals (ref: raft.go:1043-1046
    # "not currently a member of the range").
    self_tracked = _pick_b(_repl_targets(st), peers == slot)
    is_leader = (st.role == LEADER) & (st.transferee == 0) & self_tracked
    headroom = jnp.maximum(
        cfg.window - (st.last - st.snap_index) - cfg.max_props_per_round, 0
    )
    n = jnp.clip(jnp.where(is_leader, n_new, 0), 0, cfg.max_props_per_round)
    n = jnp.minimum(n, headroom)
    st2 = _append_own(cfg, st, slot, n)
    st2 = st2._replace(
        send_append=st2.send_append
        | ((n > 0) & _repl_targets(st2) & (peers != slot))
    )
    return _sel(n > 0, st2, st)


def _apply_and_compact(cfg: BatchedConfig, st: BatchedState,
                       conf_applied=None) -> BatchedState:
    """Device-side apply + compaction, before anything is sent:
    committed == applied on device (payload apply is the host's job,
    driven from the commit watermark), and with auto_compact the
    snapshot floor chases the applied watermark so the ring never
    fills. Stale ring slots below the floor need no clearing — term_at
    bounds exclude them. `conf_applied` (cfg.conf_entries) is
    _control's word that this round's apply point was taken."""
    upto = st.commit
    if cfg.conf_entries:
        # `applied` stops short of a configuration change this replica
        # has yet to apply (one committed in the round it was appended,
        # under a quorum of one): the control phase takes it next round.
        waits = (st.conf.index > st.applied) & ~conf_applied
        upto = jnp.where(waits, jnp.minimum(upto, st.conf.index - 1), upto)
    st = st._replace(applied=jnp.maximum(st.applied, upto))
    if cfg.auto_compact:
        keep = cfg.window // 2
        floor = jnp.minimum(st.applied, st.last - keep)
        new_snap = jnp.maximum(st.snap_index, floor)
        # The floor's term: a follower's as often as a leader's, so the
        # one read of the ring every round makes for emit.
        st = st._replace(
            snap_term=termlog.term_at(cfg, st, new_snap),
            snap_index=new_snap)
    return st


def _appends_due(cfg: BatchedConfig, slot, st: BatchedState):
    """(app, snp, prev), each [R]: the peers an append is due to and
    those a snapshot is (both hold ``is_leader``), and the index before
    the first entry a peer lacks (ref: raft.go:432-492
    maybeSendAppend)."""
    not_self = jnp.arange(cfg.num_replicas, dtype=I32) != slot
    want = (st.send_append & _repl_targets(st) & not_self
            & (st.role == LEADER) & ~_paused(cfg, st))
    prev = st.next - 1
    snap_needed = prev < st.snap_index
    return want & ~snap_needed, want & snap_needed, prev


def _asks_below(cfg: BatchedConfig, slot, st: BatchedState):
    """Whether this row's emit states a term that its own-term boundary
    does not answer: a candidate's vote request (the term of its last
    entry), an append whose previous entry lies below ``own_from`` (a
    new leader's first to a peer, a probe walking back), a snapshot
    with cfg.replace_replicas taken at an applied index below it. Of
    the state as emit finds it (after _apply_and_compact)."""
    app, snp, prev = _appends_due(cfg, slot, st)
    below = st.send_vote_req | jnp.any(app & (prev < st.own_from))
    if cfg.replace_replicas:
        below = below | (jnp.any(snp) & (st.applied < st.own_from))
    return below


def _sends_bulk(cfg: BatchedConfig, slot, st: BatchedState, head: int):
    """Whether this row's emit sends an append of more entries than a
    split lane's head holds (`head`, ``app_head``): of the state as
    emit finds it, like ``_asks_below``."""
    app, _, prev = _appends_due(cfg, slot, st)
    n_send = jnp.clip(st.last - prev, 0, cfg.max_ents_per_msg)
    return jnp.any(app & (n_send > head))


def _emit(cfg: BatchedConfig, slot, st: BatchedState, ring_read=None,
          head: int = 0, bulk=None, spent=None):
    """Materialize pending sends into the three request lanes of the
    outbox (KIND_VOTE, KIND_APP, KIND_HB, each [R] slots addressed by
    target) and clear flags, of the state _apply_and_compact leaves. The
    lanes leave as they are computed: nothing here builds [R, K], and
    only KIND_APP's ``ent_terms`` has columns.

    The terms a message states (of the entries an append carries and of
    the one before them, of a vote request's last entry, of the applied
    index a snapshot with cfg.replace_replicas stands at) are a
    leader's questions about its own log, all but the candidate's: an
    entry at or above ``own_from`` is of the sender's term
    (BatchedState.own_from). `ring_read` is ONE unmapped bit, whether
    any row of the batch asks below its boundary (_asks_below, reduced
    by the round outside the instance vmap wherever it holds a batch's
    lane occupancy): the ring is then read for these terms in a
    lax.cond on it, the own term standing in on the other branch, and
    the floor's term (_apply_and_compact) is the one read of the ring
    both make. Exact by construction: with the bit false no row sends a
    vote request and every append or snapshot a leader sends reaches no
    lower than its boundary, so the terms the ring would give are the
    sender's own in every slot that is ``valid`` (the rest may differ:
    the vote lane's ``log_term`` of a row that asks for no vote, every
    lane of a row the round cuts off, which the round therefore leaves
    out of the bit). The bit may be a superset: the ring is exact for
    any batch. None (under
    a mapped predicate a cond is a select and would compute both) reads
    the ring as ever.

    Where the append lane is split (`head` = ``app_head(cfg)``, static;
    0: it is not, or the round was handed a lane in one piece) the
    entries leave in two halves (``BulkLane``). The head's Wn columns
    are built as ever. The tail's indexes, terms, mask and select stand
    under `bulk`, ONE unmapped bit like `ring_read` and reduced beside
    it: whether any row of the batch sends an append of more than Wn
    entries (_sends_bulk; no superset: it is the outbox's BULK_APP to
    the bit, which route() moves the tail on). With the bit false no
    tail is built, zeros included: the branch hands on `spent`, the
    tail of the inbox this round delivered, a buffer nobody reads
    again, so that a round of steady appends neither writes, wipes nor
    copies E - Wn columns a slot; what it holds then means nothing
    (``settled``). None builds the tail in every round."""
    e = cfg.max_ents_per_msg
    # The columns every round builds: all, or a split lane's head.
    wn = head or e
    r = cfg.num_replicas
    peers = jnp.arange(r, dtype=I32)
    # A field of a lane is int32 [R], one slot a target: what a sender
    # says to all alike is widened here, and a Python-int choice made
    # int32 (the branches of route_lanes' switches must agree on it).
    per_target = lambda x: jnp.broadcast_to(jnp.asarray(x, I32), (r,))  # noqa: E731

    not_self = peers != slot
    vote_peer = _vote_targets(st) & not_self
    repl_peer = _repl_targets(st) & not_self
    is_leader = st.role == LEADER

    # What is asked of the log: the sends are decided here, above the
    # terms they state.
    app, snp, prev = _appends_due(cfg, slot, st)
    n_send = jnp.clip(st.last - prev, 0, e)  # [R]
    j = jnp.arange(wn, dtype=I32)
    ent_idx = prev[:, None] + 1 + j[None, :]  # [R, E], a head's [R, Wn]

    def ring_terms(log_term):
        ta = lambda i: termlog.term_at(cfg, st, i, log_term)  # noqa: E731
        applied = (ta(st.applied),) if cfg.replace_replicas else ()
        return (ta(st.last), ta(ent_idx), ta(prev)) + applied

    def own_terms(log_term):
        own = lambda like: jnp.broadcast_to(st.term, like.shape)  # noqa: E731
        applied = (st.term,) if cfg.replace_replicas else ()
        return (st.term, own(ent_idx), own(prev)) + applied

    # jitlint: waive(tracer-branch) -- None is the argument left out, tested at trace time, never a device value
    if ring_read is None:
        terms = ring_terms(st.log_term)
    else:
        terms = jax.lax.cond(ring_read, ring_terms, own_terms, st.log_term)
    last_term, ent_terms, prev_term = terms[:3]

    # --- vote requests (ref: raft.go:822-834) ---
    vote = empty_msgs((r,), 0)._replace(
        valid=st.send_vote_req & vote_peer,
        type=per_target(jnp.where(st.vote_req_is_pre, T_PREVOTE, T_VOTE)),
        term=per_target(
            jnp.where(st.vote_req_is_pre, st.term + 1, st.term)),
        index=per_target(st.last),
        log_term=per_target(last_term),
        ctx=per_target(jnp.where(st.vote_req_transfer, 1, 0)),
    )

    # --- heartbeats + TimeoutNow (ref: raft.go:495-511; :1367-1372) ---
    # The pending read's seq rides every confirmation heartbeat
    # (bcastHeartbeatWithCtx); TimeoutNow to a caught-up transferee
    # shares the lane (a transfer supersedes that peer's heartbeat).
    hb = st.send_heartbeat & repl_peer & is_leader
    pending_read = (st.read_index >= 0) & ~st.read_ready
    hb_ctx = jnp.where(pending_read, st.read_seq, 0)
    tr = st.transferee - 1  # valid only when transferee > 0
    ton = (
        is_leader
        & (st.transferee > 0)
        & ~st.transfer_sent
        & (st.match >= st.last)  # masked to the transferee's slot below
        & (peers == tr)
    )
    heartbeat = empty_msgs((r,), 0)._replace(
        valid=hb | ton,
        type=per_target(jnp.where(ton, T_TIMEOUT_NOW, T_HB)),
        term=per_target(st.term),
        commit=jnp.minimum(st.match, st.commit),
        ctx=jnp.where(ton, 0, hb_ctx),
    )
    st = st._replace(transfer_sent=st.transfer_sent | jnp.any(ton))

    # --- appends / snapshots (ref: raft.go:432-492 maybeSendAppend) ---
    ent_mask = j[None, :] < n_send[:, None]
    # The snapshot sent is the floor's. One that states the
    # configuration (cfg.replace_replicas) is taken where this replica
    # knows it, at its applied index, which is where etcd takes its own
    # (it snapshots at the applied index and compacts CatchUpEntries
    # behind it): the masks are as of `applied`, not as of the floor.
    snap_at, snap_t = st.snap_index, st.snap_term
    if cfg.replace_replicas:
        snap_at, snap_t = st.applied, terms[3]

    append = empty_msgs((r,), wn)._replace(
        valid=app | snp,
        type=per_target(jnp.where(snp, T_SNAP, T_APP)),
        term=per_target(st.term),
        index=jnp.where(snp, snap_at, prev),
        log_term=jnp.where(snp, snap_t, prev_term),
        commit=per_target(st.commit),
        n_ents=jnp.where(app, n_send, 0),
        ent_terms=jnp.where(ent_mask & app[:, None], ent_terms, 0),
    )
    if wn < e:
        def tail(log_term, _):
            jt = jnp.arange(wn, e, dtype=I32)
            idx = prev[:, None] + 1 + jt[None, :]
            own = lambda lt: jnp.broadcast_to(st.term, idx.shape)  # noqa: E731
            ring = lambda lt: termlog.term_at(cfg, st, idx, lt)  # noqa: E731
            if ring_read is None:
                terms = ring(log_term)
            else:
                terms = jax.lax.cond(ring_read, ring, own, log_term)
            return jnp.where(
                (jt[None, :] < n_send[:, None]) & app[:, None], terms, 0)

        # jitlint: waive(tracer-branch) -- as above
        if bulk is None:
            ent_tail = tail(st.log_term, None)
        else:
            ent_tail = jax.lax.cond(
                bulk, tail, lambda _, old: old, st.log_term, spent)
        append = BulkLane(*append, ent_tail)
    if cfg.conf_entries:
        # Entry types do not travel: an append that carries the entry
        # this leader's log marks as a configuration change says so in
        # the two fields an append leaves unused, reject_hint (its
        # index) and ctx (its code). No field more, no byte more.
        c = st.conf
        marks = app & (c.index > prev) & (c.index <= prev + n_send)
        append = append._replace(
            reject_hint=jnp.where(marks, c.index, 0),
            ctx=jnp.where(marks, c.op, 0),
        )
    if cfg.replace_replicas:
        # And a snapshot states the configuration in the same two
        # fields, which it leaves unused as well.
        voters, learners = _snapshot_conf_words(st)
        append = append._replace(
            reject_hint=jnp.where(snp, voters, append.reject_hint),
            ctx=jnp.where(snp, learners, append.ctx),
        )

    # Progress effects of the sends.
    sent_ents = app & (n_send > 0)
    st = st._replace(
        probe_sent=st.probe_sent | (sent_ents & (st.pr_state == PROBE)),
        next=jnp.where(
            sent_ents & (st.pr_state == REPLICATE), st.next + n_send, st.next
        ),
        inflight=jnp.where(
            sent_ents & (st.pr_state == REPLICATE),
            st.inflight + 1,
            st.inflight,
        ),
        pr_state=jnp.where(snp, SNAPSHOT, st.pr_state),
        pending_snapshot=jnp.where(snp, snap_at, st.pending_snapshot),
        send_append=jnp.zeros_like(st.send_append),
        send_heartbeat=jnp.zeros_like(st.send_heartbeat),
        send_vote_req=jnp.zeros_like(st.send_vote_req),
        vote_req_transfer=jnp.zeros_like(st.vote_req_transfer),
    )
    return st, (vote, append, heartbeat)


# Every jax.named_scope of the device programs, (layer, segment,
# scope): the round's in execution order (lease after emit, the two
# planes where their configuration turns them on), then the closed-loop
# engine's (engine.py: the tile loops' slices in and updates out with
# what a call puts together across tiles; the ScanWatch; the rest of
# the scan's body and of a call round the round itself; for an engine
# placed one slot a device, the exchange over the interconnect and what
# the devices agree on first: ``exchange_lanes``, ``agree_lanes``, the
# ScanWatch's reduction over a group; for a phased control schedule,
# each row's own round of the cycle and what it asks there:
# ``engine.widen_phased``; for a load plane, each group's draws of the
# round and their count: ``engine.draw_load``). The strings
# are those of the named_scope calls,
# letter for letter, and of the shape benchmark/reduce/trace.py files a
# device op by (``raft_`` and lower-case letters; the innermost wins).
# tests/batched/test_scopes.py holds every equation of the closed loop
# to it: an op a trace files under no scope is one the compiler made.
DEVICE_SCOPES = (
    ("round program", "deliver", "raft_deliver"),
    ("round program", "tick", "raft_tick"),
    ("round program", "control", "raft_control"),
    ("round program", "propose", "raft_propose"),
    ("round program", "emit", "raft_emit"),
    ("round program", "lease", "raft_lease"),
    ("round program", "telemetry", "raft_telemetry"),
    ("round program", "fleet", "raft_fleet"),
    ("round program", "route", "raft_route"),
    ("closed-loop engine", "tiles", "raft_tiles"),
    ("closed-loop engine", "watch", "raft_watch"),
    ("closed-loop engine", "carry", "raft_carry"),
    ("closed-loop engine", "ici", "raft_ici"),
    ("closed-loop engine", "agree", "raft_agree"),
    ("closed-loop engine", "phase", "raft_phase"),
    ("closed-loop engine", "load", "raft_load"),
    # Of a configuration with log_runs alone: the run table's own ops
    # (termlog.py), wherever in the round they stand.
    ("round program", "log", "raft_log"),
)

# -----------------------------------------------------------------------------
# Round assembly + router
# -----------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _route_jit(r: int):
    """The router of an R-replica layout as jitted programs (inlined
    where a caller is itself being traced, e.g. the engine's scan):
    the whole exchange, and the exchange by kind lane."""

    def exchange(x):
        # inbox[n, s] = outbox[n + s - t, t] with t = n % R: the row of
        # sender slot s in n's own group, and there the column addressed
        # to t. Per sender column s that is a select, over the R values
        # of t, of outbox plane [:, t] shifted s - t rows along N. The
        # R - 1 pad rows at each end are only ever read under a mask
        # that rejects them (n + s - t stays inside n's group), so no
        # row leaks across a group, the first and the last included.
        n = x.shape[0]
        xp = jnp.pad(x, [(r - 1, r - 1)] + [(0, 0)] * (x.ndim - 1))
        t = (jnp.arange(n, dtype=I32) % r).reshape(
            (n,) + (1,) * (x.ndim - 2))

        def shifted(s, tt):
            lo = r - 1 + s - tt
            return xp[lo:lo + n, tt]

        planes = []
        for s in range(r):
            plane = shifted(s, 0)
            for tt in range(1, r):
                plane = jnp.where(t == tt, shifted(s, tt), plane)
            planes.append(plane)
        return jnp.stack(planes, axis=1)

    def route(outbox: MsgSlots) -> MsgSlots:
        with jax.named_scope("raft_route"):
            return jax.tree.map(exchange, outbox)

    # The by-lane exchange meets the same few shapes six times over:
    # a jitted callee is traced and lowered once a shape (XLA inlines
    # it again), which is a third of a warm start's time in the scan.
    exchange_lane = jax.jit(exchange)

    def route_lanes(outbox, lane_any, lanes, stale):
        # Lane by lane and never stacked: the round hands its outbox on
        # as K lanes of [N, R], each cond takes its own lane of it and
        # nothing else as operand, and what a branch returns is the
        # carry deliver's lane cond takes as it is. (A packed [N, R, K]
        # outbox cost a pad a field in emit, a slice a field and lane
        # here, and a copy of every field at the edge of all six conds:
        # PERF.md section 6, PR 33. Per-lane results concatenated back
        # into [N, R, K] relaid the whole carried inbox out, K into the
        # sublanes: PR 31.)
        # The barrier keeps emit out of the branches: a lane only its
        # exchange reads is otherwise computed inside that branch (the
        # compiler sinks it there), where nothing gives it a layout,
        # and on TPU it then comes out instance-major and is copied
        # back (R=3 padded to a 4x128 tile: PERF.md section 6, PR 33).
        # (No instruction of the compiled scan comes of the barrier
        # itself: a scope of its own read nothing in any cell, PERF.md
        # section 6, "PR 38"; it stands under route's name with the
        # rest of this function.)
        with jax.named_scope("raft_route"):
            outbox = jax.lax.optimization_barrier(outbox)
            return _exchange_written(exchange_lane, outbox, lane_any, lanes,
                                     stale)

    return (jax.jit(route, inline=True), jax.jit(route_lanes, inline=True))


def _exchange_written(exchange, outbox, lane_any, lanes, stale):
    """Lane by lane, three ways: a lane somebody wrote is exchanged
    (`exchange` of every field it carries: None, an empty pytree, is no
    operand of the switch), one that held last round's messages
    (`stale`) wiped, the rest left as they are. A split append lane's
    tail (``BulkLane.ent_tail``) goes the same three ways apart from
    its head, on the vectors' BULK_APP: exchanged in a round in which
    some append states more than the head holds, wiped after the last
    such round, and in every other handed back untouched — the
    outbox's own, which is then the spent inbox's that emit handed on
    (``_emit``: one buffer rides the round, and `lanes` gives none)."""
    def three_ways(written, was, *operands):
        return jax.lax.switch(
            jnp.where(written, 2, was.astype(I32)),
            (lambda lane, *ob: lane,
             lambda lane, *ob: jax.tree.map(jnp.zeros_like, lane),
             lambda *ob: jax.tree.map(exchange, ob[-1])),
            *operands)

    out = [three_ways(lane_any[k], stale[k], _untailed(lanes[k]),
                      _untailed(outbox[k]))
           for k in range(NUM_KINDS)]
    tail = _tail(outbox[KIND_APP])
    # jitlint: waive(tracer-branch) -- on the lane's type, at trace time
    if tail is not None:
        out[KIND_APP] = BulkLane(*out[KIND_APP], three_ways(
            lane_any[BULK_APP], stale[BULK_APP], tail))
    return tuple(out)


def route(cfg: BatchedConfig, outbox: MsgSlots, lane_any=None,
          prev=None) -> MsgSlots:
    """All-device network: outbox[i, target_slot, k] → inbox[t, sender_slot, k]
    where i = g*R + s and t = g*R + r — rafthttp's peer streams (ref:
    SURVEY.md §5 "Distributed communication backend") as an exchange
    inside each group's R adjacent rows.

    Computed on the layout the round carries, with the instance axis N
    whole: every inbox plane [:, s] is R row-shifted outbox planes
    under the mask ``n % R == t`` (R² shift-and-select terms a field,
    elementwise along N). The group and the replica never get an axis
    of their own: a ``reshape(G, R, ...) → swapaxes → reshape`` splits
    N = g*R + s out of the TPU's lane dimension and leaves R alone in
    the 128 lanes. That spelling is the oracle of
    tests/batched/test_route.py; this one is bit-identical to it for
    every R, both ``lanes_minor`` layouts and the narrow dtypes.

    With ``lane_any=None`` every lane is exchanged, as one fused
    program with no branch in it. Mesh-sharded callers need that: the
    occupancy reduce would cross shards (see ``_step_round_jit`` on
    ``lane_skip``). Given ``lane_any``, only the lanes somebody wrote
    are exchanged: ``stack_lanes`` of ``route_lanes`` of the outbox's
    ``split_lanes``, which see (its ``prev`` here is ``(inbox [N, R,
    K], stale)``; like the round itself this carries a lane's
    ``LANE_FIELDS`` alone and hands the rest back as zeros)."""
    # Lane indexes pass through untouched: by the inbox lane-order
    # contract (NUM_REQ_KINDS, top of module), emit writes requests
    # into lanes 0..NUM_REQ_KINDS-1 and the round
    # has ALREADY placed each response in lane k + NUM_REQ_KINDS of the
    # responder's outbox row for the requester (see _step_round_jit),
    # so the exchange alone lands everything in its inbox lane.
    if lane_any is None:
        return _route_jit(cfg.num_replicas)[0](outbox)
    # (The append lane in two halves where the vector has the bit for
    # it: ``lane_occupancy`` of lanes split so.)
    head = app_head(cfg) if lane_any.shape[0] > NUM_OCC else 0
    if prev is not None:
        prev = (split_lanes(prev[0], head), prev[1])
    return stack_lanes(
        route_lanes(cfg, split_lanes(outbox, head), lane_any, prev))


def route_lanes(cfg: BatchedConfig, outbox: Tuple[MsgSlots, ...], lane_any,
                prev=None) -> Tuple[MsgSlots, ...]:
    """route() by kind lane: outbox and inbox as K lanes of [N, R]
    slots (``split_lanes``' form: a lane's ``LANE_FIELDS`` alone), what
    ``step_round`` returns when handed lanes, what the engine's scan
    carries and what ``step_round`` takes as it is.

    ``lane_any`` ([K] bool) is the outbox's batch-level lane
    occupancy, ``lane_occupancy(outbox)``, reduced by the
    caller outside any vmap (the pattern of ``_deliver_vectorized``):
    a lane somebody wrote is exchanged, under a branch with that
    unmapped predicate; a lane nobody wrote comes out as ``empty_msgs``
    has it — ``valid`` false and every payload field zero, not emit's
    term, type and commit of the requests it did not send, and never
    the last round's slots (a ``valid`` left behind would deliver a
    message twice). The exchange permutes slots inside a lane, so
    ``lane_any`` is also the occupancy of the lanes returned: the
    vector deliver's lane conds skip on next round.

    ``prev`` is ``(lanes, stale)``: the spent inbox, and per lane
    whether it may hold anything but zeros. A lane empty then and now
    is handed back untouched, which costs nothing (the vote lanes
    under steady appends); one occupied then and empty now is wiped.
    Without ``prev`` an empty lane is fresh zeros.

    A split append lane (``BulkLane``, ``app_head``) is two such
    lanes: its head goes with KIND_APP's bit, its tail the same three
    ways on BULK_APP (`lane_any` and `stale` are then one longer, as
    ``lane_occupancy`` of such lanes is), so a round of steady appends moves
    the head's Wn columns and hands the tail back untouched
    (``_exchange_written`` on whose buffer that is)."""
    if prev is None:
        prev = (jax.tree.map(jnp.zeros_like, outbox),
                jnp.zeros((NUM_KINDS if _tail(outbox[KIND_APP]) is None
                           else BULK_APP + 1,), bool))
    return _route_jit(cfg.num_replicas)[1](outbox, lane_any, *prev)


def agree_lanes(lane_any, axis: str) -> jnp.ndarray:
    """``lane_occupancy`` of a batch placed one replica slot a device
    along the mesh axis `axis` (inside ``shard_map``): a lane counts as
    occupied where any device wrote it. One small all-reduce, and the
    vector every device then branches on alike: a collective inside a
    branch that only some devices take never returns, and a device cut
    off or switched off sends nothing where its peers do."""
    with jax.named_scope("raft_agree"):
        return jax.lax.psum(lane_any.astype(I32), axis) > 0


def exchange_lanes(outbox: Tuple[MsgSlots, ...], axis: str, lane_any=None,
                   prev=None) -> Tuple[MsgSlots, ...]:
    """``route_lanes`` between devices: slot s of every group lives on
    device s of the mesh axis `axis` (inside ``shard_map``; the rows at
    hand are one device's, a row a group), so ``inbox[g, s]`` on device
    t is ``outbox[g, t]`` on device s: per lane and field one
    all-to-all over `axis` on the slot axis, the peer streams of
    rafthttp as the interconnect's collective. Nothing of ``route()``
    runs, no pad, no shift, no select.

    ``lane_any`` is ``agree_lanes`` of the outbox's occupancy and
    ``prev`` is ``(lanes, stale)`` with `stale` agreed likewise, so
    every device takes the same branch of every lane: exchanged where
    some device wrote it, wiped where it held last round's messages,
    left alone otherwise (``route_lanes``' three ways). With
    ``lane_any=None`` every lane is exchanged and nothing branches (the
    eager round). One collective a field a lane carries
    (``LANE_FIELDS``): 36 where all six lanes run."""
    def swap(o):
        return jax.lax.all_to_all(o, axis, 1, 1)

    with jax.named_scope("raft_ici"):
        # jitlint: waive(tracer-branch) -- None is the argument left out, tested at trace time, never a device value
        if lane_any is None:
            return jax.tree.map(swap, outbox)
        # As in route_lanes: emit stays out of the branches.
        outbox = jax.lax.optimization_barrier(outbox)
        return _exchange_written(swap, outbox, lane_any, *prev)


class TelemetryFrame(NamedTuple):
    """Per-round kernel telemetry (cfg.telemetry): event counters in
    telemetry.TM_NAMES column order plus the on-device invariant
    bitmap (kernels.invariant_bits / telemetry.INV_NAMES)."""

    counters: jnp.ndarray  # [N, NUM_COUNTERS] i32 (per-instance [C])
    invariants: jnp.ndarray  # [N] i32 bitmap (per-instance scalar)


def _telemetry_frame(cfg: BatchedConfig, slot, pre: BatchedState,
                     post: BatchedState, inbox_i: Tuple[MsgSlots, ...],
                     out: Tuple[MsgSlots, ...], last_tick, n_new,
                     read_snap=None,
                     conf_applied=None) -> TelemetryFrame:
    """Counters for one instance's round — a pure READ of the round's
    inputs/outputs (column order = telemetry.TM_NAMES). Never touches
    protocol state, so telemetry=True stays bit-identical.
    `read_snap` and `conf_applied` are the round's own (cfg.conf_entries:
    the read state as deliver left it, and whether the control phase
    applied a configuration change)."""
    cnt = lambda m: jnp.sum(m.astype(I32))  # noqa: E731
    v = [out[k].valid for k in range(NUM_KINDS)]
    t = [out[k].type for k in range(NUM_KINDS)]
    ar = out[KIND_APP_RESP]
    ar_v = ar.valid & (ar.type == T_APP_RESP)
    appended = post.last - last_tick
    cand = lambda role: (role == CANDIDATE) | (role == PRECANDIDATE)  # noqa: E731
    won = (post.role == LEADER) & (pre.role != LEADER)
    started = (cand(post.role) & ~cand(pre.role)) | (won & ~cand(pre.role))
    if cfg.conf_entries:
        # Reads asked for in every round of a scan reopen a batch in
        # the control phase of the round whose deliver confirmed the
        # last one, so the state after the round never shows it ready:
        # count at deliver's snapshot, and a batch the control phase
        # opened and confirmed at once (a quorum of one) beside it.
        seq, _, ready = read_snap
        reads_confirmed = (ready & ~pre.read_ready).astype(I32) + (
            post.read_ready & (post.read_seq != seq)).astype(I32)
    cols = (
        cnt(v[KIND_VOTE]),
        cnt(v[KIND_APP] & (t[KIND_APP] == T_APP)),
        cnt(v[KIND_APP] & (t[KIND_APP] == T_SNAP)),
        cnt(v[KIND_HB] & (t[KIND_HB] == T_HB)),
        cnt(v[KIND_HB] & (t[KIND_HB] == T_TIMEOUT_NOW)),
        cnt(v[KIND_VOTE_RESP]),
        cnt(v[KIND_APP_RESP]),
        cnt(v[KIND_HB_RESP]),
        sum(cnt(inbox_i[k].valid) for k in range(NUM_KINDS)),
        cnt(ar_v & ~ar.reject),
        cnt(ar_v & ar.reject),
        cnt((pre.pr_state == PROBE) & (post.pr_state == REPLICATE)),
        cnt((pre.pr_state != SNAPSHOT) & (post.pr_state == SNAPSHOT)),
        cnt((pre.pr_state != PROBE) & (post.pr_state == PROBE)),
        started.astype(I32),
        won.astype(I32),
        post.commit - pre.commit,
        reads_confirmed if cfg.conf_entries
        else (post.read_ready & ~pre.read_ready).astype(I32),
        jnp.maximum(jnp.maximum(n_new, 0) - appended, 0),
        post.fenced.astype(I32),
        # conf_changes_applied: with cfg.conf_entries, this replica's
        # apply point taken this round (step._conf_apply). Without,
        # zero on device — entry types live in the host arena, so the
        # rawnode adds the count where the masks are actually staged
        # (advance_round's pending-conf application), keeping the
        # column's per-round per-group shape.
        conf_applied.astype(I32) if cfg.conf_entries
        else jnp.zeros((), I32),
    )
    counters = jnp.stack([jnp.asarray(c, I32) for c in cols])
    assert counters.shape == (NUM_COUNTERS,)
    return TelemetryFrame(counters, invariant_bits(
        post, slot, cfg.window if cfg.log_runs else None))


def _fleet_frame(cfg: BatchedConfig, pre: BatchedState,
                 post: BatchedState, iids, slots) -> jnp.ndarray:
    """The fleet SummaryFrame (cfg.fleet_summary): one flat [L] i32
    vector in obs/fleet.FleetLayout field order, computed OUTSIDE the
    per-instance vmap — every field is a cross-row reduction
    (histograms, censuses, heat bins, top-k), aggregated at the source
    so fleet visibility costs O(L), never O(G), host-side. A pure READ
    of the round's pre/post state: protocol state stays bit-identical
    and with fleet_summary=False none of this is ever traced."""
    n = post.term.shape[0]
    r = cfg.num_replicas
    layout = FleetLayout(n, r, cfg.num_groups)
    peers = jnp.arange(r, dtype=I32)

    delta = post.commit - pre.commit          # [N] commit progress
    backlog = post.last - post.commit         # [N] uncommitted tail
    is_leader = post.role == LEADER
    # Leader-side tracked peers (voters of both halves + learners,
    # self excluded) — the progress rows the pr/inflight censuses read.
    tracked = (
        (post.voter | post.voter_out | post.learner)
        & (peers[None, :] != slots[:, None])
    )
    lmask = is_leader[:, None] & tracked

    group = iids // r                         # [N] group id of each row
    hb = layout.heat_bins
    gbin = group * hb // cfg.num_groups       # [N] heat column
    heat_hit = gbin[:, None] == jnp.arange(hb, dtype=I32)[None, :]

    k = layout.top_k
    # lax.top_k makes laggards IDENTIFIABLE: the k worst-backlogged
    # rows with their full identity. The k-element gathers below are
    # negligible next to the top_k sort itself (k is 8, not G).
    top_lag, top_idx = jax.lax.top_k(backlog, k)

    # Ring-pressure lane (log-lifecycle plane): occupancy is the live
    # span of the device log ring — last minus the compaction floor.
    # The histogram shows the fleet-wide distribution (how close rows
    # run to the window W); the max is the member's high-water mark the
    # console surfaces next to the ring_full refusal counter.
    ring_occ = post.last - post.snap_index

    parts = {
        "hist_commit_delta": log_bucket_counts(delta, FLEET_BUCKETS),
        "hist_backlog": log_bucket_counts(backlog, FLEET_BUCKETS),
        "hist_inflight": log_bucket_counts_masked(
            post.inflight, FLEET_BUCKETS, lmask),
        "hist_ring_occupancy": log_bucket_counts(
            ring_occ, FLEET_BUCKETS),
        "ring_occ_max": jnp.max(ring_occ)[None],
        "leader_slot": jnp.sum(
            ((slots[:, None] == peers[None, :]) & is_leader[:, None])
            .astype(I32), axis=0),
        "role_census": jnp.sum(
            (post.role[:, None] == jnp.arange(4, dtype=I32)[None, :])
            .astype(I32), axis=0),
        "pr_census": jnp.stack([
            jnp.sum((lmask & (post.pr_state == s)).astype(I32))
            for s in (PROBE, REPLICATE, SNAPSHOT)]),
        "fenced": jnp.sum(post.fenced.astype(I32))[None],
        "term_min": jnp.min(post.term)[None],
        "term_max": jnp.max(post.term)[None],
        "term_sum": jnp.sum(post.term)[None],
        "heat_commit": jnp.sum(
            heat_hit.astype(I32) * delta[:, None], axis=0),
        "heat_backlog": jnp.sum(
            heat_hit.astype(I32) * backlog[:, None], axis=0),
        "top_group": group[top_idx],
        "top_lag": top_lag,
        "top_commit": post.commit[top_idx],
        "top_applied": post.applied[top_idx],
        "top_term": post.term[top_idx],
        "top_role": post.role[top_idx],
        "top_lead": post.lead[top_idx],
    }
    pieces = []
    for name, length, _acc in layout.fields:
        p = jnp.ravel(jnp.asarray(parts[name], I32))
        assert p.shape == (length,), (
            f"fleet frame field {name}: {p.shape} != ({length},)")
        pieces.append(p)
    return jnp.concatenate(pieces)


class StepAux(NamedTuple):
    """Per-instance mid-round snapshots the host needs.

    last_tick: log watermark after the tick phase (just before
    proposals append) — the host assigns its queued proposal payloads
    to indexes (last_tick, last], keeping payload bytes off the device
    (ref: SURVEY.md §7).

    read_*: the ReadIndex state right after delivery — a batch can
    confirm in the deliver phase and be replaced by a latched reopen in
    _control within the same round; this snapshot is how that
    confirmation still reaches Ready.ReadStates."""

    last_tick: jnp.ndarray  # [N] last log index pre-propose
    read_seq: jnp.ndarray  # [N]
    read_index: jnp.ndarray  # [N]
    read_ready: jnp.ndarray  # [N]


@functools.lru_cache(maxsize=None)
def _step_round_jit(cfg: BatchedConfig, with_aux: bool,
                    lane_skip: bool = True):
    """One jitted round program per config — shared by every engine/
    node with the same config, whatever rows it hosts (iids/slots are
    runtime arguments, so three hosting processes' nodes reuse one
    compilation per shape).

    ``lane_skip`` enables deliver's batch-level lane-occupancy conds.
    It MUST be off for mesh-sharded callers: the
    occupancy reduce (any over the sharded instance axis) would be the
    round's first cross-device collective — the sharded layout's whole
    point is that NO collective rides the hot path (row-local quorums,
    ROADMAP item 3), and concurrent per-member sharded programs
    deadlock in the AllReduce rendezvous. Without it the conds take
    per-instance predicates and batch away into selects — correct,
    merely unskipped."""
    # Recompile sentinel: one key per distinct round-step program this
    # session (the lru_cache means this runs once per config). The
    # tier-1 shape budget in tests/batched/conftest.py audits this set.
    note_compile_key(
        "round_step",
        f"{cfg}|aux={int(with_aux)}|laneskip={int(lane_skip)}")

    def step_round(st: BatchedState, inbox, tick_mask, campaign_mask,
                   propose_n, isolate, transfer_to, read_req, iids, slots,
                   lane_any=None, conf_req=None, wipe=None):
        # `conf_req` ([N] i32, state.conf_code; cfg.conf_entries only)
        # is the configuration change offered to each instance; None,
        # an empty pytree, is no input at all. `wipe` ([N] bool;
        # cfg.replace_replicas only) likewise: the instances the
        # control phase resets to the empty replica.
        # The inbox as [N, R, K] slots (a hosting process's, the eager
        # engine's) or as the K kind lanes of [N, R] the engine's scan
        # carries: deliver takes lanes, and a lane that comes as an
        # array of its own enters its cond with no slice at the edge.
        # The outbox leaves in the form the inbox came in: lanes for
        # lanes (route_lanes takes them as they are), [N, R, K] slots
        # for slots, stacked once, outside the vmap.
        packed = isinstance(inbox, MsgSlots)
        # An occupancy vector handed in that has no bit for a tail.
        short = False
        # jitlint: waive(tracer-branch) -- None is the argument left out, tested at trace time, never a device value
        if lane_any is not None:
            short = lane_any.shape[0] <= BULK_APP
        # jitlint: waive(tracer-branch) -- the branch is on the argument's pytree structure at trace time, never on a device value
        if packed:
            # (Without the lane skip every cond is a select and a lane
            # in two halves would compute both: in one piece, then; and
            # so under a vector without the bit.)
            inbox = split_lanes(
                inbox, 0 if short or not lane_skip else app_head(cfg))
        # The append lane's entries in two halves (BulkLane), if that
        # is how the inbox holds them: the outbox then leaves so too.
        # jitlint: waive(tracer-branch) -- on the lane's type, at trace time
        head = 0 if _tail(inbox[KIND_APP]) is None else app_head(cfg)
        if head and short and lane_skip:
            raise ValueError(
                "a split append lane needs lane_occupancy's vector of the "
                f"same lanes ({BULK_APP + 1} bits, BULK_APP last), got "
                f"{lane_any.shape[0]}")
        if cfg.narrow_lanes:
            # Narrow lanes live int8/int16 BETWEEN rounds (the donated
            # state carry AND the routed inbox); the protocol math runs
            # on i32 exactly as in the wide layout, so parity is by
            # construction.
            st = widen_state(st)
            inbox = tuple(map(widen_msgs, inbox))

        # Batch-level lane occupancy for deliver's lax.cond lane
        # skips: computed OUTSIDE the vmap and passed unmapped
        # (in_axes=None), so the conds stay real branches
        # instead of degrading to selects under a mapped predicate.
        # None when lane_skip is off (sharded callers — see docstring).
        # A caller that routed this inbox by lane holds the vector
        # already (route_lanes) and hands it in; lane_skip off
        # overrules it.
        if not lane_skip:
            lane_any = None
        # A caller that counts what its rounds skipped (it handed the
        # occupancy in) is told, last, whether emit read the ring.
        counted = lane_any is not None
        # jitlint: waive(tracer-branch) -- None is an empty pytree: the branch is on the argument's structure at trace time
        if lane_skip and lane_any is None:
            lane_any = lane_occupancy(inbox)  # [K]

        # The round a row, in two halves: up to emit's sends, and from
        # them on. Between the two stands the one thing a row asks of
        # the batch in mid-round: whether anybody's emit reaches below
        # its own-term boundary (_asks_below), reduced OUTSIDE the vmap
        # to an unmapped bit like `lane_any`, and wherever that is:
        # under a mapped predicate emit's cond would be a select.
        def upto_emit(iid, slot, sti, inbox_i, do_tick, do_camp, n_new,
                      iso, tr_to, rd_req, cf_req, wp, lane_any):
            # Partitioned instances neither receive nor send this round
            # (fault injection; ref: tests/framework bridge & pkg/proxy).
            # Phases carry jax.named_scope annotations so xprof/JAX
            # profiler traces attribute device time per phase (SURVEY
            # §5 tracing: profiler hooks around the step kernel).
            pre = sti  # round-entry state (telemetry deltas)
            inbox_i = tuple(
                inbox_i[k]._replace(valid=inbox_i[k].valid & ~iso)
                for k in range(NUM_KINDS))
            with jax.named_scope("raft_deliver"):
                sti, req_resps = _deliver_vectorized(
                    cfg, iid, slot, sti, inbox_i, lane_any)
            with jax.named_scope("raft_tick"):
                sti = _tick(cfg, iid, slot, sti, do_tick, do_camp)
            read_snap = (sti.read_seq, sti.read_index, sti.read_ready)
            with jax.named_scope("raft_control"):
                sti, conf_applied = _control(
                    cfg, slot, sti, tr_to, rd_req, cf_req, wp, iid)
            last_tick = sti.last
            with jax.named_scope("raft_propose"):
                sti = _propose(cfg, slot, sti, n_new)
            with jax.named_scope("raft_emit"):
                sti = _apply_and_compact(cfg, sti, conf_applied)
                below = wide = None
                # jitlint: waive(tracer-branch) -- on the argument's structure, as above
                if lane_any is not None:
                    # (A row cut off sends nothing, whatever it asks:
                    # a retired node's replicas campaign into the void
                    # for as long as they are away.)
                    below = _asks_below(cfg, slot, sti) & ~iso
                    if head:
                        wide = _sends_bulk(cfg, slot, sti, head) & ~iso
            return (slot, n_new, iso, pre, inbox_i, sti, req_resps,
                    read_snap, conf_applied, last_tick), (below, wide)

        def from_emit(mid, ring_read, bulk):
            (slot, n_new, iso, pre, inbox_i, sti, req_resps, read_snap,
             conf_applied, last_tick) = mid
            with jax.named_scope("raft_emit"):
                sti, out = _emit(cfg, slot, sti, ring_read, head, bulk,
                                 _tail(inbox_i[KIND_APP]))
            # The response to sender s's request of kind k is slot s of
            # lane k + NUM_REQ_KINDS; it routes back by the same
            # exchange (the inbox lane-order contract, top of module).
            # A lane leaves the row with the fields it carries: emit
            # states the rest as the zeros they are (LANE_FIELDS), and
            # dropped here, before the vmap returns, no [N, R] plane of
            # them is ever made.
            out = out + req_resps
            out = tuple(
                _carried(k, out[k]._replace(valid=out[k].valid & ~iso))
                for k in range(NUM_KINDS))
            with jax.named_scope("raft_lease"):
                # Quorum-evidence lease re-arm (BatchedState.lease_ticks):
                # commit progress this round means a quorum just acked
                # our log; a ReadIndex batch confirming means a quorum
                # just answered our heartbeat ctx. Either way no rival
                # can win for >= election_timeout of our ticks. Leaders
                # mid-transfer never re-arm; non-leaders hold zero (the
                # one step-down path, so every become_follower variant
                # is covered without touching it).
                # read_snap, not sti.read_ready: a batch can confirm in
                # deliver and be replaced by a latched reopen within
                # this same round — the confirmation still happened.
                fresh = (
                    (sti.role == LEADER) & (sti.transferee == 0)
                    & ((sti.commit > pre.commit)
                       | (read_snap[2] & ~pre.read_ready))
                )
                lease = jnp.where(
                    fresh, cfg.election_timeout, sti.lease_ticks)
                sti = sti._replace(lease_ticks=jnp.where(
                    sti.role == LEADER, lease, 0))
            ret = (sti, out, StepAux(last_tick, *read_snap))
            if cfg.telemetry:
                with jax.named_scope("raft_telemetry"):
                    ret += (_telemetry_frame(
                        cfg, slot, pre, sti, inbox_i, out, last_tick,
                        n_new, read_snap, conf_applied),)
            return ret

        def whole_round(ax, *rows):
            """Both halves over the instance axis `ax` of every array
            of `rows`, and emit's bit (None where there is no batch)."""
            mid, (below, wide) = jax.vmap(
                upto_emit, in_axes=(ax,) * len(rows) + (None,),
                out_axes=ax)(*rows, lane_any)
            ring_read = bulk = None
            # jitlint: waive(tracer-branch) -- on the argument's structure, as above
            if below is not None:
                with jax.named_scope("raft_emit"):
                    ring_read = jnp.any(below)
                    # (Of a split append lane: emit's other bit.)
                    # jitlint: waive(tracer-branch) -- on the structure, as above
                    if wide is not None:
                        bulk = jnp.any(wide)
            return jax.vmap(
                from_emit, in_axes=(ax, None, None), out_axes=ax)(
                    mid, ring_read, bulk), ring_read

        rows = (iids, slots, st, inbox, tick_mask, campaign_mask,
                propose_n, isolate, transfer_to, read_req, conf_req, wipe)
        if cfg.lanes_minor:
            # Instance axis minor inside the kernel: every elementwise
            # op fills the TPU vector lanes with N, not with R/K/W.
            to_minor = lambda x: (
                jnp.moveaxis(x, 0, -1) if x.ndim > 1 else x
            )
            to_major = lambda x: (
                jnp.moveaxis(x, -1, 0) if x.ndim > 1 else x
            )
            outs, ring_read = whole_round(-1, *jax.tree.map(to_minor, rows))
            outs = jax.tree.map(to_major, outs)
        else:
            outs, ring_read = whole_round(0, *rows)
        sti, out, aux = outs[:3]
        fleet = None
        if cfg.fleet_summary:
            # Cross-row reductions, so this lives OUTSIDE the vmap on
            # the full [N, ...] pre/post state (`st` is the widened
            # round-entry state; `sti` the widened post state).
            with jax.named_scope("raft_fleet"):
                fleet = _fleet_frame(cfg, st, sti, iids, slots)
        if cfg.narrow_lanes:
            sti = narrow_state(sti)
            # Telemetry/fleet frames above read the WIDE outbox; the
            # narrowed one is what rides the route()→inbox carry.
            out = tuple(map(narrow_msgs, out))
        # jitlint: waive(tracer-branch) -- on the argument's pytree structure, as above
        if packed:
            out = stack_lanes(out)
        # Output order: (state, outbox[, aux][, telemetry][, fleet]
        # [, emit's bit]) — callers index via the cfg flags (engine/
        # rawnode compute the positions once at build time); the bit is
        # the last of what it is handed to (`counted`, above).
        ret = (sti, out) + ((aux,) if with_aux else ())
        if cfg.telemetry:
            ret += (outs[3],)
        if cfg.fleet_summary:
            ret += (fleet,)
        # jitlint: waive(tracer-branch) -- on the argument's structure, as above
        if counted:
            ret += (ring_read,)
        return ret

    # NOT donated: hosting callers (BatchedRawNode) build the inbox by
    # zero-copy wrapping host numpy staging buffers (jnp.asarray on CPU
    # aliases the host memory), and donating an aliased buffer lets XLA
    # write outputs into memory the host still views — observed as
    # garbage outbox fields on the hosted restart path. Buffer-donation
    # round pipelining lives in the engine's closed_loop jit
    # (engine.py), whose state/inbox are always jax-native buffers.
    return jax.jit(step_round)


def make_step_round(cfg: BatchedConfig, iids=None, slots=None,
                    with_aux: bool = False, lane_skip: bool = True):
    """Build the round function:

        state, outbox[, aux] = step_round(state, inbox, tick_mask,
                                          campaign_mask, propose_n, isolate)

    (and, by keyword, ``transfer_to``, ``read_req``, for a
    configuration with ``conf_entries`` ``conf_req`` and for one with
    ``replace_replicas`` ``wipe``: what the control phase is asked).

    All arrays stay on device; chain with route() for a closed-loop
    multi-raft simulation (the dense all-replica layout), or pass
    explicit `iids`/`slots` for a hosting process that owns one replica
    slot of each group (iid = group*R + slot keeps the deterministic
    randomized-timeout hash identical across topologies). `inbox` is
    [N, R, K] slots or the K kind lanes route_lanes() returns; a caller
    that holds the inbox's lane occupancy already (route_lanes' own
    `lane_any`) may hand it in as `lane_any` and save the reduce, and
    is then handed back, last, one bool more: whether this round's emit
    read the log ring for the terms it states (_emit)."""
    # Resolve deliver_shape="auto" BEFORE the per-config jit cache so
    # "auto" and "vectorized" share one program.
    # ``lane_skip=False`` is for mesh-sharded callers — see
    # _step_round_jit on why the occupancy reduce must not cross
    # shards.
    cfg = cfg.validate().resolved()
    # Apply-plane knobs never enter the round-step program (the plane
    # is a separate jitted program, applyplane.py): strip them to
    # defaults before the per-config jit cache so apply_plane on/off
    # share ONE compiled round — the static-plane contract enforced
    # structurally, and the conftest compile-shape budget stays put.
    cfg = cfg.apply_plane_key()
    # jitlint: waive(tracer-branch) -- a tile of the engine's closed loop builds its step under trace with its rows' own ids: None is the argument left out, tested at trace time, never a device value
    if iids is None:
        iids = jnp.arange(cfg.num_instances, dtype=I32)
    else:
        iids = jnp.asarray(iids, I32)
    # jitlint: waive(tracer-branch) -- as above
    if slots is None:
        slots = iids % cfg.num_replicas
    else:
        slots = jnp.asarray(slots, I32)
    inner = _step_round_jit(cfg, with_aux, lane_skip)
    n = iids.shape[0]
    zero_i = jnp.zeros((n,), I32)
    zero_b = jnp.zeros((n,), bool)

    def step(st, inbox, tick_mask, campaign_mask, propose_n, isolate,
             transfer_to=None, read_req=None, lane_any=None, conf_req=None,
             wipe=None):
        if cfg.conf_entries:
            conf_req = zero_i if conf_req is None else conf_req
        elif conf_req is not None:
            raise ValueError(
                "conf_req needs a configuration with conf_entries")
        if cfg.replace_replicas:
            wipe = zero_b if wipe is None else wipe
        elif wipe is not None:
            raise ValueError(
                "wipe needs a configuration with replace_replicas")
        return inner(st, inbox, tick_mask, campaign_mask, propose_n,
                     isolate,
                     zero_i if transfer_to is None else transfer_to,
                     zero_b if read_req is None else read_req,
                     iids, slots, lane_any, conf_req, wipe)

    return step


# -----------------------------------------------------------------------------
# On-device outbox packing (the hosted collect fast path)
# -----------------------------------------------------------------------------

# Words per wire record: the device emits outbox messages pre-packed at
# wire widths — [M, REC_WORDS] i32 rows whose little-endian bytes ARE
# msgblock.REC_DTYPE records. The host then materializes the round's
# outbound block with one np.asarray + view-cast + boolean take instead
# of 14 fancy-indexed gathers over [n, R, K] fields (msgblock
# compact_records).
REC_WORDS = 9


@functools.lru_cache(maxsize=None)
def _pack_outbox_jit():
    # Unroutable types pack lane 0; they are never valid so the host
    # compress drops them (a -1 lane would smear into the type byte).
    lane_tab = jnp.asarray(np.maximum(LANE_OF, 0).astype(np.int32))

    def pack(valid, typ, reject, n_ents, term, log_term, index, commit,
             reject_hint, ctx, slots):
        # The outbox may arrive in narrow storage dtypes
        # (cfg.narrow_lanes → NARROW_MSG_DTYPES); the shift/or packing
        # below needs i32 words (an int8 `typ << 24` would wrap).
        typ = typ.astype(I32)
        n_ents = n_ents.astype(I32)
        n, r, _k = typ.shape
        shape = typ.shape
        rows = jnp.broadcast_to(
            jnp.arange(n, dtype=I32)[:, None, None], shape)
        to = jnp.broadcast_to(
            jnp.arange(1, r + 1, dtype=I32)[None, :, None], shape)
        frm = jnp.broadcast_to(
            (slots.astype(I32) + 1)[:, None, None], shape)
        lane = lane_tab[jnp.clip(typ, 0, NUM_WIRE_TYPES - 1)]
        # Little-endian byte lanes of REC_DTYPE's packed u1 fields.
        w_addr = to | (frm << 8) | (lane << 16) | (typ << 24)
        ne = jnp.where(typ == T_APP, n_ents, 0)
        w_flags = reject.astype(I32) | (ne << 8)
        words = jnp.stack(
            (rows, w_addr, w_flags, term, log_term, index, commit,
             reject_hint, ctx), axis=-1)
        simple = (valid & (typ != T_SNAP)).reshape(-1)
        cplx = (valid & (typ == T_SNAP)).reshape(-1)
        return words.reshape(-1, REC_WORDS), simple, cplx

    return jax.jit(pack)


def pack_outbox(out: MsgSlots, slots):
    """Pack a device outbox into wire-record words on device.

    Returns (words [M, REC_WORDS] i32, simple [M] bool, complex [M]
    bool) with M = n*R*K flat slots: `simple` marks block-eligible
    messages (everything but MsgSnap), `complex` the MsgSnap slots that
    keep the per-message object path. The words' bytes are exactly
    msgblock.REC_DTYPE, so the host-side collect is a view-cast."""
    return _pack_outbox_jit()(
        out.valid, out.type, out.reject, out.n_ents, out.term,
        out.log_term, out.index, out.commit, out.reject_hint, out.ctx,
        slots,
    )
