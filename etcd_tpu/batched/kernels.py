"""Replica-axis and log-ring kernels for the batched engine.

These are the array forms of the scalar oracles in ``etcd_tpu.raft``:
  - quorum_committed   ↔ quorum.MajorityConfig.committed_index
                         (ref: raft/quorum/majority.go:126-172)
  - vote_result        ↔ quorum.MajorityConfig.vote_result
                         (ref: raft/quorum/majority.go:178-210)
  - term_at            ↔ raftLog.term (ref: raft/log.go:268-288)
  - find_conflict_by_term ↔ raftLog.findConflictByTerm
                         (ref: raft/log.go:150-171) — exploits that log
                         terms are nondecreasing in the index, so the
                         backward scan becomes a masked count.

All functions are written per-instance (scalars + [R]/[W] vectors) and
are used under vmap over the instance axis.
"""

from __future__ import annotations

import jax.numpy as jnp

I32 = jnp.int32
MAX_I32 = 2**31 - 1  # plain int: keep module import free of backend init

VOTE_PENDING, VOTE_LOST, VOTE_WON = 1, 2, 3


def quorum_committed(match: jnp.ndarray, voter: jnp.ndarray) -> jnp.ndarray:
    """Largest index acked by a quorum of voters.

    Go picks srt[n-(n/2+1)] of the ascending sort of n acked indexes
    (missing voters count 0). With non-voters masked to 0 that is the
    q-th largest of the R masked values, q = n//2 + 1, and the q-th
    largest of a multiset is the largest member that at least q
    members reach: max{m_j : #{i : m_i >= m_j} >= q}. The set is never
    empty (the smallest member counts all R >= q) and `match` is never
    negative, so 0 is the identity of the max.

    Written over the R static slots: R*R compares, R selects and R
    maxima on per-slot scalars, which XLA fuses into the phase that
    calls it. A `sort` is the one op of the round the TPU compiler
    never fuses (its operand goes out to memory, it runs as a kernel of
    its own padded to a power of two, and a one-hot pick reads the
    result back): PERF.md section 6, "PR 41".

    The mask is applied to the whole [R] vector BEFORE the slots are
    taken apart, and that order is load-bearing: one op of [N, R] shape
    is what keeps the instance axis minor. With voter[i] and match[i]
    sliced first (the same bits) the node-placed closed loop compiles
    with the log ring ring-minor all through tick, propose and control,
    at 3.5 times the round's cost.
    """
    r = match.shape[-1]
    n = jnp.sum(voter.astype(I32))
    q = n // 2 + 1
    masked = jnp.where(voter, match, 0)
    m = [masked[i] for i in range(r)]
    best = jnp.zeros((), I32)
    for j in range(r):
        reach = sum((m[i] >= m[j]).astype(I32) for i in range(r))
        best = jnp.maximum(best, jnp.where(reach >= q, m[j], 0))
    # Empty config commits "everything" (joint-quorum convention).
    return jnp.where(n == 0, MAX_I32, best)


def vote_result(votes: jnp.ndarray, voter: jnp.ndarray) -> jnp.ndarray:
    """VOTE_WON / VOTE_LOST / VOTE_PENDING from a [R] vote vector
    (-1 missing / 0 rejected / 1 granted) and a voter mask."""
    n = jnp.sum(voter.astype(I32))
    yes = jnp.sum((voter & (votes == 1)).astype(I32))
    no = jnp.sum((voter & (votes == 0)).astype(I32))
    missing = n - yes - no
    q = n // 2 + 1
    won = (yes >= q) | (n == 0)
    pending = yes + missing >= q
    return jnp.where(won, VOTE_WON, jnp.where(pending, VOTE_PENDING, VOTE_LOST))


def joint_committed(
    match: jnp.ndarray,
    voter: jnp.ndarray,
    voter_out: jnp.ndarray,
    in_joint: jnp.ndarray,
) -> jnp.ndarray:
    """Joint-config commit index = min over both halves
    (ref: raft/quorum/joint.go:49-56)."""
    main = quorum_committed(match, voter)
    return jnp.where(
        in_joint,
        jnp.minimum(main, quorum_committed(match, voter_out)),
        main,
    )


def joint_vote_result(
    votes: jnp.ndarray,
    voter: jnp.ndarray,
    voter_out: jnp.ndarray,
    in_joint: jnp.ndarray,
) -> jnp.ndarray:
    """Joint vote result (ref: raft/quorum/joint.go:61-75): lost if
    either half lost, pending if either half pending, else won."""
    a = vote_result(votes, voter)
    b = jnp.where(in_joint, vote_result(votes, voter_out), VOTE_WON)
    lost = (a == VOTE_LOST) | (b == VOTE_LOST)
    pending = (a == VOTE_PENDING) | (b == VOTE_PENDING)
    return jnp.where(lost, VOTE_LOST,
                     jnp.where(pending, VOTE_PENDING, VOTE_WON))


def term_at(
    log_term: jnp.ndarray,
    snap_index: jnp.ndarray,
    snap_term: jnp.ndarray,
    last: jnp.ndarray,
    i: jnp.ndarray,
) -> jnp.ndarray:
    """Term of entry i; 0 outside [snap_index, last] (the reference's
    "zero term on compacted/unavailable" behavior).

    `i` may be a scalar or an [..., K] batch of indexes; the ring read
    is a one-hot compare+reduce over W (TPU-friendly: no gathers)."""
    w = log_term.shape[-1]
    in_ring = (i > snap_index) & (i <= last)
    p = jnp.arange(w, dtype=I32)
    im = jnp.mod(jnp.clip(i, 0, None), w)
    hit = jnp.expand_dims(im, -1) == p  # [..., W]
    ring_val = jnp.sum(jnp.where(hit, log_term, 0), axis=-1)
    return jnp.where(
        i == snap_index, snap_term, jnp.where(in_ring, ring_val, 0)
    )


def find_conflict_by_term(
    log_term: jnp.ndarray,
    snap_index: jnp.ndarray,
    snap_term: jnp.ndarray,
    last: jnp.ndarray,
    index: jnp.ndarray,
    term: jnp.ndarray,
) -> jnp.ndarray:
    """Largest idx <= index with term_at(idx) <= term.

    Log terms never decrease with index, so the answer is
    snap_index + |{ j in (snap_index, min(index,last)] : term(j) <= term }|.
    Degenerates to snap_index (the dummy index) when nothing matches,
    like the reference's backward scan hitting ErrCompacted.
    """
    w = log_term.shape[-1]
    hi = jnp.minimum(index, last)
    # Iterate ring POSITIONS instead of indexes: ring slot p holds the
    # unique index i_p in (snap_index, snap_index+W] with i_p % W == p,
    # so the rotation-gather becomes a pure compare+reduce.
    p = jnp.arange(w, dtype=I32)
    idx = snap_index + 1 + jnp.mod(p - snap_index - 1, w)
    valid = idx <= hi
    cnt = jnp.sum((valid & (log_term <= term)).astype(I32))
    # When nothing in the window matches, the reference's backward walk
    # stops at the dummy index (term = snap_term) or, if even that term
    # is too large, one below it (term() reports 0 below the dummy —
    # ref: log.go:268-274).
    floor = jnp.where(snap_term <= term, snap_index, snap_index - 1)
    return jnp.where(cnt > 0, snap_index + cnt, floor)


def invariant_bits(st, slot, window=None) -> jnp.ndarray:
    """Per-instance illegal-state bitmap (bit layout:
    telemetry.INV_NAMES), computed on end-of-round state. `window` is
    the log's capacity where ``st.log_term`` is no ring of that many
    slots (BatchedConfig.log_runs: the run table), and the map then
    ends in one bit more: the floor above the applied index, where a
    full run table gave away entries that had yet to be applied.

    Everything here is impossible under the raft model — a set bit
    means either a kernel bug or a violated environment assumption
    (e.g. a torn WAL tail faking back acked state). Leader-side
    progress conditions are masked to tracked peers other than self.
    """
    # Local constants mirror state.py (state imports nothing from this
    # module, but keeping kernels import-free of state preserves the
    # existing layering for its scalar-oracle consumers).
    leader, probe, snapshot = 2, 0, 2
    r = st.match.shape[-1]
    capacity = window
    # jitlint: waive(tracer-branch) -- None is the argument left out (a static int otherwise), tested at trace time, never a device value
    if window is None:
        capacity = st.log_term.shape[-1]
    peers = jnp.arange(r, dtype=I32)
    is_leader = st.role == leader
    tracked = (st.voter | st.voter_out | st.learner) & (peers != slot)
    bad = [
        # next <= match on a tracked peer: next must stay >= match+1.
        is_leader & jnp.any(tracked & (st.next <= st.match)),
        # commit beyond the last log index.
        st.commit > st.last,
        # compaction floor above the commit watermark.
        st.snap_index > st.commit,
        # a leader whose own lead pointer names someone else.
        is_leader & (st.lead != slot + 1),
        # the progress wedge signature: paused probe that can never
        # make progress (probe_sent pinned while next <= match).
        is_leader & jnp.any(
            tracked & (st.pr_state == probe) & st.probe_sent
            & (st.next <= st.match)),
        # snapshot state whose pending index the peer already covers:
        # the accept path can never lift the pause.
        is_leader & jnp.any(
            tracked & (st.pr_state == snapshot)
            & (st.pending_snapshot <= st.match)),
        # a confirmed read batch with no batch open.
        st.read_ready & (st.read_index < 0),
        # a durability-fenced instance holding leadership: the fence
        # suppresses campaigning (and boot roles are follower), so a
        # fenced leader means the fence lane failed to gate an
        # election path — the exact hazard the fence exists to close.
        st.fenced & is_leader,
        # outgoing-voter residue outside a joint config: voter_out only
        # means anything while in_joint (quorum/commit read it through
        # the joint gates), so a nonzero row with in_joint false is a
        # conf-apply that flipped the lanes inconsistently — stale
        # outgoing voters would silently rejoin the electorate the
        # moment a later change re-enters joint.
        ~st.in_joint & jnp.any(st.voter_out),
        # ring occupancy past the window: an append crossed the
        # compaction floor and overwrote a live slot. The propose
        # headroom clamp + the host-side ring_full refusal make this
        # unreachable; a trip means log-lifecycle pressure accounting
        # broke (wrap = silent log corruption, the worst failure the
        # ring representation admits).
        (st.last - st.snap_index) > capacity,
        # leader-lease residue on a non-leader: the lease lane
        # authorizes quorum-free linearizable reads, so every
        # step-down path must zero it in the same round (step.py's
        # post-emit re-arm does exactly that) — a trip here is a
        # stale read authorization, the one failure mode the lease
        # fast path admits.
        (st.lease_ticks > 0) & ~is_leader,
    ]
    # jitlint: waive(tracer-branch) -- as above
    if window is not None:
        bad.append(st.snap_index > st.applied)
    bits = jnp.zeros((), I32)
    for i, b in enumerate(bad):
        bits = bits | (b.astype(I32) << i)
    return bits


def log_bucket_index(v: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """Log2 bucket of each non-negative value: bucket 0 holds v == 0,
    bucket b (1..num_buckets-2) holds v in [2^(b-1), 2^b), the last
    bucket is open-ended — the fleet-summary histogram discipline
    (obs/fleet.BUCKET_BOUNDS mirrors this host-side).

    Branch- and gather-free: the bucket index is the count of powers of
    two at-or-below v (a [.., B-1] compare + reduce keeps the VPU full
    instead of a serialized floor-log)."""
    thr = jnp.asarray([1 << b for b in range(num_buckets - 1)], I32)
    return jnp.sum((v[..., None] >= thr).astype(I32), axis=-1)


def log_bucket_counts_masked(v: jnp.ndarray, num_buckets: int,
                             mask: jnp.ndarray) -> jnp.ndarray:
    """[B] histogram of `v` (any leading shape) over log2 buckets,
    restricted to `mask` (same shape as v; masked-out elements count
    toward no bucket). One-hot compare + reduce — no scatters, so it
    vectorizes on TPU like the ring/quorum kernels above. The ONE
    bucketing implementation: the unmasked variant wraps it, so the
    bucket discipline cannot diverge between the two."""
    b = log_bucket_index(v, num_buckets)
    hit = (b[..., None] == jnp.arange(num_buckets, dtype=I32))
    hit = hit & mask[..., None]
    axes = tuple(range(hit.ndim - 1))
    return jnp.sum(hit.astype(I32), axis=axes)


def log_bucket_counts(v: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """Unmasked log_bucket_counts_masked (an all-true mask fuses to a
    no-op; a shared Optional-mask branch would trip the jitlint
    tracer-branch rule)."""
    return log_bucket_counts_masked(
        v, num_buckets, jnp.ones(jnp.shape(v), bool))


def ring_write(
    log_term: jnp.ndarray, start_index: jnp.ndarray, terms: jnp.ndarray,
    count: jnp.ndarray,
) -> jnp.ndarray:
    """Write `count` terms at log positions start_index..start_index+count-1
    into the [W] ring."""
    j = jnp.arange(terms.shape[-1], dtype=I32)
    return ring_write_masked(log_term, start_index, terms, j < count)


def ring_write_masked(
    log_term: jnp.ndarray, start_index: jnp.ndarray, terms: jnp.ndarray,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    """Write terms[j] at log position start_index+j for each masked j.

    Scatter-free, with ONE reduce of ring shape: a [W, K] outer
    compare says which ring slot each masked entry lands in, each hit
    carries the difference terms[j] - log_term, and the old ring plus
    the sum of the differences over K is the write. Positions are
    distinct (K <= W, consecutive indexes), so at most one difference
    in a slot is not zero and old + (term - old) is the term, bit for
    bit (int32 wraps). A reduce's output ends a TPU fusion, so each
    reduce of ring shape is a pass over the [N, W] ring: there is one,
    and the add fuses into whatever follows. It is not none, for the
    layout: K selects and no reduce are the same bits, but inside a
    lane's lax.cond nothing then gives the ring a layout, the TPU
    compiler lays the branch out ring-minor and both branches relayout
    all of it (PERF.md section 6, PR 39;
    tests/batched/test_ring_layout.py)."""
    w = log_term.shape[-1]
    k = terms.shape[-1]
    # K > W would alias ring positions and SUM colliding differences;
    # shapes are static, so this check costs nothing at runtime.
    assert k <= w, f"ring write batch {k} exceeds window {w}"
    p = jnp.arange(w, dtype=I32)
    jj = jnp.arange(k, dtype=I32)
    pos_j = jnp.mod(start_index + jj, w)  # [K]
    hit = (p[:, None] == pos_j[None, :]) & mask[None, :]  # [W, K]
    delta = jnp.where(hit, terms[None, :] - log_term[:, None], 0)
    return log_term + jnp.sum(delta, axis=-1)
