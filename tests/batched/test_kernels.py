"""Kernel ↔ oracle differential tests: the replica-axis reductions and
log-ring scans must agree with the scalar reference-semantics code for
all inputs (ref: SURVEY.md §2.1 quorum / tracker rows). Device calls are
batched through one jitted vmap per kernel."""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched.kernels import (
    MAX_I32,
    VOTE_LOST,
    VOTE_PENDING,
    VOTE_WON,
    find_conflict_by_term,
    joint_committed,
    joint_vote_result,
    quorum_committed,
    term_at,
    vote_result,
)
from etcd_tpu.raft.log import RaftLog
from etcd_tpu.raft.quorum import (
    MAX_UINT64,
    JointConfig,
    MajorityConfig,
    VoteResult,
)
from etcd_tpu.raft.storage import MemoryStorage
from etcd_tpu.raft.types import ConfState, Entry, Snapshot, SnapshotMetadata

rng = random.Random(0)
R = 8
W = 64


def test_quorum_committed_matches_oracle():
    cases = []
    for _ in range(500):
        match = [rng.randint(0, 20) for _ in range(R)]
        voter = [rng.random() < 0.7 for _ in range(R)]
        cases.append((match, voter))
    match = jnp.array([c[0] for c in cases], jnp.int32)
    voter = jnp.array([c[1] for c in cases])
    got = np.asarray(jax.jit(jax.vmap(quorum_committed))(match, voter))
    for i, (m, v) in enumerate(cases):
        cfg = MajorityConfig(j for j in range(R) if v[j])
        if not cfg:
            assert got[i] == 2**31 - 1  # device ∞ is int32 max
        else:
            assert got[i] == cfg.committed_index(lambda vid: m[vid]), (m, v)


VOTE_OF = {
    VOTE_WON: VoteResult.VoteWon,
    VOTE_LOST: VoteResult.VoteLost,
    VOTE_PENDING: VoteResult.VotePending,
}


def test_vote_result_matches_oracle():
    cases = []
    for _ in range(500):
        votes = [rng.choice([-1, 0, 1]) for _ in range(R)]
        voter = [rng.random() < 0.7 for _ in range(R)]
        cases.append((votes, voter))
    votes = jnp.array([c[0] for c in cases], jnp.int32)
    voter = jnp.array([c[1] for c in cases])
    got = np.asarray(jax.jit(jax.vmap(vote_result))(votes, voter))
    for i, (vs, v) in enumerate(cases):
        cfg = MajorityConfig(j for j in range(R) if v[j])
        votes_map = {j: bool(vs[j]) for j in range(R) if vs[j] >= 0}
        assert VOTE_OF[got[i]] == cfg.vote_result(votes_map), (vs, v)


@pytest.mark.parametrize("r", [1, 3, 5, 7])
def test_joint_quorum_kernels_match_oracle(r):
    """``joint_committed`` and ``joint_vote_result`` against
    ``JointConfig`` on random rows: in-joint rows (both halves decide)
    and rows whose outgoing half is set but not in force."""
    rs = np.random.RandomState(42 + r)
    n = 700
    match = rs.randint(0, 50, size=(n, r)).astype(np.int32)
    voter = rs.rand(n, r) < 0.8
    voter_out = rs.rand(n, r) < 0.4
    in_joint = rs.rand(n) < 0.5
    votes = rs.randint(-1, 2, size=(n, r)).astype(np.int32)
    # Empty-config rows: no voter at all, and an in-joint row with
    # neither half populated.
    voter[0] = False
    in_joint[0] = False
    voter[1] = False
    voter_out[1] = False
    in_joint[1] = True

    args = (jnp.asarray(voter), jnp.asarray(voter_out),
            jnp.asarray(in_joint))
    commit = np.asarray(
        jax.jit(jax.vmap(joint_committed))(jnp.asarray(match), *args))
    vres = np.asarray(
        jax.jit(jax.vmap(joint_vote_result))(jnp.asarray(votes), *args))
    assert in_joint.any() and (~in_joint & voter_out.any(axis=1)).any()
    for i in range(n):
        cfg = JointConfig(
            np.flatnonzero(voter[i]).tolist(),
            np.flatnonzero(voter_out[i]).tolist() if in_joint[i] else ())
        want = cfg.committed_index(lambda vid, i=i: int(match[i, vid]))
        assert commit[i] == (MAX_I32 if want == MAX_UINT64 else want), i
        cast = {j: bool(votes[i, j]) for j in range(r) if votes[i, j] >= 0}
        assert VOTE_OF[vres[i]] == cfg.vote_result(cast), i


def test_an_empty_joint_config_commits_everything_and_wins_the_vote():
    """quorum/majority.go's convention for a half with no voter, which
    makes a half-populated joint quorum behave like a majority one."""
    r = 3
    none = jnp.zeros((r,), bool)
    for in_joint in (False, True):
        assert int(joint_committed(
            jnp.zeros((r,), jnp.int32), none, none,
            jnp.asarray(in_joint))) == MAX_I32
        assert int(joint_vote_result(
            jnp.full((r,), -1, jnp.int32), none, none,
            jnp.asarray(in_joint))) == VOTE_WON
    # A populated incoming half decides alone beside an empty outgoing.
    voter = jnp.asarray([True, True, True])
    assert int(joint_committed(
        jnp.asarray([5, 3, 1], jnp.int32), voter, none,
        jnp.asarray(True))) == 3


def _random_log():
    """A host RaftLog and the matching device ring."""
    snap_index = rng.randint(0, 5)
    snap_term = rng.randint(1, 3) if snap_index else 0
    n = rng.randint(0, 20)
    terms = []
    t = max(snap_term, 1)
    for _ in range(n):
        t += rng.choice([0, 0, 0, 1, 2])  # nondecreasing
        terms.append(t)

    storage = MemoryStorage()
    if snap_index:
        storage.apply_snapshot(
            Snapshot(
                metadata=SnapshotMetadata(
                    conf_state=ConfState(voters=[1]),
                    index=snap_index,
                    term=snap_term,
                )
            )
        )
    storage.append(
        [Entry(term=terms[i], index=snap_index + 1 + i) for i in range(n)]
    )
    log = RaftLog(storage)

    ring = np.zeros(W, np.int32)
    for i in range(n):
        ring[(snap_index + 1 + i) % W] = terms[i]
    last = snap_index + n
    return log, ring, snap_index, snap_term, last


def test_term_at_and_find_conflict_by_term_match_oracle():
    logs, queries_ta, queries_fc = [], [], []
    for li in range(100):
        log, ring, si, st_, last = _random_log()
        logs.append((log, ring, si, st_, last))
        for i in range(0, last + 3):
            queries_ta.append((li, i))
        for _ in range(10):
            index = rng.randint(si, last) if last > si else si
            term = rng.randint(0, 8)
            queries_fc.append((li, index, term))

    rings = jnp.array([l[1] for l in logs])
    sis = jnp.array([l[2] for l in logs], jnp.int32)
    sts = jnp.array([l[3] for l in logs], jnp.int32)
    lasts = jnp.array([l[4] for l in logs], jnp.int32)

    # term_at batch
    li_ta = jnp.array([q[0] for q in queries_ta], jnp.int32)
    i_ta = jnp.array([q[1] for q in queries_ta], jnp.int32)
    got_ta = np.asarray(
        jax.jit(jax.vmap(term_at))(
            rings[li_ta], sis[li_ta], sts[li_ta], lasts[li_ta], i_ta
        )
    )
    for k, (li, i) in enumerate(queries_ta):
        log, _, si, _, _ = logs[li]
        expect = log.zero_term_on_err_compacted(i)
        # Below the snapshot the device has no information (returns 0),
        # matching zero-term-on-compacted.
        assert got_ta[k] == expect or i < si, (li, i, got_ta[k], expect)

    # find_conflict_by_term batch
    li_fc = jnp.array([q[0] for q in queries_fc], jnp.int32)
    idx_fc = jnp.array([q[1] for q in queries_fc], jnp.int32)
    t_fc = jnp.array([q[2] for q in queries_fc], jnp.int32)
    got_fc = np.asarray(
        jax.jit(jax.vmap(find_conflict_by_term))(
            rings[li_fc], sis[li_fc], sts[li_fc], lasts[li_fc], idx_fc, t_fc
        )
    )
    for k, (li, index, term) in enumerate(queries_fc):
        log = logs[li][0]
        expect = log.find_conflict_by_term(index, term)
        assert got_fc[k] == expect, (li, index, term, got_fc[k], expect)
