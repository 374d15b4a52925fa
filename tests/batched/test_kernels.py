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
    ring_write,
    ring_write_masked,
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

from .test_scopes import _bodies

rng = random.Random(0)
R = 8
W = 64


def _quorum_cases(r, n=400):
    """[n, r] acked indexes and voter masks: small ranges (ties), rows
    all equal, rows near the top of int32, no voter, one voter, all."""
    rs = np.random.RandomState(100 + r)
    top = rs.choice([3, 20, MAX_I32], size=(n, 1))
    match = (rs.randint(0, MAX_I32, size=(n, r)) % top).astype(np.int32)
    voter = rs.rand(n, r) < rs.rand(n, 1)
    match[0] = 7  # all equal
    match[1] = MAX_I32 - 1  # the largest index the device holds
    match[2, 0] = MAX_I32 - 1
    voter[:3] = True
    voter[3] = False  # no voter
    voter[4] = False
    voter[4, r - 1] = True  # one voter, the last slot
    voter[5] = True
    voter[5, 0] = r == 1  # all but the first
    return match, voter


def _majority_committed(match_row, voter_row):
    cfg = MajorityConfig(np.flatnonzero(voter_row).tolist())
    want = cfg.committed_index(lambda vid: int(match_row[vid]))
    return MAX_I32 if want == MAX_UINT64 else want  # device ∞ is int32 max


@pytest.mark.parametrize("r", range(1, 9))
def test_quorum_committed_matches_oracle(r):
    match, voter = _quorum_cases(r)
    fn = jax.jit(jax.vmap(quorum_committed))
    got = np.asarray(fn(jnp.asarray(match), jnp.asarray(voter)))
    assert got.dtype == np.int32
    for i in range(len(match)):
        assert got[i] == _majority_committed(match[i], voter[i]), (
            match[i], voter[i])
    # The q-th largest of R numbers is compares and selects: no sort
    # (the one op of the round the TPU compiler never fuses) and no
    # gather in the program.
    text = fn.lower(jnp.asarray(match), jnp.asarray(voter)).as_text()
    assert "stablehlo.sort" not in text and "gather" not in text


@pytest.mark.parametrize("in_joint", (False, True), ids=("majority", "joint"))
@pytest.mark.parametrize("r", range(1, 9))
def test_joint_committed_matches_oracle(r, in_joint):
    """The minimum over both halves where the configuration is joint,
    the incoming half alone where it is not, whatever the outgoing mask
    holds."""
    match, voter = _quorum_cases(r)
    voter_out = np.random.RandomState(200 + r).rand(*voter.shape) < 0.5
    voter_out[0] = False  # an empty outgoing half beside a full incoming
    voter_out[3] = False  # both halves empty
    got = np.asarray(jax.jit(jax.vmap(joint_committed, (0, 0, 0, None)))(
        jnp.asarray(match), jnp.asarray(voter), jnp.asarray(voter_out),
        jnp.asarray(in_joint)))
    for i in range(len(match)):
        want = _majority_committed(match[i], voter[i])
        if in_joint:
            want = min(want, _majority_committed(match[i], voter_out[i]))
        assert got[i] == want, (match[i], voter[i], voter_out[i])


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


def _ring_write_cases(w, k, seed):
    """Rows of (ring, start, terms, mask): wrapping and far starts,
    all-false, all-true, prefix (`count`) and holed masks, terms over
    the whole int32 range (the write copies bits, it does not add)."""
    g = np.random.default_rng(seed)
    n = 256
    ring = g.integers(-5, 1 << 20, size=(n, w), dtype=np.int32)
    start = g.integers(0, 6 * w, size=n, dtype=np.int32)
    start[:w] = np.arange(w)                      # every wrap point
    start[w:w + 8] = (1 << 30) + np.arange(8) * 5  # a long-lived log
    terms = g.integers(-(1 << 31), (1 << 31) - 1, size=(n, k),
                       dtype=np.int64).astype(np.int32)
    terms[::7] = 0
    mask = g.random((n, k)) < 0.5
    mask[0::4] = True
    mask[1::4] = False
    count = g.integers(0, k + 1, size=n, dtype=np.int32)
    count[0::5] = 0
    count[1::5] = k
    mask[2::4] = np.arange(k)[None, :] < count[2::4, None]
    return ring, start, terms, mask, count


def _scatter(ring, start, terms, mask):
    out = ring.copy()
    w = ring.shape[-1]
    for i in range(ring.shape[0]):
        for j in range(terms.shape[-1]):
            if mask[i, j]:
                out[i, (int(start[i]) + j) % w] = terms[i, j]
    return out


@pytest.mark.parametrize("form", ["masked", "count"])
@pytest.mark.parametrize("k", [1, 2, 4, 8, "W"])
@pytest.mark.parametrize("w", [16, 32])
def test_ring_write_matches_a_scatter(w, k, form):
    k = w if k == "W" else k
    ring, start, terms, mask, count = _ring_write_cases(w, k, seed=w * 100 + k)
    if form == "masked":
        got = jax.jit(jax.vmap(ring_write_masked))(ring, start, terms, mask)
    else:
        got = jax.jit(jax.vmap(ring_write))(ring, start, terms, count)
        mask = np.arange(k)[None, :] < count[:, None]
    np.testing.assert_array_equal(np.asarray(got),
                                  _scatter(ring, start, terms, mask))


def _primitives(jaxpr):
    """Every equation of a jaxpr in order, an equation that encloses
    others (a jitted callee) read through them."""
    for eqn in jaxpr.eqns:
        bodies = list(_bodies(eqn))
        if not bodies:
            yield eqn
        for body in bodies:
            yield from _primitives(body)


@pytest.mark.parametrize("fn,last", [(ring_write_masked, "mask"),
                                     (ring_write, "count")])
def test_ring_write_is_one_reduce_and_an_add(fn, last):
    """A reduce's output ends a TPU fusion, so every reduce of ring
    shape is a pass over [N, W]: the write holds ONE, a sum, and after
    it only the add that puts the old ring back. No reduce_or (the
    `any` over K the sum-and-select form had), no scatter or gather
    (they serialise)."""
    w, k, n = 32, 4, 8
    arg = (jnp.zeros((n, k), bool) if last == "mask"
           else jnp.zeros((n,), jnp.int32))
    closed = jax.make_jaxpr(jax.vmap(fn))(
        jnp.zeros((n, w), jnp.int32), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n, k), jnp.int32), arg)
    eqns = list(_primitives(closed.jaxpr))
    names = [e.primitive.name for e in eqns]
    for prim in ("reduce_or", "reduce_max", "reduce_and", "argmax",
                 "scatter", "scatter-add", "gather", "dynamic_slice",
                 "dynamic_update_slice", "while", "cond"):
        assert prim not in names, (prim, names)
    assert [x for x in names if x.startswith("reduce")] == ["reduce_sum"]
    assert names[-2:] == ["reduce_sum", "add"]
    for eqn in eqns[-2:]:
        (out,) = eqn.outvars
        assert out.aval.shape == (n, w) and out.aval.dtype == jnp.int32
