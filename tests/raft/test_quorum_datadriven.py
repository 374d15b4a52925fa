"""Byte-for-byte replay of the reference's quorum datadriven suites
(ref: raft/quorum/datadriven_test.go, testdata/{majority_commit,
majority_vote,joint_commit,joint_vote}.txt) through the host quorum
oracle — plus a differential pass of every case through the device
quorum kernels (etcd_tpu.batched.kernels joint_committed /
joint_vote_result), which is exactly where a missed edge case in the
batched engine would hide.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched.kernels import (
    MAX_I32,
    VOTE_LOST,
    VOTE_PENDING,
    VOTE_WON,
    joint_committed,
    joint_vote_result,
)
from etcd_tpu.raft.quorum import (
    MAX_UINT64,
    JointConfig,
    MajorityConfig,
    VoteResult,
    index_str,
)
from etcd_tpu.rafttest.datadriven import parse_file

TESTDATA = "/root/reference/raft/quorum/testdata"
if not os.path.isdir(TESTDATA):
    pytest.skip(f"reference testdata not available: {TESTDATA}",
                allow_module_level=True)
FILES = sorted(
    f for f in os.listdir(TESTDATA) if f.endswith(".txt")
)


def alternative_majority_committed_index(c: MajorityConfig, l: dict) -> int:
    """Alternative commit-index definition the reference cross-checks
    against (ref: raft/quorum/quick_test.go:85-121): the largest index
    acked by (at least) a quorum."""
    if len(c) == 0:
        return MAX_UINT64
    id_to_idx = {vid: l[vid] for vid in c if vid in l}
    idx_to_votes = {idx: 0 for idx in id_to_idx.values()}
    for idx in id_to_idx.values():
        for idy in idx_to_votes:
            if idy <= idx:
                idx_to_votes[idy] += 1
    q = len(c) // 2 + 1
    max_quorum_idx = 0
    for idx, n in idx_to_votes.items():
        if n >= q and idx > max_quorum_idx:
            max_quorum_idx = idx
    return max_quorum_idx


def parse_case(d):
    """Returns (joint, ids, idsj, idxs, votes) mirroring the reference
    harness's argument parsing (datadriven_test.go:62-110)."""
    joint = False
    ids, idsj, idxs, votes = [], [], [], []
    for arg in d.cmd_args:
        for v in arg.vals:
            if arg.key == "cfg":
                ids.append(int(v))
            elif arg.key == "cfgj":
                joint = True
                if v != "zero":
                    idsj.append(int(v))
            elif arg.key == "idx":
                idxs.append(0 if v == "_" else int(v))
            elif arg.key == "votes":
                votes.append({"y": 2, "n": 1, "_": 0}[v])
            else:
                raise ValueError(f"unknown arg {arg.key}")
    return joint, ids, idsj, idxs, votes


def make_lookuper(idxs, ids, idsj):
    """ref: datadriven_test.go makeLookuper — zero entries (from _
    placeholders) are removed: "no entry" differs from "zero entry"."""
    l = {}
    p = 0
    for vid in list(ids) + list(idsj):
        if vid in l:
            continue
        if p < len(idxs):
            l[vid] = idxs[p]
            p += 1
    return {vid: idx for vid, idx in l.items() if idx != 0}


def run_case(d) -> str:
    joint, ids, idsj, idxs, votes = parse_case(d)
    c = MajorityConfig(ids)
    cj = MajorityConfig(idsj)
    input_ = votes if d.cmd == "vote" else idxs
    voters = JointConfig(ids, idsj).ids()
    if len(voters) != len(input_):
        return (
            f"error: mismatched input (explicit or _) for voters "
            f"{sorted(voters)}: {input_}"
        )
    # Build via string concatenation exactly like the Go harness's
    # strings.Builder: Describe of an empty quorum has no trailing
    # newline, so the result renders as "<empty majority quorum>∞".
    buf = ""
    if d.cmd == "committed":
        l = make_lookuper(idxs, ids, idsj)
        acked = lambda vid: l.get(vid)  # noqa: E731
        if not joint:
            idx = c.committed_index(acked)
            buf += c.describe(acked)
            a = alternative_majority_committed_index(c, l)
            if a != idx:
                buf += f"{index_str(a)} <-- via alternative computation\n"
            a = JointConfig(ids, ()).committed_index(acked)
            if a != idx:
                buf += f"{index_str(a)} <-- via zero-joint quorum\n"
            a = JointConfig(ids, ids).committed_index(acked)
            if a != idx:
                buf += f"{index_str(a)} <-- via self-joint quorum\n"
            for vid in c:
                iidx = l.get(vid, 0)
                if idx > iidx and iidx > 0:
                    for lowered in (iidx - 1, 0):
                        lo = dict(l)
                        lo[vid] = lowered
                        lo = {k: v for k, v in lo.items() if v != 0}
                        a = c.committed_index(lambda x: lo.get(x))
                        if a != idx:
                            buf += (
                                f"{index_str(a)} <-- overlaying "
                                f"{vid}->{iidx if lowered else 0}"
                            )
            buf += f"{index_str(idx)}\n"
        else:
            cc = JointConfig(ids, idsj)
            buf += cc.describe(acked)
            idx = cc.committed_index(acked)
            a = JointConfig(idsj, ids).committed_index(acked)
            if a != idx:
                buf += f"{index_str(a)} <-- via symmetry\n"
            buf += f"{index_str(idx)}\n"
    elif d.cmd == "vote":
        ll = make_lookuper(votes, ids, idsj)
        l = {vid: v != 1 for vid, v in ll.items()}
        if not joint:
            buf += f"{c.vote_result(l)}\n"
        else:
            r = JointConfig(ids, idsj).vote_result(l)
            a = JointConfig(idsj, ids).vote_result(l)
            if a != r:
                buf += f"{a} <-- via symmetry\n"
            buf += f"{r}\n"
    else:
        raise ValueError(f"unknown command {d.cmd}")
    return buf


@pytest.mark.parametrize("fname", FILES)
def test_quorum_datadriven_parity(fname):
    """Host oracle renders every case byte-identically."""
    failures = []
    for d in parse_file(os.path.join(TESTDATA, fname)):
        actual = run_case(d)
        if actual.rstrip("\n") != d.expected.rstrip("\n"):
            failures.append(
                f"{d.pos}\n--- expected ---\n{d.expected}\n"
                f"--- actual ---\n{actual}"
            )
    assert not failures, f"{len(failures)} mismatches:\n" + "\n".join(
        failures[:3]
    )


def device_committed(ids, idsj, joint, l):
    """Adapter: arbitrary voter-id sets -> the kernel's replica-slot
    arrays. Slots are the sorted distinct ids; match defaults to 0 for
    missing acks, exactly the kernel's convention."""
    slots = sorted(set(ids) | set(idsj))
    r = max(len(slots), 1)
    match = np.zeros(r, np.int32)
    voter = np.zeros(r, bool)
    voter_out = np.zeros(r, bool)
    for s, vid in enumerate(slots):
        match[s] = l.get(vid, 0)
        voter[s] = vid in ids
        voter_out[s] = vid in idsj
    got = joint_committed(
        jnp.asarray(match), jnp.asarray(voter), jnp.asarray(voter_out),
        jnp.asarray(bool(joint)),
    )
    return int(got)


def device_vote(ids, idsj, joint, l):
    slots = sorted(set(ids) | set(idsj))
    r = max(len(slots), 1)
    votes = np.full(r, -1, np.int32)
    voter = np.zeros(r, bool)
    voter_out = np.zeros(r, bool)
    for s, vid in enumerate(slots):
        if vid in l:
            votes[s] = 1 if l[vid] else 0
        voter[s] = vid in ids
        voter_out[s] = vid in idsj
    got = joint_vote_result(
        jnp.asarray(votes), jnp.asarray(voter), jnp.asarray(voter_out),
        jnp.asarray(bool(joint)),
    )
    return int(got)


@pytest.mark.parametrize("fname", FILES)
def test_quorum_datadriven_device_kernels(fname):
    """Every datadriven case agrees with the device quorum kernels
    (commit index saturates at MAX_I32 where the host says MAX_UINT64;
    the device twin of the "commits everything" convention)."""
    kind_map = {
        VoteResult.VotePending: int(VOTE_PENDING),
        VoteResult.VoteLost: int(VOTE_LOST),
        VoteResult.VoteWon: int(VOTE_WON),
    }
    for d in parse_file(os.path.join(TESTDATA, fname)):
        joint, ids, idsj, idxs, votes = parse_case(d)
        if len(JointConfig(ids, idsj).ids()) != len(
            votes if d.cmd == "vote" else idxs
        ):
            continue  # the error-case directive
        if d.cmd == "committed":
            l = make_lookuper(idxs, ids, idsj)
            want = JointConfig(ids, idsj).committed_index(l.get) if joint \
                else MajorityConfig(ids).committed_index(l.get)
            got = device_committed(ids, idsj, joint, l)
            want32 = min(want, int(MAX_I32))
            assert got == want32, f"{d.pos}: device {got} != host {want32}"
        elif d.cmd == "vote":
            ll = make_lookuper(votes, ids, idsj)
            l = {vid: v != 1 for vid, v in ll.items()}
            want = JointConfig(ids, idsj).vote_result(l) if joint \
                else MajorityConfig(ids).vote_result(l)
            got = device_vote(ids, idsj, joint, l)
            assert got == kind_map[want], (
                f"{d.pos}: device {got} != host {want}"
            )
