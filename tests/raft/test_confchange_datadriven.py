"""Byte-for-byte replay of the reference's confchange datadriven suite
(ref: raft/confchange/datadriven_test.go, testdata/*.txt — 9 files:
joint_autoleave, joint_idempotency, joint_learners_next, joint_safety,
simple_idempotency, simple_promote_demote, simple_safety, update, zero)
through the host Changer — plus a device differential: every resulting
config's voter/learner masks are fed to the batched quorum kernels and
must agree with the host JointConfig on vote/commit math.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched.kernels import (
    MAX_I32,
    joint_committed,
)
from etcd_tpu.raft.confchange import Changer, ConfChangeError
from etcd_tpu.raft.tracker import ProgressTracker, progress_map_str
from etcd_tpu.raft.types import ConfChangeSingle, ConfChangeType
from etcd_tpu.rafttest.datadriven import parse_file

TESTDATA = "/root/reference/raft/confchange/testdata"
if not os.path.isdir(TESTDATA):
    pytest.skip(f"reference testdata not available: {TESTDATA}",
                allow_module_level=True)
FILES = sorted(f for f in os.listdir(TESTDATA) if f.endswith(".txt"))

TOKEN_TYPES = {
    "v": ConfChangeType.ConfChangeAddNode,
    "l": ConfChangeType.ConfChangeAddLearnerNode,
    "r": ConfChangeType.ConfChangeRemoveNode,
    "u": ConfChangeType.ConfChangeUpdateNode,
}


def run_file(fname, device_check=None):
    tr = ProgressTracker(10)
    changer = Changer(tr, last_index=0)
    failures = []
    for d in parse_file(os.path.join(TESTDATA, fname)):
        actual = run_case(changer, d)
        changer.last_index += 1  # the harness's deferred LastIndex++
        if actual.rstrip("\n") != d.expected.rstrip("\n"):
            failures.append(
                f"{d.pos}\n--- expected ---\n{d.expected}\n"
                f"--- actual ---\n{actual}"
            )
        elif device_check is not None:
            device_check(d.pos, changer.tracker)
    return failures


def run_case(changer, d) -> str:
    ccs = []
    toks = d.input.strip().split(" ") if d.input.strip() else []
    for tok in toks:
        if len(tok) < 2:
            return f"unknown token {tok}"
        if tok[0] not in TOKEN_TYPES:
            return f"unknown input: {tok}"
        ccs.append(
            ConfChangeSingle(type=TOKEN_TYPES[tok[0]], node_id=int(tok[1:]))
        )
    try:
        if d.cmd == "simple":
            cfg, prs = changer.simple(ccs)
        elif d.cmd == "enter-joint":
            auto_leave = False
            for arg in d.cmd_args:
                if arg.key == "autoleave":
                    auto_leave = arg.vals[0] == "true"
            cfg, prs = changer.enter_joint(auto_leave, ccs)
        elif d.cmd == "leave-joint":
            if ccs:
                return "this command takes no input\n"
            cfg, prs = changer.leave_joint()
        else:
            return "unknown command"
    except ConfChangeError as e:
        return f"{e}\n"
    changer.tracker.config = cfg
    changer.tracker.progress = prs
    return f"{cfg}\n{progress_map_str(prs)}"


@pytest.mark.parametrize("fname", FILES)
def test_confchange_datadriven_parity(fname):
    failures = run_file(fname)
    assert not failures, f"{len(failures)} mismatches:\n" + "\n".join(
        failures[:3]
    )


@pytest.mark.parametrize("fname", FILES)
def test_confchange_datadriven_device_masks(fname):
    """After every successful command, derive the device voter masks
    from the resulting config and check the device commit kernel
    against the host joint quorum over a few match assignments — the
    confchange → set_membership mask pipeline in miniature."""

    def check(pos, tracker):
        cfg = tracker.config
        ids = sorted(
            set(cfg.voters.incoming)
            | set(cfg.voters.outgoing)
            | set(cfg.learners)
            | set(cfg.learners_next)
        )
        if not ids:
            return
        r = len(ids)
        voter = np.array([i in cfg.voters.incoming for i in ids], bool)
        voter_out = np.array([i in cfg.voters.outgoing for i in ids], bool)
        in_joint = bool(cfg.voters.outgoing)
        rng = np.random.RandomState(hash(pos) % (2**31))
        for _ in range(4):
            match = rng.randint(0, 20, size=r).astype(np.int32)
            l = {vid: int(m) for vid, m in zip(ids, match) if m > 0}
            want = cfg.voters.committed_index(l.get)
            got = int(
                joint_committed(
                    jnp.asarray(match * np.array(
                        [vid in l for vid in ids], np.int32)),
                    jnp.asarray(voter),
                    jnp.asarray(voter_out),
                    jnp.asarray(in_joint),
                )
            )
            assert got == min(want, int(MAX_I32)), (
                f"{pos}: device commit {got} != host {want}"
            )

    failures = run_file(fname, device_check=check)
    assert not failures
