"""Quickcheck-style randomized equivalence properties
(ref: raft/quorum/quick_test.go TestQuick — CommittedIndex agrees with
the dumb alternative definition; raft/confchange/quick_test.go
TestConfChangeQuick — a batch of changes via one joint transition
equals the same changes as successive simple changes)."""

import os
import random

import pytest

from etcd_tpu.raft.confchange import Changer
from etcd_tpu.raft.quorum import MajorityConfig
from etcd_tpu.raft.tracker import ProgressTracker, progress_map_str
from etcd_tpu.raft.types import ConfChangeSingle, ConfChangeType

# The dumb definition lives beside the datadriven replay, which skips
# at collection without the reference's testdata; so does this file.
if not os.path.isdir("/root/reference/raft/quorum/testdata"):
    pytest.skip("reference testdata not available: "
                "/root/reference/raft/quorum/testdata",
                allow_module_level=True)

from .test_quorum_datadriven import (  # noqa: E402
    alternative_majority_committed_index,
)


def test_quick_majority_commit():
    """ref: quorum/quick_test.go:28-44 (50k cases there; 20k here)."""
    rng = random.Random(20260730)
    for case in range(20000):
        n = rng.randrange(10)
        ids = rng.sample(range(1, 2 * n + 2), n)
        c = MajorityConfig(ids)
        l = {vid: rng.randrange(1, n + 2) for vid in ids
             if rng.random() < 0.8}
        got = c.committed_index(l.get)
        want = alternative_majority_committed_index(c, l)
        assert got == want, f"case {case}: cfg={sorted(c)} l={l}"


def _gen_ccs(rng, num_range, id_fn, typ_fn):
    return [
        ConfChangeSingle(type=typ_fn(), node_id=id_fn())
        for _ in range(rng.randint(*num_range))
    ]


def _snapshot(tracker):
    return (str(tracker.config), progress_map_str(tracker.progress))


def _setup_changer(setup):
    tr = ProgressTracker(10)
    c = Changer(tr, last_index=10)
    for cc in setup:
        cfg, prs = c.simple([cc])
        tr.config, tr.progress = cfg, prs
    return c


@pytest.mark.parametrize("seed", range(4))
def test_conf_change_joint_equals_simple(seed):
    """ref: confchange/quick_test.go:30-141 (1000 cases there; 250 per
    seed here). Node 1 is always a voter so simple changes can make
    progress, and generated ids never touch it (no voterless configs)."""
    rng = random.Random(1000 + seed)
    types = list(ConfChangeType)
    for case in range(250):
        setup = [ConfChangeSingle(type=ConfChangeType.ConfChangeAddNode,
                                  node_id=1)] + _gen_ccs(
            rng, (1, 5),
            id_fn=lambda: rng.randint(1, 6),
            typ_fn=lambda: ConfChangeType.ConfChangeAddNode,
        )
        ccs = _gen_ccs(
            rng, (1, 9),
            id_fn=lambda: rng.randint(2, 10),
            typ_fn=lambda: types[rng.randrange(len(types))],
        )

        # Path 1: successive simple changes.
        c1 = _setup_changer(setup)
        for cc in ccs:
            cfg, prs = c1.simple([cc])
            c1.tracker.config, c1.tracker.progress = cfg, prs

        # Path 2: one joint transition (entered twice to check the
        # autoLeave flag changes nothing else, left twice to check
        # LeaveJoint determinism).
        c2 = _setup_changer(setup)
        cfg_a, prs_a = c2.enter_joint(False, ccs)
        cfg_b, prs_b = c2.enter_joint(True, ccs)
        cfg_b.auto_leave = False
        assert str(cfg_a) == str(cfg_b), f"case {case}"
        assert progress_map_str(prs_a) == progress_map_str(prs_b)
        c2.tracker.config, c2.tracker.progress = cfg_a, prs_a
        cfg_l1, prs_l1 = c2.leave_joint()
        c2.tracker.config, c2.tracker.progress = cfg_a, prs_a
        cfg_l2, prs_l2 = c2.leave_joint()
        assert str(cfg_l1) == str(cfg_l2), f"case {case}"
        assert progress_map_str(prs_l1) == progress_map_str(prs_l2)
        c2.tracker.config, c2.tracker.progress = cfg_l2, prs_l2

        assert _snapshot(c1.tracker) == _snapshot(c2.tracker), (
            f"case {case}: setup={setup} ccs={ccs}\n"
            f"simple={_snapshot(c1.tracker)}\n"
            f"joint={_snapshot(c2.tracker)}"
        )
