"""The reconfiguration cell's comparisons beside ``compare.py``'s and
``fault_checks.py``'s (imported, not edited): what a deployment that
drains a node through joint configurations under writes and ReadIndex
reads has to hold over *all* its groups, from the state read back once
at a period's end, from the telemetry plane's per-instance totals as
the window opened and closed, from what the scan counted in its carry,
and, for the sampled groups, from the plain reference. Exact, every
limit 0. Plain arrays in, so a test can hand each function a fault.

``state[field]`` is the engine's ``[G*R, ...]`` array (``BatchedState``
fields, and ``learner_next`` of its ``ConfLanes``); instance
``g*R + s`` is replica slot s of group g.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from .compare import Check

LEADER = 2
MASKS = ("voter", "voter_out", "learner", "learner_next")
CHANGES_A_PERIOD = 4  # demote, leave, promote, leave


def membership_checks(state: Dict[str, np.ndarray], num_groups: int,
                      num_replicas: int) -> List[Check]:
    """At a period's end every replica holds its leader's view of the
    configuration, and that view is where the cycle began: all voters,
    no learner, no joint configuration. (A group without one leader is
    ``fault_checks.group_checks``' to count; here its replicas are held
    to slot 0's view.)"""
    g_n, r = num_groups, num_replicas
    leads = state["role"].reshape(g_n, r) == LEADER
    at = leads.argmax(axis=1)
    rows = np.arange(g_n)
    differ = np.zeros((g_n, r), bool)
    for f in MASKS + ("in_joint",):
        arr = state[f].reshape((g_n, r) + state[f].shape[1:])
        own = arr[rows, at][:, None]
        differ |= (arr != own).reshape(g_n, r, -1).any(axis=2)
    joint = state["in_joint"].reshape(g_n, r)
    not_home = (~state["voter"].all(axis=1) | state["voter_out"].any(axis=1)
                | state["learner"].any(axis=1)
                | state["learner_next"].any(axis=1)).reshape(g_n, r) | joint
    return [
        Check("replicas_whose_masks_differ_from_their_leaders",
              int(differ.sum()), 0),
        Check("replicas_not_all_voters_at_the_periods_end",
              int(not_home.sum()), 0),
    ]


def window_checks(commit_open: np.ndarray, commit_close: np.ndarray,
                  reads_open: np.ndarray, reads_close: np.ndarray,
                  applied_open: np.ndarray, applied_close: np.ndarray,
                  periods: int) -> List[Check]:
    """``commit_*`` are each group's highest commit, ``reads_*`` each
    group's ReadIndex batches confirmed and ``applied_*`` each
    instance's configuration changes applied (the telemetry plane's
    ``reads_confirmed`` and ``conf_changes_applied``), as the window
    opened and closed; the window is ``periods`` whole periods."""
    moved = applied_close - applied_open
    return [
        Check("groups_that_committed_nothing_in_the_window",
              int((commit_close <= commit_open).sum()), 0),
        Check("groups_that_confirmed_no_read_in_the_window",
              int((reads_close <= reads_open).sum()), 0),
        Check("replicas_that_did_not_apply_four_changes_a_period",
              int((moved != CHANGES_A_PERIOD * periods).sum()), 0),
        Check("window_of_no_whole_period", 0 if periods > 0 else 1, 0),
    ]


def run_checks(invariants: np.ndarray, counters: Dict[str, int],
               watch: Dict[str, int]) -> List[Check]:
    """Over every instance and every round of the run: the telemetry
    plane's invariant bitmap OR-ed over all rounds, its totals since
    the engine was built, and the counts the scans kept in their
    carry. A snapshot carries no ConfState on the device yet, so this
    schedule must send none."""
    return [
        Check("instances_with_an_invariant_bit_set",
              int((invariants != 0).sum()), 0),
        Check("snapshots_sent_in_the_run", int(counters["sent_snapshot"]), 0),
        Check("reads_confirmed_below_an_earlier_commit_of_the_group",
              int(watch["reads_below_commit"]), 0),
        Check("commits_in_a_joint_configuration_through_the_cut",
              int(watch["joint_commits_in_stall"]), 0),
        Check("configuration_marks_overwritten_unapplied",
              int(watch["conf_marks_lost"]), 0),
        Check("run_without_a_round_in_a_joint_configuration",
              0 if watch["joint_instance_rounds"] > 0 else 1, 0),
        Check("run_without_a_transfer_won",
              0 if counters["sent_timeout_now"] > 0
              and counters["elections_won"] > 0 else 1, 0),
    ]


def _slots(mask_row: np.ndarray) -> Tuple[int, ...]:
    return tuple(np.nonzero(mask_row)[0].tolist())


def sample_checks(state: Dict[str, np.ndarray], history: np.ndarray,
                  num_replicas: int, sample: Sequence[int],
                  ref_membership: Callable[[int], List[Tuple]],
                  ref_reads: Callable[[int], List[Tuple[int, int, bool]]],
                  ref_history: Callable[[int], List[int]]) -> List[Check]:
    """The sampled groups against the plain reference, replica by
    replica, in what ``compare.engine_checks`` does not look at: each
    replica's own view of the configuration, its read state
    (``read_seq``, ``read_index``, ``read_ready``) and its history, the
    hash of its state after every round of the run."""
    r = num_replicas
    masks_bad = reads_bad = history_bad = 0
    for g in sample:
        want_m, want_r, want_h = (ref_membership(g), ref_reads(g),
                                  ref_history(g))
        for s in range(r):
            i = g * r + s
            joint = bool(state["in_joint"][i])
            got = (_slots(state["voter"][i]),
                   _slots(state["voter_out"][i]) if joint else (),
                   _slots(state["learner"][i]),
                   _slots(state["learner_next"][i]))
            masks_bad += got != tuple(want_m[s])
            got = (int(state["read_seq"][i]), int(state["read_index"][i]),
                   bool(state["read_ready"][i]))
            reads_bad += got != tuple(want_r[s])
            history_bad += int(history[i]) != int(want_h[s])
    return [
        Check("sampled_replicas_membership_differs_from_reference",
              masks_bad, 0),
        Check("sampled_replicas_read_state_differs_from_reference",
              reads_bad, 0),
        Check("sampled_replicas_history_differs_from_reference",
              history_bad, 0),
    ]
