"""The trickle cell's comparisons beside ``compare.py``'s,
``fault_checks.py``'s, ``reconf_checks.py``'s and ``replace_checks.py``'s
(imported, not edited): what a deployment that moves a few groups at a
time off a node has to hold over *all* its groups, group by group by
where each stands in its own cycle, from the state read back once when
the run ends, from the telemetry plane's per-instance totals and from
what the scan counted in its carry. Exact, every limit 0. Plain arrays
in, so a test can hand each function a fault.

``state[field]`` is the engine's ``[G*R, ...]`` array; instance
``g*R + s`` is replica slot s of group g. ``k`` is ``[G]``: the round
of its own cycle each group stands in when the run ends (``rounds_done
- starts``; below 0 for a group not started, the rebalancer's NEVER
among them, the cycle's length or more for one that is done with it).
The nodes are the generator's: e the node the replicas move to, d the
node drained.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from .compare import Check
from .fault_checks import group_checks
from .replace_checks import FRESH


def rows_of(state: Dict[str, np.ndarray], groups: np.ndarray,
            slots, num_replicas: int) -> Dict[str, np.ndarray]:
    """The rows of `slots` of `groups`, group by group."""
    rows = (np.asarray(groups, np.int64)[:, None] * num_replicas
            + np.asarray(slots)[None, :]).reshape(-1)
    return {f: v[rows] for f, v in state.items()}


def resting_checks(state: Dict[str, np.ndarray], k: np.ndarray, e: int,
                   d: int, num_replicas: int, window: int,
                   cycle_rounds: int, add_learner_round: int) -> List[Check]:
    """``fault_checks.group_checks`` (one leader, never two in a term,
    replicas agreed, committed prefixes equal, nobody half a ring
    behind) over the groups that are in no move, on their three live
    slots: all but e where no learner has been offered yet, all but d
    where the cycle is done. (A group in the middle of its move is
    held to its class and to the reference.)"""
    r = num_replicas
    out = None
    for groups, empty in ((np.flatnonzero(k < add_learner_round), e),
                          (np.flatnonzero(k >= cycle_rounds), d)):
        live = [s for s in range(r) if s != empty]
        got = group_checks(rows_of(state, groups, live, r), len(groups),
                           r - 1, window)
        out = got if out is None else [
            Check(a.name, a.value + b.value, 0) for a, b in zip(out, got)]
    return out


def membership_checks(state: Dict[str, np.ndarray], k: np.ndarray, e: int,
                      d: int, num_replicas: int, cycle_rounds: int,
                      add_learner_round: int, swap_round: int) -> List[Check]:
    """Every finished move left the three nodes that are not d as
    voters, no learner, nothing outgoing; a group no learner has been
    offered to holds the three that are not e; and no replica is in a
    joint configuration outside rounds ``swap_round`` to the last of
    its own group's cycle."""
    r = num_replicas
    g_n = len(k)
    by = lambda f: state[f].reshape((g_n, r) + state[f].shape[1:])  # noqa: E731
    voter, joint = by("voter"), by("in_joint")
    other = (by("voter_out").any(axis=2) | by("learner").any(axis=2)
             | by("learner_next").any(axis=2) | joint)
    slots = np.arange(r)
    done, before = k >= cycle_rounds, k < add_learner_round
    bad_done = ((voter != (slots != d)[None, None, :]).any(axis=2)
                | other) & (slots != d)[None, :]
    bad_before = ((voter != (slots != e)[None, None, :]).any(axis=2)
                  | other) & (slots != e)[None, :]
    outside = joint.any(axis=1) & ((k < swap_round) | done)
    return [
        Check("replicas_of_a_finished_move_whose_voters_are_not_the_three_"
              "that_stay", int(bad_done[done].sum()), 0),
        Check("replicas_of_a_group_not_started_whose_voters_are_not_the_"
              "three_seated", int(bad_before[before].sum()), 0),
        Check("groups_in_a_joint_configuration_outside_their_own_cycles_"
              "swap_to_end", int(outside.sum()), 0),
    ]


def fresh_slot_checks(state: Dict[str, np.ndarray], groups: np.ndarray,
                      slot: int, num_replicas: int, election_timeout: int,
                      ticks: np.ndarray, reads: bool,
                      what: str) -> List[Check]:
    """Slot `slot` of each of `groups` is a fresh replica on every
    field: ``replace_checks.FRESH``, the timeout a new process draws
    first (the hash at reset count 0, of the instance's own id) and the
    two lanes a round moves on any replica: the ticks it has counted
    (``ticks``, a group; it has no timer to fire) and the read it is
    asked for like everyone (``reads``)."""
    rows = np.asarray(groups, np.int64) * num_replicas + slot
    want = dict(FRESH)
    want["election_elapsed"] = np.asarray(ticks)
    want["read_req_latch"] = reads
    want["randomized_timeout"] = election_timeout + (
        ((rows + 1) * 7919) % election_timeout)
    differ = np.zeros(len(rows), bool)
    for f, v in state.items():
        if f == "history":  # the scan's, not the replica's: it goes on
            continue
        got, exp = v[rows], want.get(f, 0)
        if np.ndim(exp):
            exp = np.asarray(exp).reshape((-1,) + (1,) * (got.ndim - 1))
        differ |= (got != exp).reshape(len(rows), -1).any(axis=1)
    return [Check(what, int(differ.sum()), 0)]


def move_checks(k: np.ndarray, snaps: np.ndarray, applied: np.ndarray,
                watch: Dict[str, int], swaps_due: int, resets_due: int,
                num_replicas: int, add_learner_round: int,
                slack: int) -> List[Check]:
    """``snaps`` are the snapshots each group's replicas have sent and
    ``applied`` the configuration changes each instance has applied,
    since the engine was built (the telemetry plane's ``sent_snapshot``
    and ``conf_changes_applied``). A move takes one snapshot: none
    before the learner is on offer, one once it has been for its own
    round and ``slack`` more, never two. A group that has not started
    applies no change.
    ``swaps_due`` and ``resets_due`` are the moves whose swap (with
    ``slack`` rounds after the offer's own to be taken in) and whose
    wipe fell in the run."""
    by_group = applied.reshape(len(k), num_replicas).sum(axis=1)
    carried = k > add_learner_round + slack
    return [
        Check("moves_that_took_other_than_one_snapshot",
              int((snaps[carried] != 1).sum())
              + int((snaps[~carried] > (k[~carried] >= add_learner_round)
                     ).sum()), 0),
        Check("changes_applied_by_a_group_that_has_not_started",
              int((by_group[k < 0] != 0).sum()), 0),
        Check("swaps_taken_other_than_one_a_move_whose_swap_fell_in_the_run",
              abs(int(watch["swaps_taken"]) - swaps_due), 0),
        Check("replicas_reset_other_than_one_a_move_whose_wipe_fell_in_the_"
              "run", abs(int(watch["replicas_reset"]) - resets_due), 0),
        Check("new_replicas_no_snapshot_gave_a_configuration",
              max(swaps_due - int(watch["conf_restores"]), 0), 0),
    ]


def run_checks(invariants: np.ndarray, counters: Dict[str, int],
               watch: Dict[str, int]) -> List[Check]:
    """Over every instance and every round of the run: the invariant
    bitmap is zero and so are the counts only the scan can see."""
    return [
        Check("instances_with_an_invariant_bit_set",
              int((invariants != 0).sum()), 0),
        Check("reads_confirmed_below_an_earlier_commit_of_the_group",
              int(watch["reads_below_commit"]), 0),
        Check("commits_in_a_joint_configuration_in_a_stalled_round",
              int(watch["joint_commits_in_stall"]), 0),
        Check("configuration_marks_overwritten_unapplied",
              int(watch["conf_marks_lost"]), 0),
        Check("votes_or_campaigns_by_a_slot_outside_its_configuration",
              int(watch["outsider_votes_or_campaigns"]), 0),
        Check("swaps_taken_before_the_new_replica_was_level_in_replicate",
              int(watch["swaps_before_ready"]), 0),
        Check("run_without_a_round_in_a_joint_configuration",
              0 if watch["joint_instance_rounds"] > 0 else 1, 0),
        Check("run_without_a_transfer_won",
              0 if counters["sent_timeout_now"] > 0
              and counters["elections_won"] > 0 else 1, 0),
    ]


def window_checks(commit_open: np.ndarray, commit_close: np.ndarray,
                  reads_open: np.ndarray, reads_close: np.ndarray,
                  cycles: int) -> List[Check]:
    """Every group, moved or not, committed and confirmed reads in the
    window, which held ``cycles`` whole cycles."""
    return [
        Check("groups_that_committed_nothing_in_the_window",
              int((commit_close <= commit_open).sum()), 0),
        Check("groups_that_confirmed_no_read_in_the_window",
              int((reads_close <= reads_open).sum()), 0),
        Check("window_of_no_whole_cycle", 0 if cycles > 0 else 1, 0),
    ]
