"""The replacement cell's comparisons beside ``compare.py``'s,
``fault_checks.py``'s and ``reconf_checks.py``'s (imported, not edited):
what a deployment that replaces its nodes one at a time under writes
and ReadIndex reads has to hold over *all* its groups, from the state
read back once at a period's end, from the telemetry plane's
per-instance totals as the window opened and closed, and from what the
scan counted in its carry. Exact, every limit 0. Plain arrays in, so a
test can hand each function a fault.

``state[field]`` is the engine's ``[G*R, ...]`` array (``BatchedState``
fields, ``learner_next`` of its ``ConfLanes``, the other lanes as
``conf_<name>``); instance ``g*R + s`` is replica slot s of group g. At
a period's end slot ``d``, the node retired in it, is the empty spare
and the other R - 1 slots are the group.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from .compare import Check

# What a replica that holds nothing reads, field by field: a fresh
# RawNode over empty storage (stated here and not taken from the
# program, which is what is compared with it). Every field not named
# is zero or false.
FRESH = {"read_index": -1, "votes": -1, "next": 1}


def live_view(state: Dict[str, np.ndarray], num_groups: int,
              num_replicas: int, d: int) -> Dict[str, np.ndarray]:
    """The rows of the R - 1 slots that are not ``d``, group by group:
    what ``fault_checks.group_checks`` is handed as a deployment of
    R - 1 replicas (it reads fields with one value an instance, and
    the ring)."""
    r = num_replicas
    live = np.asarray([s for s in range(r) if s != d])
    rows = (np.arange(num_groups)[:, None] * r + live[None, :]).reshape(-1)
    return {f: v[rows] for f, v in state.items()}


def membership_checks(state: Dict[str, np.ndarray], num_groups: int,
                      num_replicas: int, d: int) -> List[Check]:
    """At a period's end every replica that is not on node ``d`` holds
    the configuration the replacement leaves: the voters exactly the
    R - 1 nodes that are not ``d``, no learner, nothing outgoing, no
    joint configuration."""
    r = num_replicas
    live = np.arange(r) != d
    on_live = np.tile(live, num_groups)
    wrong_voters = (state["voter"] != live[None, :]).any(axis=1)
    not_home = (state["voter_out"].any(axis=1) | state["learner"].any(axis=1)
                | state["learner_next"].any(axis=1) | state["in_joint"])
    return [
        Check("replicas_whose_voters_are_not_the_live_nodes",
              int((wrong_voters & on_live).sum()), 0),
        Check("replicas_with_a_learner_or_a_joint_configuration_at_the_"
              "periods_end", int((not_home & on_live).sum()), 0),
    ]


def empty_slot_checks(state: Dict[str, np.ndarray], num_groups: int,
                      num_replicas: int, d: int, election_timeout: int,
                      ticks: int, reads: bool) -> List[Check]:
    """Slot ``d``'s rows are those of a fresh replica on every field:
    ``FRESH``, the timeout a new process draws first (the hash at reset
    count 0), and the two lanes a round moves on any replica: the ticks
    it has counted since the reset (``ticks``; it has no timer to fire)
    and the read it is asked for like everyone (``reads``)."""
    r = num_replicas
    rows = np.arange(num_groups, dtype=np.int64) * r + d
    want = dict(FRESH)
    want["election_elapsed"] = ticks
    want["read_req_latch"] = reads
    want["randomized_timeout"] = election_timeout + (
        ((rows + 1) * 7919) % election_timeout)
    differ = np.zeros(num_groups, bool)
    for f, v in state.items():
        if f == "history":  # the scan's, not the replica's: it goes on
            continue
        got, exp = v[rows], want.get(f, 0)
        if np.ndim(exp):
            exp = np.asarray(exp).reshape((-1,) + (1,) * (got.ndim - 1))
        differ |= (got != exp).reshape(num_groups, -1).any(axis=1)
    return [Check("wiped_slots_that_are_not_a_fresh_replica",
                  int(differ.sum()), 0)]


def window_checks(commit_open: np.ndarray, commit_close: np.ndarray,
                  reads_open: np.ndarray, reads_close: np.ndarray,
                  applied_open: np.ndarray, applied_close: np.ndarray,
                  applies: np.ndarray, snaps_open: np.ndarray,
                  snaps_close: np.ndarray, periods: int) -> List[Check]:
    """``commit_*`` are each group's highest commit, ``reads_*`` each
    group's ReadIndex batches confirmed, ``snaps_*`` the snapshots each
    group's replicas sent and ``applied_*`` each instance's
    configuration changes applied (the telemetry plane's
    ``reads_confirmed``, ``sent_snapshot`` and
    ``conf_changes_applied``), as the window opened and closed;
    ``applies`` is what each node's replicas had to apply in it (the
    generator's ``applies``: by a replica's place in the cycle), and
    the window holds ``periods`` whole periods, so as many new replicas
    a group."""
    moved = (applied_close - applied_open).reshape(-1, len(applies))
    return [
        Check("groups_that_committed_nothing_in_the_window",
              int((commit_close <= commit_open).sum()), 0),
        Check("groups_that_confirmed_no_read_in_the_window",
              int((reads_close <= reads_open).sum()), 0),
        Check("replicas_that_did_not_apply_the_changes_their_place_gives",
              int((moved != applies[None, :]).sum()), 0),
        Check("groups_whose_new_replica_was_sent_more_than_two_snapshots",
              int((snaps_close - snaps_open > 2 * periods).sum()), 0),
        Check("window_of_no_whole_period", 0 if periods > 0 else 1, 0),
    ]


def run_checks(invariants: np.ndarray, counters: Dict[str, int],
               watch: Dict[str, int], num_groups: int,
               periods: int) -> List[Check]:
    """Over every instance and every round of the run (``periods``
    whole periods of it): the telemetry plane's invariant bitmap OR-ed
    over all rounds, its totals since the engine was built, and the
    counts the scans kept in their carry."""
    swaps = num_groups * periods
    return [
        Check("instances_with_an_invariant_bit_set",
              int((invariants != 0).sum()), 0),
        Check("reads_confirmed_below_an_earlier_commit_of_the_group",
              int(watch["reads_below_commit"]), 0),
        Check("commits_in_a_joint_configuration_through_the_cut",
              int(watch["joint_commits_in_stall"]), 0),
        Check("configuration_marks_overwritten_unapplied",
              int(watch["conf_marks_lost"]), 0),
        Check("votes_or_campaigns_by_a_slot_outside_its_configuration",
              int(watch["outsider_votes_or_campaigns"]), 0),
        Check("swaps_taken_before_the_new_replica_was_level_in_replicate",
              int(watch["swaps_before_ready"]), 0),
        Check("swaps_taken_other_than_one_a_group_a_period",
              abs(int(watch["swaps_taken"]) - swaps), 0),
        Check("replicas_reset_other_than_one_a_group_a_period",
              abs(int(watch["replicas_reset"]) - swaps), 0),
        Check("new_replicas_no_snapshot_gave_a_configuration",
              max(swaps - int(watch["conf_restores"]), 0), 0),
        Check("run_without_a_round_in_a_joint_configuration",
              0 if watch["joint_instance_rounds"] > 0 else 1, 0),
        Check("run_without_a_transfer_won",
              0 if counters["sent_timeout_now"] > 0
              and counters["elections_won"] > 0 else 1, 0),
    ]
