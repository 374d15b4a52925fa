"""The load cell's comparisons beside ``compare.py``'s,
``fault_checks.py``'s and ``reconf_checks.py``'s (imported, not
edited): what a deployment in which no two groups are offered the same
thing has to hold over *all* its groups. There are no classes here (no
group equals another), so full coverage is the invariants over every
group and a conservation law over every group, in exact integers, with
the plain reference's replay of the draws as the other side; the groups
the reference follows replica by replica are drawn by popularity. Exact,
every limit 0. Plain arrays in, so a test can hand each function a
fault.

``state[field]`` is the engine's ``[G*R, ...]`` array; instance
``g*R + s`` is replica slot s of group g.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from .compare import Check, engine_checks

LEADER = 2


def conservation_checks(state: Dict[str, np.ndarray], num_groups: int,
                        num_replicas: int, last_before: np.ndarray,
                        offered_ref: np.ndarray, dropped: np.ndarray,
                        won: np.ndarray, leader_slots: np.ndarray
                        ) -> List[Check]:
    """After a closing call with nothing offered. ``last_before`` is
    each group's last index when the load began, ``offered_ref`` the
    updates the reference's replay of the draws offered it since,
    ``dropped`` and ``won`` the telemetry plane's ``proposals_dropped``
    and ``elections_won`` since, summed over the group's rows. Every
    replica is offered what its group is, so the rows of a group refuse
    R x offered less what its leader appended: a group's log grew by
    ``R x offered - dropped`` (and by the empty entry of each election
    won), every replica holds all of it, and all of it is committed:
    each update offered is in every replica's committed log exactly
    once, or was counted as dropped by the leader that refused it."""
    g_n, r = num_groups, num_replicas
    last = state["last"].reshape(g_n, r)
    commit = state["commit"].reshape(g_n, r)
    grew = last.max(axis=1).astype(np.int64) - last_before
    owed = r * offered_ref.astype(np.int64) - dropped + won
    leads = state["role"].reshape(g_n, r) == LEADER
    quiet = offered_ref == 0
    moved = quiet & ((grew != 0) | ~leads[np.arange(g_n), leader_slots])
    return [
        Check("replicas_short_of_their_groups_log_once_load_stops",
              int((last != last.max(axis=1)[:, None]).sum()), 0),
        Check("replicas_with_entries_uncommitted_once_load_stops",
              int((commit != last).sum()), 0),
        Check("groups_whose_log_grew_by_more_than_was_offered_and_taken",
              int((grew > owed).sum()), 0),
        Check("groups_whose_log_grew_by_less_than_was_offered_and_taken",
              int((grew < owed).sum()), 0),
        Check("groups_offered_nothing_that_appended_or_lost_their_leader",
              int(moved.sum()), 0),
    ]


def count_checks(counts: Dict[str, int], replayed: Dict[str, int]
                 ) -> List[Check]:
    """What the program counted in its scans' carry against the
    reference's replay of the same rounds: updates offered, group-rounds
    with a read asked, group-rounds with either."""
    return [Check(f"load_count_{name}_differs_from_the_replay",
                  abs(int(counts[name]) - int(replayed[name])), 0)
            for name in ("offered", "reads_asked", "active")]


def run_checks(invariants: np.ndarray, moved: Dict[str, int],
               watch: Dict[str, int]) -> List[Check]:
    """Over every instance and every round of the run: the invariant
    bitmap, the telemetry plane's totals since the load began
    (``moved``) and the scan's own counts. Nobody is cut off and nobody
    is asked to hand over: a campaign or a snapshot here is a finding."""
    return [
        Check("instances_with_an_invariant_bit_set",
              int((invariants != 0).sum()), 0),
        Check("snapshots_sent_in_the_run", int(moved["sent_snapshot"]), 0),
        Check("elections_started_in_the_run",
              int(moved["elections_started"]), 0),
        Check("reads_confirmed_below_an_earlier_commit_of_the_group",
              int(watch["reads_below_commit"]), 0),
        Check("run_in_a_joint_configuration",
              int(watch["joint_instance_rounds"]), 0),
    ]


def window_checks(offered: int, reads_asked: int, committed: int,
                  reads_confirmed: int) -> List[Check]:
    """A window in which nothing was offered, asked, committed or
    confirmed measured something else."""
    return [Check(f"window_without_{name}", 0 if n > 0 else 1, 0)
            for name, n in (("updates_offered", offered),
                            ("reads_asked", reads_asked),
                            ("entries_committed", committed),
                            ("reads_confirmed", reads_confirmed))]


def sampled_engine_checks(
        state: Dict[str, np.ndarray], num_replicas: int, window: int,
        sample: Sequence[int],
        ref_state: Callable[[int], List[Tuple[int, ...]]],
        ref_log: Callable[[int, int], List[Tuple[int, int]]]
) -> List[Check]:
    """``compare.engine_checks``' comparison of the sampled groups with
    the reference in state and log, on the sampled groups' rows alone
    (its class equality has nothing to say here: every group is a class
    of its own, and is left out)."""
    r = num_replicas
    rows = (np.asarray(sample, np.int64)[:, None] * r
            + np.arange(r)).reshape(-1)
    sub = {f: state[f][rows] for f in
           ("term", "role", "lead", "commit", "last", "snap_index",
            "log_term")}
    local = list(range(len(sample)))
    checks = engine_checks(
        sub, len(sample), r, window, np.arange(len(sample)), local,
        lambda i: ref_state(sample[i]), lambda i, s: ref_log(sample[i], s),
        skip_fields=())
    return [c for c in checks
            if c.name != "groups_unequal_within_leader_class"]
