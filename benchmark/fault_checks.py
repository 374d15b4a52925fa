"""The fault cells' comparisons beside ``compare.py``'s (imported here,
not edited): what a deployment under elections has to hold over *all*
its groups, from the state read back once after the run and from the
telemetry plane's totals. Exact, every limit 0. Plain arrays in, so a
test can hand each function a fault.

``state[field]`` is the engine's ``[G*R, ...]`` array (``BatchedState``
fields); instance ``g*R + s`` is replica slot s of group g.
"""

from __future__ import annotations

from itertools import combinations
from typing import Dict, List, Sequence

import numpy as np

from .compare import Check

LEADER = 2  # BatchedState.role, as raft's StateType
REPLICATE = 1  # BatchedState.pr_state, as tracker's StateType


def group_checks(state: Dict[str, np.ndarray], num_groups: int,
                 num_replicas: int, window: int) -> List[Check]:
    """The guarantees every group holds in its final state: one leader
    (never two in a term), replicas agreed on term and leader, committed
    prefixes equal wherever two rings hold the index, no replica's
    commit more than ``window/2`` behind its leader's."""
    g_n, r = num_groups, num_replicas
    by_group = lambda f: state[f].reshape((g_n, r) + state[f].shape[1:])  # noqa: E731
    role, term, lead = by_group("role"), by_group("term"), by_group("lead")
    commit, snap = by_group("commit"), by_group("snap_index")
    ring = by_group("log_term")
    leads = role == LEADER
    not_one = int((leads.sum(axis=1) != 1).sum())
    two_in_term = 0
    for a, b in combinations(range(r), 2):
        two_in_term += int((leads[:, a] & leads[:, b]
                            & (term[:, a] == term[:, b])).sum())
    disagree = int(((term != term[:, :1]).any(axis=1)
                    | (lead != lead[:, :1]).any(axis=1)).sum())
    # Log matching over the committed prefix both rings still hold.
    j = np.arange(window)
    rows = np.arange(g_n)[:, None]
    prefix_bad = np.zeros(g_n, bool)
    for a, b in combinations(range(r), 2):
        lo = np.maximum(snap[:, a], snap[:, b])
        hi = np.minimum(commit[:, a], commit[:, b])
        idx = lo[:, None] + 1 + j[None, :]
        held = idx <= hi[:, None]
        ta = ring[:, a][rows, idx % window]
        tb = ring[:, b][rows, idx % window]
        prefix_bad |= (held & (ta != tb)).any(axis=1)
    lead_commit = np.where(leads, commit, 0).max(axis=1)
    lagging = int((leads.any(axis=1)[:, None]
                   & (commit < lead_commit[:, None] - window // 2)).sum())
    return [
        Check("groups_without_exactly_one_leader", not_one, 0),
        Check("groups_with_two_leaders_in_a_term", two_in_term, 0),
        Check("groups_disagreeing_on_term_or_leader", disagree, 0),
        Check("groups_whose_committed_prefixes_differ",
              int(prefix_bad.sum()), 0),
        Check("replicas_lagging_their_leader_past_half_the_ring",
              lagging, 0),
    ]


def quiet_checks(state: Dict[str, np.ndarray], num_groups: int,
                 num_replicas: int) -> List[Check]:
    """``state`` after rounds with every node up and nothing offered:
    a follower that was carried by snapshots under load has to have
    caught up by appends. Counts the replicas that are not level with
    their leader (``commit`` and ``last``) or that their leader does
    not hold in REPLICATE; all of a group that has no one leader."""
    g_n, r = num_groups, num_replicas
    role = state["role"].reshape(g_n, r)
    commit = state["commit"].reshape(g_n, r)
    last = state["last"].reshape(g_n, r)
    leads = role == LEADER
    led = leads.sum(axis=1) == 1
    at = leads.argmax(axis=1)
    rows = np.arange(g_n)
    progress = state["pr_state"].reshape(g_n, r, r)[rows, at]
    behind = ((commit != commit[rows, at][:, None])
              | (last != last[rows, at][:, None])
              | ((progress != REPLICATE) & ~leads))
    return [Check("replicas_not_caught_up_once_load_stops",
                  int((behind | ~led[:, None]).sum()), 0)]


def window_checks(commit_open: np.ndarray, commit_close: np.ndarray,
                  invariants: np.ndarray, counters_open: Dict[str, int],
                  counters_close: Dict[str, int],
                  need: Sequence[str] = ("elections_started",
                                         "elections_won", "sent_snapshot"),
                  ) -> List[Check]:
    """``commit_*`` are each group's highest commit as the window opened
    and closed, ``invariants`` the telemetry plane's bitmap of every
    instance OR-ed over every round since the engine was built,
    ``counters_*`` its totals by name at the same two points. A window
    in which no election was started or won, or no snapshot sent,
    measured something else."""
    checks = [
        Check("groups_that_committed_nothing_in_the_window",
              int((commit_close <= commit_open).sum()), 0),
        Check("instances_with_an_invariant_bit_set",
              int((invariants != 0).sum()), 0),
    ]
    for name in need:
        moved = counters_close[name] - counters_open[name]
        checks.append(Check(f"window_without_{name}",
                            0 if moved > 0 else 1, 0))
    return checks


def schedule_classes(leader_slots: np.ndarray, num_replicas: int,
                     election_timeout: int) -> np.ndarray:
    """A class id for each group: groups of one class ran the same
    schedule, so their rows are equal field for field. What a group's
    run depends on, the node schedule apart (which is every group's):
    the replica the seed made its first leader, and its replicas'
    randomized timeouts, ``et + ((iid+1)*7919 + resets*104729) % et``
    with iid = g*R + s, which the residues of ``(iid+1)*7919`` modulo
    ``et`` fix for every reset count."""
    g = np.arange(len(leader_slots), dtype=np.int64)
    key = [leader_slots.astype(np.int64)] + [
        ((g * num_replicas + s + 1) * 7919) % election_timeout
        for s in range(num_replicas)]
    inverse = np.unique(np.stack(key, axis=1), axis=0,
                        return_inverse=True)[1]
    return inverse.reshape(-1).astype(np.int64)
