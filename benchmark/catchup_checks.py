"""The deep-log cell's comparisons beside ``fault_checks.py``'s
(imported here, not edited): what ``engine100k-r3-deeplog`` has to hold
where its log is a table of term runs and not a ring, and what a
replica that returns inside the catch-up window has to have done. Exact,
every limit 0. Plain arrays in, so a test can hand each function a
fault.

``state["log_term"]`` is the engine's ``[G*R, 2, K]`` run table
(``BatchedConfig.log_runs``): row 0 the index each run starts at, row 1
its term, a slot with term 0 empty; entry i has the largest term among
the runs that start at or below i. Nothing here imports the program:
that rule is restated in numpy.
"""

from __future__ import annotations

from itertools import combinations
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

from .compare import STATE_FIELDS, Check

LEADER = 2  # BatchedState.role, as raft's StateType
REPLICATE = 1  # BatchedState.pr_state, as tracker's StateType
_CHUNK = 8192  # groups a pass of the prefix check


def runs_term_at(runs: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """``runs`` [..., 2, K] and indexes ``idx`` [..., M]: the term the
    table holds for each index (0 where no run reaches it)."""
    start, term = runs[..., 0, None, :], runs[..., 1, None, :]
    reach = (term > 0) & (start <= idx[..., None])
    return np.where(reach, term, 0).max(axis=-1)


def log_terms(runs: np.ndarray, snap_index: int,
              last: int) -> List[Tuple[int, int]]:
    """One replica's (index, term) log above its floor, every index
    from the floor to ``last``."""
    idx = np.arange(snap_index + 1, last + 1)
    return list(zip(idx.tolist(), runs_term_at(runs, idx).tolist()))


def group_checks(state: Dict[str, np.ndarray], num_groups: int,
                 num_replicas: int, kept: int) -> List[Check]:
    """``fault_checks.group_checks`` over run tables: one leader (never
    two in a term), replicas agreed on term and leader, committed
    prefixes equal wherever two replicas hold the index, no replica's
    commit more than ``kept`` (the entries kept behind the applied
    index) behind its leader's. Two logs are step functions of the
    index, so they are equal over a range exactly if they are equal at
    its first index and wherever either changes term inside it: the
    runs' starts."""
    g_n, r = num_groups, num_replicas
    by_group = lambda f: state[f].reshape((g_n, r) + state[f].shape[1:])  # noqa: E731
    role, term, lead = by_group("role"), by_group("term"), by_group("lead")
    commit, snap = by_group("commit"), by_group("snap_index")
    runs = by_group("log_term")
    leads = role == LEADER
    not_one = int((leads.sum(axis=1) != 1).sum())
    two_in_term = 0
    for a, b in combinations(range(r), 2):
        two_in_term += int((leads[:, a] & leads[:, b]
                            & (term[:, a] == term[:, b])).sum())
    disagree = int(((term != term[:, :1]).any(axis=1)
                    | (lead != lead[:, :1]).any(axis=1)).sum())
    prefix_bad = np.zeros(g_n, bool)
    for lo_g in range(0, g_n, _CHUNK):
        rows = slice(lo_g, lo_g + _CHUNK)
        for a, b in combinations(range(r), 2):
            lo = np.maximum(snap[rows, a], snap[rows, b])
            hi = np.minimum(commit[rows, a], commit[rows, b])
            at = np.concatenate([
                lo[:, None] + 1, runs[rows, a, 0], runs[rows, b, 0]], axis=1)
            held = (at > lo[:, None]) & (at <= hi[:, None])
            differ = runs_term_at(runs[rows, a], at) != runs_term_at(
                runs[rows, b], at)
            prefix_bad[rows] |= (held & differ).any(axis=1)
    lead_commit = np.where(leads, commit, 0).max(axis=1)
    lagging = int((leads.any(axis=1)[:, None]
                   & (commit < lead_commit[:, None] - kept)).sum())
    return [
        Check("groups_without_exactly_one_leader", not_one, 0),
        Check("groups_with_two_leaders_in_a_term", two_in_term, 0),
        Check("groups_disagreeing_on_term_or_leader", disagree, 0),
        Check("groups_whose_committed_prefixes_differ",
              int(prefix_bad.sum()), 0),
        Check("replicas_lagging_their_leader_past_the_entries_kept",
              lagging, 0),
    ]


def run_checks(counters: Dict[str, int]) -> List[Check]:
    """``counters`` are the telemetry plane's totals over every
    instance and round since the engine was built: a replica that
    returns inside the catch-up window is carried by appends, so no
    snapshot was ever sent and no peer ever stood in SNAPSHOT (the
    opposite of ``fault_checks.window_checks``' "snapshots moved")."""
    return [Check(f"run_with_{name}", int(counters[name]), 0)
            for name in ("sent_snapshot", "to_snapshot")]


def level_checks(level: Dict[str, np.ndarray], node: int, num_groups: int,
                 num_replicas: int, max_ents: int) -> List[Check]:
    """``level`` is (role, commit, pr_state) read ``level_rounds`` after
    node ``node`` healed: each of its replicas has to stand within
    ``max_ents`` of its group's commit and, where it does not lead, in
    REPLICATE on its leader's row. A group with no one leader counts."""
    g_n, r = num_groups, num_replicas
    role = level["role"].reshape(g_n, r)
    commit = level["commit"].reshape(g_n, r)
    leads = role == LEADER
    at = leads.argmax(axis=1)
    rows = np.arange(g_n)
    progress = level["pr_state"].reshape(g_n, r, r)[rows, at, node]
    bad = ((leads.sum(axis=1) != 1)
           | (commit.max(axis=1) - commit[:, node] > max_ents)
           | ((progress != REPLICATE) & (at != node)))
    return [Check("returned_replicas_not_level_in_replicate",
                  int(bad.sum()), 0),
            Check("returned_replicas_none_checked",
                  0 if g_n else 1, 0)]


def engine_checks(
    state: Dict[str, np.ndarray],
    num_groups: int,
    num_replicas: int,
    classes: np.ndarray,
    sample_groups: Sequence[int],
    shadow_state: Callable[[int], List[Tuple[int, ...]]],
    shadow_log: Callable[[int, int], List[Tuple[int, int]]],
) -> List[Check]:
    """``compare.engine_checks`` over run tables (the names of its
    checks, so the controls' scripts read them): every group committed;
    groups of one class equal row for row in every field, the run table
    among them; the sampled groups equal the plain reference in state
    and in the term of every index from the floor to ``last``."""
    g_n, r = num_groups, num_replicas
    commit = state["commit"].reshape(g_n, r)
    uncommitted = int((commit.min(axis=1) <= 0).sum())
    differ = np.zeros(g_n, bool)
    for c in np.unique(classes):
        members = np.nonzero(classes == c)[0]
        for arr in state.values():
            rows = arr.reshape((g_n, r) + arr.shape[1:])[members]
            bad = (rows != rows[0]).reshape(len(members), -1).any(axis=1)
            differ[members[bad]] = True
    state_bad = log_bad = 0
    for g in sample_groups:
        want = shadow_state(g)
        for s in range(r):
            i = g * r + s
            got = tuple(int(state[f][i]) for f in STATE_FIELDS)
            state_bad += got != tuple(want[s])
            dev_log = log_terms(state["log_term"][i],
                                int(state["snap_index"][i]),
                                int(state["last"][i]))
            log_bad += dev_log != shadow_log(g, s)
    return [
        Check("groups_that_committed_nothing", uncommitted, 0),
        Check("groups_unequal_within_leader_class", int(differ.sum()), 0),
        Check("sampled_replicas_state_differs_from_reference",
              state_bad, 0),
        Check("sampled_replicas_log_differs_from_reference", log_bad, 0),
        Check("sampled_groups_none", 0 if len(sample_groups) else 1, 0),
    ]
