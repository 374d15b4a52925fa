"""The comparisons that decide ``correct``.

Every comparison here is exact, so every limit is 0: a count of answers
that differ from the plain reference. The functions take plain data (what
the driver read back from the system, and what the reference says it
should be) so that a test can hand them a fault and see ``correct``
come out false.

* served cells — the reference is the host's own record of the run: the
  dict of every put a client saw acknowledged (``acked``) and of every
  put ever offered (``proposed``). The guarantees of the configuration
  become counts: acknowledged puts missing on a member, groups whose
  replicas hash differently, values nobody proposed, members whose WAL
  shows no fsync, linearizable reads that returned anything but the
  acknowledged value, sampled puts missing after a restart.
* engine cells — the reference is ``reference.shadow.ShadowCluster``
  (plain ``RawNode``s under the round's network rules) stepped through
  the same schedule.
"""

from __future__ import annotations

import hashlib
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def verdict(checks: Sequence[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)


def kv_hash(items: Dict[bytes, bytes]) -> bytes:
    h = hashlib.sha256()
    for k in sorted(items):
        v = items[k]
        h.update(len(k).to_bytes(4, "big") + k
                 + len(v).to_bytes(4, "big") + v)
    return h.digest()


Key = Tuple[int, bytes]


def served_checks(
    acked: Dict[Key, bytes],
    proposed: Dict[Key, bytes],
    member_kvs: Sequence[Sequence[Dict[bytes, bytes]]],
    wal_fsyncs: Sequence[int],
    window_fsyncs: Optional[Sequence[int]],
    lreads: Optional[Sequence[Tuple[int, bytes, Optional[bytes]]]],
    restart_kvs: Optional[Sequence[Sequence[Dict[bytes, bytes]]]],
    restart_sample: Sequence[Key],
    lreads_unserved: int = 0,
) -> List[Check]:
    """``member_kvs[m][g]`` is member m's applied map of group g, read
    once the members have converged; ``restart_kvs`` the same after
    ``stop()`` and a re-open. ``lreads`` are (group, key, value
    returned) of linearizable reads, every one of a key whose put was
    acknowledged before the read began; ``lreads_unserved`` counts reads
    of the check's own sample that no leader served in time. ``window_fsyncs`` is each
    member's count of fsyncs inside the window, or ``None`` for a
    window that put nothing, and ``lreads`` is ``None`` for a cell that
    reads nothing linearizably."""
    missing = 0
    for (g, k), v in acked.items():
        for kvs in member_kvs:
            if kvs[g].get(k) != v:
                missing += 1
                break
    groups = len(member_kvs[0])
    hash_bad = 0
    for g in range(groups):
        first = kv_hash(member_kvs[0][g])
        if any(kv_hash(kvs[g]) != first for kvs in member_kvs[1:]):
            hash_bad += 1
    unknown = 0
    for kvs in member_kvs:
        for g, items in enumerate(kvs):
            for k, v in items.items():
                if proposed.get((g, k)) != v:
                    unknown += 1
    checks = [
        Check("acked_puts_not_on_every_member", missing, 0),
        Check("groups_with_replica_hash_mismatch", hash_bad, 0),
        Check("applied_values_never_proposed", unknown, 0),
        Check("members_without_wal_fsync",
              sum(1 for n in wal_fsyncs if n <= 0), 0),
    ]
    if lreads is not None:  # a cell whose traffic reads linearizably
        stale = sum(1 for g, k, got in lreads if got != acked.get((g, k)))
        checks += [
            Check("linearizable_reads_stale_or_wrong", stale, 0),
            Check("linearizable_reads_not_served", lreads_unserved, 0),
            Check("linearizable_reads_none_checked",
                  0 if len(lreads) else 1, 0),
        ]
    if window_fsyncs is not None:  # a window that put something
        checks.append(Check("members_without_fsync_in_window",
                            sum(1 for n in window_fsyncs if n <= 0), 0))
    if restart_kvs is not None:
        lost = 0
        for g, k in restart_sample:
            if any(kvs[g].get(k) != acked[(g, k)] for kvs in restart_kvs):
                lost += 1
        checks.append(Check("restart_sample_not_served", lost, 0))
        checks.append(Check("restart_sample_empty",
                            0 if len(restart_sample) else 1, 0))
    return checks


STATE_FIELDS = ("term", "role", "lead", "commit", "last")


def engine_checks(
    state: Dict[str, np.ndarray],
    num_groups: int,
    num_replicas: int,
    window: int,
    leader_slots: np.ndarray,
    sample_groups: Sequence[int],
    shadow_state: Callable[[int], List[Tuple[int, ...]]],
    shadow_log: Callable[[int, int], List[Tuple[int, int]]],
    skip_fields: Sequence[str] = ("randomized_timeout",),
) -> List[Check]:
    """``state[field]`` is the engine's ``[G*R, ...]`` array after the
    run. ``shadow_state(g)`` gives the reference's per-replica
    (term, role, lead, commit, last) of sampled group g, and
    ``shadow_log(g, slot)`` its (index, term) log above the snapshot."""
    g_n, r = num_groups, num_replicas
    commit = state["commit"].reshape(g_n, r)
    uncommitted = int((commit.min(axis=1) <= 0).sum())

    # Groups that drew the same leader slot ran the same schedule, so
    # their rows are equal in every field (all but the timeout lane,
    # which is seeded by the instance id and fires in no run here).
    differ = np.zeros(g_n, bool)
    for slot in np.unique(leader_slots):
        members = np.nonzero(leader_slots == slot)[0]
        for f, arr in state.items():
            if f in skip_fields:
                continue
            rows = arr.reshape((g_n, r) + arr.shape[1:])[members]
            bad = (rows != rows[0]).reshape(len(members), -1).any(axis=1)
            differ[members[bad]] = True
    state_bad = log_bad = 0
    for g in sample_groups:
        want = shadow_state(g)
        for s in range(r):
            i = g * r + s
            got = tuple(int(state[f][i]) for f in STATE_FIELDS)
            if got != tuple(want[s]):
                state_bad += 1
            lo, hi = int(state["snap_index"][i]), int(state["last"][i])
            ring = state["log_term"][i]
            dev_log = [(j, int(ring[j % window]))
                       for j in range(lo + 1, hi + 1)]
            if dev_log != shadow_log(g, s):
                log_bad += 1
    return [
        Check("groups_that_committed_nothing", uncommitted, 0),
        Check("groups_unequal_within_leader_class", int(differ.sum()), 0),
        Check("sampled_replicas_state_differs_from_reference",
              state_bad, 0),
        Check("sampled_replicas_log_differs_from_reference", log_bad, 0),
        Check("sampled_groups_none", 0 if len(sample_groups) else 1, 0),
    ]
