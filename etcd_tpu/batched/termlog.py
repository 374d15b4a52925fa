"""The replica's log behind one interface: what the round asks of it
(the term of an entry, the reject hint, an append of its own term's
entries, an append's entries written over a conflict), answered from
the ring of terms (``kernels.term_at`` and its siblings, ``[W]``) or,
with ``BatchedConfig.log_runs`` = K, from K term runs (``[2, K]``).

Term runs. The device holds only the terms of entries, and a log's
terms never decrease with the index, so a log is its runs: row 0 of the
table the index a term's entries start at, row 1 the term, a slot with
term 0 empty. Entry i has the largest term among the runs that start at
or below i; the reject hint is one below the first run above the term
asked; an append starts a run only where the term changes; a conflict
drops the runs that start at or past it. Each is an elementwise pass
and a reduce over K, the gather-free shape the ring kernels have over
W, whatever the window: at etcd's depth (5,000 catch-up entries, a
window of 10,240) a ring would be 12.6 GB at 307,200 replicas and every
``term_at`` a pass over all of it.

A run of term t lives in slot ``t mod K``: a term names its run, so a
write needs no count of the runs and no order among the slots. A run
entirely at or below the floor stays where it is until its slot is
wanted (it answers nothing wrongly: a later run covers every index
above the floor). Where a new term's slot holds a run the window still
needs, the table is full for that term: the floor moves up to that
run's last entry (``snap_index``, ``snap_term``; a shorter tail, always
legal: a follower below it gets a snapshot where appends would have
done), and where that passes ``applied`` the end-of-round state says so
(``snap_index > applied``: telemetry.INV_NAMES, runs_passed_applied). A
leader's window therefore never holds two runs in one slot, and neither
does any append cut from it.

All functions are per instance (scalars and ``[K]`` / ``[E]`` vectors)
and run under the round's vmap. The run table's own ops stand under the
``named_scope`` ``raft_log`` (step.DEVICE_SCOPES), so a trace says what
the deep log costs inside each phase; the ring's stand where they did.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernels import (MAX_I32, find_conflict_by_term, ring_write,
                      ring_write_masked, term_at as ring_term_at)

I32 = jnp.int32

def runs_term_at(log, snap_index, snap_term, last, i):
    """``kernels.term_at`` over the run table: `i` a scalar or any batch
    of indexes."""
    with jax.named_scope("raft_log"):
        # (An empty slot starts nowhere: one compare a run and index.)
        start = jnp.where(log[1] > 0, log[0], MAX_I32)
        i = jnp.asarray(i)
        val = jnp.max(jnp.where(start <= i[..., None], log[1], 0), axis=-1)
        in_log = (i > snap_index) & (i <= last)
        return jnp.where(i == snap_index, snap_term,
                         jnp.where(in_log, val, 0))


def runs_find_conflict(log, snap_index, snap_term, last, index, term):
    """``kernels.find_conflict_by_term`` over the run table: the entries
    of a term at most `term` are a prefix of the log, which ends one
    below the first run above it."""
    with jax.named_scope("raft_log"):
        start, t = log[0], log[1]
        above = jnp.min(jnp.where(t > term, start, MAX_I32))
        ans = jnp.minimum(jnp.minimum(index, last), above - 1)
        floor = jnp.where(snap_term <= term, snap_index, snap_index - 1)
        return jnp.where(ans > snap_index, ans, floor)


def _runs_insert(start, term, snap_index, snap_term, last, c_start, c_term,
                 c_mask):
    """The table with the candidate runs ``(c_start[j], c_term[j])`` of
    the masked j put in (each into its term's slot; one the slot holds
    already stays as it is), and the floor: moved up past the newest
    run that was written over while the log up to `last` still reached
    into it."""
    k = term.shape[-1]
    slot = jnp.arange(k, dtype=I32)
    into = jnp.where(c_mask, jnp.mod(c_term, k), -1)  # no slot: -1
    hit = into[None, :] == slot[:, None]
    new_term = jnp.max(jnp.where(hit, c_term[None, :], 0), axis=-1)
    new_start = jnp.max(jnp.where(hit, c_start[None, :], 0), axis=-1)
    write = (new_term > 0) & (new_term != term)
    evicted = jnp.max(jnp.where(write, term, 0))
    # Where the evicted run ends: one below the next run that stays
    # (none written over is newer than it), or at the log's end.
    end = jnp.minimum(
        jnp.min(jnp.where(term > evicted, start, MAX_I32)) - 1, last)
    full = (evicted > 0) & (end > snap_index)
    return (jnp.stack([jnp.where(write, new_start, start),
                       jnp.where(write, new_term, term)]),
            jnp.where(full, end, snap_index),
            jnp.where(full, evicted, snap_term))


def _runs_append_own(log, snap_index, snap_term, last, term, n):
    with jax.named_scope("raft_log"):
        return _runs_insert(
            log[0], log[1], snap_index, snap_term, last,
            (last + 1)[None], term[None], (n > 0)[None])


def _runs_append_entries(log, snap_index, snap_term, last, prev, ent_terms,
                         write, ci, any_conflict):
    with jax.named_scope("raft_log"):
        j = jnp.arange(ent_terms.shape[-1], dtype=I32)
        at = prev + 1 + ci  # the first entry written
        drop = any_conflict & (log[0] >= at)
        start = jnp.where(drop, 0, log[0])
        term = jnp.where(drop, 0, log[1])
        before = jnp.concatenate([ent_terms[:1], ent_terms[:-1]])
        first = write & ((j == ci) | (ent_terms != before))
        return _runs_insert(
            start, term, snap_index, snap_term,
            jnp.where(any_conflict, at - 1, last), prev + 1 + j, ent_terms,
            first)


# -- the interface step.py takes the log through ---------------------------------


def term_at(cfg, st, i, log=None):
    """Term of entry `i` of `st`'s log; 0 outside [snap_index, last].
    `log` where the caller holds the log apart from the state (emit's
    read under its cond)."""
    log = st.log_term if log is None else log
    read = runs_term_at if cfg.log_runs else ring_term_at
    return read(log, st.snap_index, st.snap_term, st.last, i)


def find_conflict(cfg, st, index, term):
    """Largest idx <= index with term_at(idx) <= term (the reject hint,
    ref: raft/log.go findConflictByTerm)."""
    find = runs_find_conflict if cfg.log_runs else find_conflict_by_term
    return find(st.log_term, st.snap_index, st.snap_term, st.last, index,
                term)


def append_own(cfg, st, n, cols: int):
    """`st` with `n` entries of its own term written after ``last`` (the
    log alone: ``last`` is the caller's). The ring's write is `cols`
    term columns wide (static; n <= cols). A run table may move the
    floor (module docstring)."""
    if cfg.log_runs:
        log, snap_index, snap_term = _runs_append_own(
            st.log_term, st.snap_index, st.snap_term, st.last, st.term, n)
        return st._replace(log_term=log, snap_index=snap_index,
                           snap_term=snap_term)
    terms = jnp.full((cols,), 1, I32) * st.term
    return st._replace(
        log_term=ring_write(st.log_term, st.last + 1, terms, n))


def append_entries(cfg, st, prev, ent_terms, write, ci, any_conflict):
    """`st` with an append's entries written: ``ent_terms[j]`` at index
    ``prev + 1 + j`` for the j of `write`, which are those from the
    first conflicting offset `ci` on that hold an entry, and none
    without `any_conflict` (the log alone: ``last`` is the caller's)."""
    if cfg.log_runs:
        log, snap_index, snap_term = _runs_append_entries(
            st.log_term, st.snap_index, st.snap_term, st.last, prev,
            ent_terms, write, ci, any_conflict)
        return st._replace(log_term=log, snap_index=snap_index,
                           snap_term=snap_term)
    return st._replace(log_term=ring_write_masked(
        st.log_term, prev + 1, ent_terms, write))
