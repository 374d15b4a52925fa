"""Round-span recorder: where a round's host time goes, on the clock the
device trace uses.

The tracer beside this module (``tracer.py``) follows single sampled
proposals across members. This one follows *rounds*: every host phase
of a served member round (``member.*``, ``rawnode.*``) and of an engine
call (``engine.*``) is one span, recorded where the work happens:

* name, start and end on ``time.monotonic_ns`` (the tracer's clock, so
  a proposal's ``stage`` stamp *is* its round's ``rawnode.stage``
  start), the enclosing span on the same thread (``parent``), and the
  identifier its request shares, ``(member, round)``: the member
  round's sequence number (``member.stats["rounds"]`` as the round
  starts; for the engine, member 0 and the call's number). A span
  opened under another takes its parent's ``(member, round)``.
* For the phases a metric reads it of (:meth:`Recorder.phases`,
  ``cpu=``), the thread's CPU nanoseconds over the span
  (``time.thread_time_ns`` at both ends: wall less CPU is time off the
  processor — the interpreter lock, a mutex). It is a system call, of
  6-8 us on the chip's host, so no other span pays it.
* Every span also opens a ``jax.profiler.TraceAnnotation`` of the same
  name with ``member`` and ``round`` as stats, so that whenever a
  profiler session is open the span is an event of the xplane, beside
  the device ops and on their clock (``benchmark/reduce/gaps.py`` puts
  each device idle gap down to one). With no session open that is a
  flag test inside the annotation.

Always on: there is no switch. Spans go to a bounded ring per writing
thread (fixed slots, one tuple store a span, no lock and no counter on
the writer's path): a flight recorder, in which the newest spans push
out the oldest. Rings are read in-process (:meth:`Recorder.snapshot`)
and dumped on demand like the tracer's (:meth:`Recorder.dump`). What a
reader lost — spans written since the ring was last read and pushed out
before this reading — is counted as it reads, on
``etcd_tpu_trace_span_drops_total{cls="round_ring"}``: never silent,
and not moving while nobody reads.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Collection, Dict, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

from .artifacts import KIND_ROUNDSPANS, dump_path

# What a name of this recorder starts with (the xplane holds other
# annotations too; the gap reduction keeps these).
PREFIXES = ("engine.", "member.", "rawnode.")
# Slots per writing thread. A 30 s window of a served member is about
# 500 rounds of 13 spans on its round thread and 5 a round on its drain
# thread.
RING_SLOTS = 32768
# Rings kept: each thread that ever wrote has one, and it outlives the
# thread (a stopped member's spans are still read). Past this many the
# oldest rings of dead threads go.
MAX_RINGS = 64
DROP_CLASS = "round_ring"


class SpanRec(NamedTuple):
    """One closed span as :meth:`Recorder.snapshot` returns it."""

    name: str
    seq: int  # per-thread open order; ``parent`` refers to it
    parent: int  # -1: opened under no span
    member: int
    round: int
    t0: int  # monotonic_ns
    t1: int
    cpu_ns: int  # thread CPU over the span; -1: not read
    stats: Optional[dict]
    thread: int  # the writing thread's ring (``Recorder.ring_id``)


class _Ring:
    """One thread's spans, in close order."""

    __slots__ = ("id", "thread", "slots", "head", "stack", "seq",
                 "read_to", "dropped")

    def __init__(self, slots: int, ring_id: int) -> None:
        self.id = ring_id  # never reused, unlike a thread's ident
        self.thread = threading.current_thread()
        self.slots: list = [None] * slots
        self.head = 0  # records ever written
        self.stack: List["Span"] = []  # open spans, outermost first
        self.seq = 0
        self.read_to = 0  # ``head`` as the ring was last read
        self.dropped = 0  # records no reading got (readers' side)

    def put(self, rec: tuple) -> None:
        i = self.head
        self.slots[i % len(self.slots)] = rec
        self.head = i + 1

    def read(self) -> Tuple[List[tuple], int]:
        """(records still whole in the ring, oldest first; how many
        were written since the last reading and are gone). The writer
        may be writing meanwhile: what it overwrote during the copy is
        left out, and counts as gone."""
        n = len(self.slots)
        h0 = self.head
        copy = list(self.slots)
        lo = max(0, self.head - n)
        lost = max(0, lo - self.read_to)
        self.read_to = max(lo, h0)
        self.dropped += lost
        return [copy[i % n] for i in range(lo, h0)], lost


class Span:
    """One open span; use through :meth:`Recorder.span` (a context
    manager) or :meth:`Recorder.phases`. After it closes, ``t0``,
    ``t1`` and ``cpu_ns`` are what the ring holds."""

    __slots__ = ("name", "member", "round", "stats", "t0", "t1", "cpu_ns",
                 "seq", "parent", "_ring", "_ann", "_c0")

    def __init__(self, ring: _Ring, name: str, member: int, round: int,
                 stats: Optional[dict]) -> None:
        self._ring = ring
        self.name = name
        self.member = member
        self.round = round
        self.stats = stats
        self.t0 = self.t1 = 0
        self.cpu_ns = self._c0 = -1

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def _open(self) -> None:
        ring = self._ring
        if ring.stack:
            p = ring.stack[-1]
            self.parent, self.member, self.round = p.seq, p.member, p.round
        else:
            self.parent = -1
        self.seq = ring.seq
        ring.seq += 1
        ring.stack.append(self)
        self._ann = TraceAnnotation(self.name, member=self.member,
                                    round=self.round, **(self.stats or {}))
        self._ann.__enter__()

    def _close(self, t1: int, c1: int = 0) -> None:
        self.t1 = t1
        if self._c0 >= 0:
            self.cpu_ns = c1 - self._c0
        self._ann.__exit__(None, None, None)
        # Down to this span: an exception may have passed a child's
        # close by.
        stack = self._ring.stack
        while stack and stack.pop() is not self:
            pass
        self._ring.put((self.name, self.seq, self.parent, self.member,
                        self.round, self.t0, t1, self.cpu_ns, self.stats))

    def __enter__(self) -> "Span":
        self._open()
        self.t0 = time.monotonic_ns()
        return self

    def __exit__(self, *_exc) -> None:
        self._close(time.monotonic_ns())


class Phases:
    """Consecutive spans of one thread that share their boundaries:
    ``next(name)`` closes the phase that is open and opens ``name`` on
    one reading of the clock, so no boundary is read twice and the
    phases sum to the whole. The thread's CPU clock is read at the
    boundaries of the phases named in ``cpu`` and nowhere else. ``dur``
    holds each closed phase's nanoseconds by the name given to ``next``
    (added up where a name repeats)."""

    __slots__ = ("_rec", "_prefix", "_member", "_round", "_cpu", "_cur",
                 "_key", "dur")

    def __init__(self, rec: "Recorder", prefix: str, member: int,
                 round: int, cpu: Collection[str]) -> None:
        self._rec = rec
        self._prefix = prefix
        self._member = member
        self._round = round
        self._cpu = cpu
        self._cur: Optional[Span] = None
        self._key = ""
        self.dur: Dict[str, int] = {}

    def next(self, name: str) -> Span:
        """Open phase ``name``; the span returned has its ``t0``."""
        t = time.monotonic_ns()
        cur, wants = self._cur, name in self._cpu
        c = (time.thread_time_ns()
             if wants or (cur is not None and cur._c0 >= 0) else 0)
        self._end(t, c)
        sp = Span(self._rec._ring(), self._prefix + name, self._member,
                  self._round, None)
        sp._open()
        sp.t0 = t
        if wants:
            sp._c0 = c
        self._cur, self._key = sp, name
        return sp

    def _end(self, t: int, c: int) -> None:
        cur = self._cur
        if cur is not None:
            cur._close(t, c)
            self.dur[self._key] = self.dur.get(self._key, 0) + t - cur.t0
            self._cur = None

    def end(self) -> None:
        """Close the open phase and open none (what follows belongs to
        the enclosing span alone)."""
        cur = self._cur
        self._end(time.monotonic_ns(), time.thread_time_ns()
                  if cur is not None and cur._c0 >= 0 else 0)

    def __enter__(self) -> "Phases":
        return self

    def __exit__(self, *_exc) -> None:
        self.end()


class Recorder:
    """The rings of one process. Program code records into
    :data:`DEFAULT`; a test may make its own."""

    def __init__(self, slots: int = RING_SLOTS, registry=None) -> None:
        self.slots = int(slots)
        self._registry = registry
        self._local = threading.local()
        self._rings: List[_Ring] = []
        self._made = 0  # rings ever made: the next ring's id
        self._mu = threading.Lock()  # ring list only, never a span's path

    def _ring(self) -> _Ring:
        try:
            return self._local.ring
        except AttributeError:
            with self._mu:
                ring = self._local.ring = _Ring(self.slots, self._made)
                self._made += 1
                self._rings.append(ring)
                extra = len(self._rings) - MAX_RINGS
                if extra > 0:
                    dead = [r for r in self._rings
                            if not r.thread.is_alive()][:extra]
                    self._rings = [r for r in self._rings
                                   if r not in dead]
            return ring

    def _count_drops(self, member: int, lost: int) -> None:
        # Lazy: batched.telemetry imports the hosting layer, which
        # imports this module.
        from ..batched.telemetry import trace_drop_counter

        trace_drop_counter(self._registry).labels(
            str(member), DROP_CLASS).inc(lost)

    # -- writing ---------------------------------------------------------------

    def span(self, name: str, member: int = 0, round: int = -1,
             **stats) -> Span:
        """``with rec.span("member.round", 1, 17) as sp: ...``"""
        return Span(self._ring(), name, member, round, stats or None)

    def phases(self, prefix: str, member: int = 0, round: int = -1,
               cpu: Collection[str] = ()) -> Phases:
        """``with rec.phases("rawnode.") as ph: ph.next("stage") ...``;
        ``cpu``: the phases whose thread CPU time is read too."""
        return Phases(self, prefix, member, round, cpu)

    def record(self, name: str, t0: int, t1: int,
               member: Optional[int] = None, round: int = -1,
               **stats) -> None:
        """A span whose ends were read elsewhere (a wait that begins on
        one thread and ends on another; a duration somebody else
        clocked); written to the calling thread's ring with no profiler
        event. Given its ``member`` it stands under no span, with the
        ``(member, round)`` given. With none it is a span of the
        calling thread like any other: parent and ``(member, round)``
        are those of the innermost span open there (-1 and (0, -1)
        under none)."""
        ring = self._ring()
        parent = -1
        if member is None:
            member, round = 0, -1
            if ring.stack:
                p = ring.stack[-1]
                parent, member, round = p.seq, p.member, p.round
        seq = ring.seq
        ring.seq += 1
        ring.put((name, seq, parent, member, round, t0, t1, -1,
                  stats or None))

    # -- reading ---------------------------------------------------------------

    def snapshot(self, member: Optional[int] = None) -> List[SpanRec]:
        """Every span the rings hold, ring by ring (threads in the
        order they first wrote), each ring oldest first. What a ring
        lost since it was last read is counted as dropped, under the
        member of its oldest span."""
        with self._mu:
            rings = list(self._rings)
        out: List[SpanRec] = []
        for ring in rings:
            recs, lost = ring.read()
            if lost:
                self._count_drops(recs[0][3] if recs else 0, lost)
            out.extend(SpanRec._make(r + (ring.id,)) for r in recs
                       if member is None or r[3] == member)
        return out

    def ring_id(self) -> int:
        """The calling thread's ring: ``SpanRec.thread`` of its spans."""
        return self._ring().id

    def dropped(self) -> int:
        """Spans that were pushed out before any reading got them, as
        of the last reading."""
        with self._mu:
            return sum(r.dropped for r in self._rings)

    def to_payload(self, member: Optional[int] = None) -> dict:
        """The dump / admin-op shape. ``monotonic_ns``/``wall_ns`` are a
        paired reading of the two clocks, as in the tracer's payload."""
        with self._mu:
            names = {r.id: r.thread.name for r in self._rings}
        got = self.snapshot(member)
        return {
            "fields": list(SpanRec._fields),
            "monotonic_ns": time.monotonic_ns(),
            "wall_ns": time.time_ns(),
            "dropped": self.dropped(),
            "threads": {str(k): v for k, v in names.items()},
            "spans": [list(s) for s in got],
        }

    def dump(self, member: Optional[int] = None, reason: str = "manual",
             dump_dir: Optional[str] = None,
             path: Optional[str] = None) -> str:
        """Write the rings as JSON next to the tracer's dumps."""
        if path is None:
            path = dump_path(KIND_ROUNDSPANS,
                             "all" if member is None else str(member),
                             reason, dump_dir)
        payload = self.to_payload(member)
        payload["reason"] = reason
        with open(path, "w") as f:
            json.dump(payload, f)
            f.write("\n")
        return path


DEFAULT = Recorder()
span = DEFAULT.span
phases = DEFAULT.phases
record = DEFAULT.record
snapshot = DEFAULT.snapshot

