"""Generator ``kv_closed``: a closed loop of N clients over a KV cluster.

The upstream tool (``tools/benchmark put`` / ``range``) is a closed loop
of N clients, each with one request outstanding, and so is this. The
traffic file gives the numbers:

* ``clients`` and ``read_share``: ``round(clients * read_share)`` clients
  read linearizably, the rest put. A reading client is a thread (the
  member's ``linearizable_get`` blocks); the putting clients are logical
  and share this one generator thread, which finds their
  acknowledgements by the watermark poll copied from
  ``hosting_proc._bench``: one compare of each member's
  ``applied_index`` per poll, key checks only in groups whose mark
  moved.
* puts: every put a fresh ``key_bytes`` key (bytes 1..255) and a
  ``value_bytes`` value, in a group drawn uniformly, all from the seed;
  offered to the group's leader on whichever member leads it. A refusal
  or a lost leader is retried like a client following leader hints; a
  put with no acknowledgement ``retry_after_s`` after it was offered is
  offered again (puts are idempotent), and after ``op_timeout_s`` it has
  failed.
* reads: each of a key of the preloaded keyspace
  (``preload_keys_per_group`` keys in every group, put during set-up),
  drawn uniformly from the seed, at the group's leader.
* ``ramp_s`` of load before the window, so the window sees a loop in
  its stride; ``poll_interval_ms`` between polls, printed with the
  result.

An acknowledgement is the put applied on its leader, which the member
does only after the quorum's fsync. Latency is from a client's call to
the poll that finds the acknowledgement.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..harness import say

CHUNK = 16384


def make(traffic: dict, sizes: dict, seed: int) -> dict:
    """Everything drawn from the seed: the preloaded keyspace, the
    stream of fresh puts (made in chunks, on demand) and each reading
    client's order of keys."""
    groups = int(sizes["num_groups"])
    kb, vb = int(traffic["key_bytes"]), int(traffic["value_bytes"])
    kpg = int(traffic.get("preload_keys_per_group", 0))
    rng = np.random.default_rng([seed, 0x9E10])
    n = groups * kpg
    keys = rng.integers(1, 256, size=(n, kb), dtype=np.uint8)
    vals = rng.integers(0, 256, size=(n, vb), dtype=np.uint8)
    preload = [(i // kpg, keys[i].tobytes(), vals[i].tobytes())
               for i in range(n)]
    readers = int(round(int(traffic["clients"])
                        * float(traffic.get("read_share", 0.0))))
    return {
        "groups": groups, "key_bytes": kb, "value_bytes": vb,
        "preload": preload, "seed": seed,
        "readers": readers,
        "writers": int(traffic["clients"]) - readers,
    }


class PutStream:
    """The seeded stream of fresh puts: (group, key, value) forever."""

    def __init__(self, load: dict) -> None:
        self.load = load
        self.rng = np.random.default_rng([load["seed"], 0x9E11])
        self.buf: deque = deque()

    def next(self) -> Tuple[int, bytes, bytes]:
        if not self.buf:
            ld = self.load
            g = self.rng.integers(0, ld["groups"], size=CHUNK)
            k = self.rng.integers(1, 256, size=(CHUNK, ld["key_bytes"]),
                                  dtype=np.uint8)
            v = self.rng.integers(0, 256, size=(CHUNK, ld["value_bytes"]),
                                  dtype=np.uint8).tobytes()
            vb = ld["value_bytes"]
            self.buf.extend(
                (int(g[i]), k[i].tobytes(), v[i * vb:(i + 1) * vb])
                for i in range(CHUNK))
        return self.buf.popleft()


class _PutLoop:
    """The logical putting clients and the poll that serves them."""

    def __init__(self, target, traffic: dict, n_members: int) -> None:
        self.t = target
        self.retry_after = float(traffic["retry_after_s"])
        self.timeout = float(traffic["op_timeout_s"])
        self.refresh = float(traffic["leader_refresh_ms"]) / 1e3
        self.n_members = n_members
        self.lead = target.leaders()
        self.lead_at = time.perf_counter()
        self.marks = [target.applied_marks(m) for m in range(n_members)]
        # pend[member][group] -> ops offered there, oldest first.
        self.pend: List[Dict[int, deque]] = [{} for _ in range(n_members)]
        self.unsent: deque = deque()
        self.outstanding = 0
        self.acked: Dict[Tuple[int, bytes], bytes] = {}
        self.proposed: Dict[Tuple[int, bytes], bytes] = {}
        self.done: List[Tuple[float, float, bool]] = []  # t, latency, ok
        self.refusals = 0
        self.reoffers = 0
        self.last_sweep = time.perf_counter()

    def offer(self, op: list, now: float) -> None:
        """op = [group, key, value, t_call, t_offered, member]."""
        g = op[0]
        if now - self.lead_at > self.refresh:
            self.lead, self.lead_at = self.t.leaders(), now
        m = int(self.lead[g]) - 1
        if m >= 0 and self.t.propose(m, g, op[1], op[2]):
            op[4], op[5] = now, m
            self.pend[m].setdefault(g, deque()).append(op)
        else:
            self.refusals += 1
            self.lead_at = 0.0  # look again before the next offer
            self.unsent.append(op)

    def submit(self, g: int, k: bytes, v: bytes, now: float) -> None:
        self.proposed[(g, k)] = v
        self.outstanding += 1
        self.offer([g, k, v, now, now, -1], now)

    def _finish(self, op: list, now: float, ok: bool) -> None:
        self.outstanding -= 1
        if ok:
            self.acked[(op[0], op[1])] = op[2]
        self.done.append((now, now - op[3], ok))

    def in_flight_per_group(self, groups) -> Dict[int, int]:
        live = dict.fromkeys(groups, 0)
        for pend in self.pend:
            for g, q in pend.items():
                live[g] += len(q)
        for op in self.unsent:
            live[op[0]] += 1
        return live

    def poll(self, now: float) -> int:
        """Find acknowledgements; returns how many clients came free."""
        freed = 0
        sweep = now - self.last_sweep > 1.0
        if sweep:
            self.last_sweep = now
        for m in range(self.n_members):
            marks = self.t.applied_marks(m)
            pend = self.pend[m]
            if sweep:
                groups = list(pend)
            else:
                groups = np.nonzero(marks != self.marks[m])[0].tolist()
            self.marks[m] = marks
            for g in groups:
                q = pend.get(g)
                if not q:
                    continue
                keep = deque()
                for op in q:
                    if self.t.applied_value(m, g, op[1]) == op[2]:
                        self._finish(op, now, True)
                        freed += 1
                    elif now - op[3] > self.timeout:
                        self._finish(op, now, False)
                        freed += 1
                    elif sweep and now - op[4] > self.retry_after:
                        self.reoffers += 1
                        self.unsent.append(op)
                    else:
                        keep.append(op)
                if keep:
                    pend[g] = keep
                else:
                    del pend[g]
        for _ in range(len(self.unsent)):
            op = self.unsent.popleft()
            if now - op[3] > self.timeout:
                self._finish(op, now, False)
                freed += 1
            else:
                self.offer(op, now)
        return freed


def preload(target, load: dict, traffic: dict) -> None:
    """Put the keyspace the reads will ask for, ``preload_inflight_per_
    group`` keys of a group in flight at a time. Part of set-up."""
    items = load["preload"]
    load["preloaded"] = {}
    if not items:
        return
    loop = _PutLoop(target, traffic, len(target.members))
    per_group: Dict[int, deque] = {}
    for g, k, v in items:
        per_group.setdefault(g, deque()).append((k, v))
    inflight = int(traffic["preload_inflight_per_group"])
    live = {g: 0 for g in per_group}
    deadline = time.perf_counter() + 600.0
    while loop.outstanding or any(per_group.values()):
        now = time.perf_counter()
        if now > deadline:
            raise TimeoutError(
                f"preload: {len(loop.acked)}/{len(items)} acknowledged")
        for g, q in per_group.items():
            while q and live[g] < inflight:
                k, v = q.popleft()
                live[g] += 1
                loop.submit(g, k, v, now)
        loop.poll(time.perf_counter())
        live = loop.in_flight_per_group(per_group)
        time.sleep(0.002)
    failed = [d for d in loop.done if not d[2]]
    if failed or len(loop.acked) != len(items):
        raise RuntimeError(
            f"preload: {len(failed)} puts failed, {len(loop.acked)}/"
            f"{len(items)} acknowledged")
    load["preloaded"] = dict(loop.acked)


class _Reader(threading.Thread):
    """One reading client: a closed loop of ``linearizable_get``."""

    def __init__(self, idx: int, target, load: dict, traffic: dict,
                 stop: threading.Event, shared: dict) -> None:
        super().__init__(daemon=True, name=f"bench-reader-{idx}")
        self.t = target
        self.items = load["preload"]
        self.rng = np.random.default_rng([load["seed"], 0x9E12, idx])
        self.timeout = float(traffic["op_timeout_s"])
        self.stop_ev = stop
        self.shared = shared
        self.done: List[Tuple[float, float, bool]] = []
        self.answers: List[Tuple[int, bytes, Optional[bytes]]] = []
        self.retries = 0
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            self._loop()
        except BaseException as e:  # surfaced by the generator thread
            self.error = e

    def _loop(self) -> None:
        order = self.rng.integers(0, len(self.items), size=4096)
        i = 0
        while not self.stop_ev.is_set():
            g, k, v = self.items[int(order[i % len(order)])]
            i += 1
            if i % len(order) == 0:
                order = self.rng.integers(0, len(self.items), size=4096)
            t0 = time.perf_counter()
            got, ok = None, False
            while True:
                m = int(self.shared["lead"][g]) - 1
                try:
                    if m >= 0:
                        got = self.t.lread(m, g, k, self.timeout)
                        ok = True
                        break
                except TimeoutError:
                    break
                except self.shared["retry"]:
                    pass
                self.retries += 1
                self.shared["stale"] = True
                if (time.perf_counter() - t0 > self.timeout
                        or self.stop_ev.is_set()):
                    break
                time.sleep(0.005)
            now = time.perf_counter()
            if ok or not self.stop_ev.is_set():
                self.done.append((now, now - t0, ok and got == v))
                if ok and len(self.answers) < 64:
                    self.answers.append((g, k, got))
                elif ok and got != v:
                    self.answers.append((g, k, got))


def p95(values: List[float]) -> float:
    """95th percentile, nearest rank."""
    s = sorted(values)
    return s[min(len(s) - 1, int(0.95 * len(s)))]


def run(target, load: dict, traffic: dict, seconds: float, probe) -> dict:
    """Ramp, then the window of ``seconds``; returns what was counted."""
    poll_s = float(traffic["poll_interval_ms"]) / 1e3
    ramp = float(traffic["ramp_s"])
    n_members = len(target.members)
    loop = _PutLoop(target, traffic, n_members)
    loop.acked.update(load.get("preloaded", {}))
    loop.proposed.update(load.get("preloaded", {}))
    stream = PutStream(load)
    writers = load["writers"]

    stop = threading.Event()
    shared = {"lead": target.leaders(), "stale": False,
              "retry": target.Retry}
    readers = [_Reader(i, target, load, traffic, stop, shared)
               for i in range(load["readers"])]
    if readers and not load["preload"]:
        raise ValueError("reading clients need preload_keys_per_group > 0")
    for r in readers:
        r.start()

    t_begin = time.perf_counter()
    t_w0 = t_begin + ramp
    t_w1 = t_w0 + seconds
    cpu0 = cpu1 = None
    opened = closed = False
    t_end = 0.0
    free = writers
    polls = 0
    lead_changes = 0
    lead_at = t_begin
    while True:
        now = time.perf_counter()
        if not opened and now >= t_w0:
            opened, t_w0 = True, now
            t_w1 = t_w0 + seconds
            cpu0 = time.thread_time()
            target.window_opens()
        if opened and not closed and now >= t_w1:
            closed, t_w1 = True, now
            cpu1 = time.thread_time()
            target.window_closes()
            # The trace is taken after the window, under the same load:
            # closing it stalls this thread for seconds, which would
            # otherwise fall into the window's counters.
            probe.start()
            t_end = now + (probe.length_s if probe.want else 0.0)
        if closed and now >= t_end:
            break
        for _ in range(free):
            g, k, v = stream.next()
            while (g, k) in loop.proposed:
                g, k, v = stream.next()
            loop.submit(g, k, v, now)
        free = loop.poll(time.perf_counter())
        if shared["stale"] or now - lead_at > 0.25:
            lead = target.leaders()
            if opened and not closed:
                lead_changes += int((lead != shared["lead"]).sum())
            shared["lead"], shared["stale"] = lead, False
            lead_at = now
        polls += 1
        if opened and not closed and polls % 8 == 0:
            target.sample()
        time.sleep(poll_s)
    probe.stop()

    # Past the window: stop offering, let what is in flight finish (an
    # acknowledgement still counts for the read-back, not for a metric).
    stop.set()
    deadline = time.perf_counter() + float(traffic["op_timeout_s"]) + 1.0
    while loop.outstanding and time.perf_counter() < deadline:
        loop.poll(time.perf_counter())
        time.sleep(poll_s)
    for r in readers:
        r.join(timeout=float(traffic["op_timeout_s"]) + 5.0)
        if r.is_alive():
            raise RuntimeError(f"{r.name} did not stop")
        if r.error is not None:
            raise r.error

    done = list(loop.done)
    lreads: List[Tuple[int, bytes, Optional[bytes]]] = []
    for r in readers:
        done.extend(r.done)
        lreads.extend(r.answers)
    in_win = [d for d in done if t_w0 <= d[0] <= t_w1]
    ok = [d for d in in_win if d[2]]
    window_s = t_w1 - t_w0
    if not ok:
        raise RuntimeError("no operation was acknowledged in the window")
    lat_ms = [d[1] * 1e3 for d in ok]
    out = {
        "window_s": window_s,
        "attempted": len(in_win),
        "failed": len(in_win) - len(ok),
        "ops_per_s": len(ok) / window_s,
        "op_p95_ms": p95(lat_ms),
        "op_p50_ms": sorted(lat_ms)[len(lat_ms) // 2],
        "latency_samples": len(lat_ms),
        "poll_interval_ms": poll_s * 1e3,
        "polls": polls,
        "clients_putting": writers,
        "clients_reading": len(readers),
        "client_cpu_s": cpu1 - cpu0,
        "refusals": loop.refusals,
        "reoffers": loop.reoffers,
        "read_retries": sum(r.retries for r in readers),
        "acked": loop.acked,
        "proposed": loop.proposed,
        "lreads": lreads,
        "counters": target.window_counters(),
    }
    thirds = [sum(1 for d in ok if t_w0 + i * window_s / 3 <= d[0]
                  < t_w0 + (i + 1) * window_s / 3) / (window_s / 3)
              for i in range(3)]
    out["leader_changes"] = lead_changes
    say("steadiness", ops_per_s_by_third=thirds,
        leader_changes=lead_changes, failed=out["failed"],
        reoffers=loop.reoffers, refusals=loop.refusals)
    say("latency", samples=len(lat_ms), p50_ms=out["op_p50_ms"],
        p95_ms=out["op_p95_ms"], max_ms=max(lat_ms),
        poll_interval_ms=out["poll_interval_ms"])
    return out
