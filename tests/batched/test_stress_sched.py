"""Scheduler-stress mode for the threaded host code (review round 4, #8).

Python has no ThreadSanitizer: Go gets `-race` for free on the
reference's heavily-threaded rafthttp/etcdserver code
(ref: scripts/test.sh:61-73); the closest honest analog here is to
MAXIMIZE interleavings and then assert clean behavior:

* `sys.setswitchinterval(5e-6)` forces preemption every few bytecode
  ops (~1000x the default 5ms), shaking out check-then-act windows;
* randomized delays are injected AT THE ROUTER BOUNDARIES
  (deliver/deliver_block), the seam between transport threads and the
  member's staging locks — where the round loop, drain worker, ticker
  and delivery threads cross;
* faulthandler is armed so a deadlock dumps all stacks on timeout;
* thread counts must return to baseline after stop (leak assertion).

What `-race` covers that this cannot: Go's detector proves the
ABSENCE of unsynchronized access on the exercised paths by
instrumenting every read/write; this test only raises the PROBABILITY
of hitting a racy interleaving and catches its symptoms (corruption,
deadlock, leak, crash). A lost update with benign symptoms can
survive it — the round-5 membership-mask race was exactly that class,
found by state inspection, not by stress. See README "Testing".
"""

import faulthandler
import random
import sys
import threading
import time

import pytest

from etcd_tpu.batched.hosting import MultiRaftCluster

G = 8


@pytest.fixture
def aggressive_scheduler():
    old = sys.getswitchinterval()
    sys.setswitchinterval(5e-6)
    faulthandler.enable()
    # A deadlock must dump all stacks and fail, not hang until the CI
    # harness SIGKILLs pytest (which faulthandler does not hook).
    faulthandler.dump_traceback_later(600, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        sys.setswitchinterval(old)


def test_router_boundary_delay_stress(tmp_path, aggressive_scheduler):
    baseline_threads = threading.active_count()
    c = MultiRaftCluster(str(tmp_path), num_members=3, num_groups=G)
    # Inject randomized delays at the router boundary of every member:
    # delivery threads now yield mid-handoff, widening every window
    # between transport staging and the round loop.
    rng = random.Random(7)
    for m in c.members.values():
        orig_deliver = m.deliver
        orig_block = m.deliver_block

        def deliver(group, msg, _o=orig_deliver):
            if rng.random() < 0.2:
                time.sleep(rng.random() * 0.002)
            _o(group, msg)

        def deliver_block(blk, _o=orig_block):
            if rng.random() < 0.2:
                time.sleep(rng.random() * 0.002)
            _o(blk)

        m.deliver = deliver
        m.deliver_block = deliver_block
    try:
        c.wait_leaders()
        errors = []
        stop = threading.Event()

        def proposer(tid):
            r2 = random.Random(tid)
            for seq in range(10):
                if stop.is_set():
                    return
                try:
                    c.put(r2.randrange(G), b"sk%d" % tid,
                          b"sv%d" % seq, timeout=15.0)
                except TimeoutError:
                    pass
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))

        threads = [threading.Thread(target=proposer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        # Join budget covers the worst LEGAL runtime (10 puts x 15s
        # swallowed timeouts each) plus margin — a slow-but-live
        # proposer is stress-induced latency, not a wedge.
        deadline = time.monotonic() + 10 * 15 + 60
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        stop.set()
        assert not any(t.is_alive() for t in threads), "proposer wedged"
        assert not errors, errors
        # Replicas converge to identical KV content under the stress.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            views = []
            for m in c.members.values():
                with m._lock:  # apply threads mutate kvs concurrently
                    views.append(tuple(sorted(
                        (g, k, v) for g in range(G)
                        for k, v in m.kvs[g].data.items())))
            if views[0] == views[1] == views[2] and views[0]:
                break
            time.sleep(0.1)
        else:
            raise AssertionError("replicas diverged under stress")
    finally:
        c.stop()
    # Leak assertion: every member/router/drain thread exits.
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if threading.active_count() <= baseline_threads:
            break
        time.sleep(0.1)
    leftover = [t.name for t in threading.enumerate()]
    assert threading.active_count() <= baseline_threads + 1, leftover
