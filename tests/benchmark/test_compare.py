"""The comparison that decides ``correct`` can fail: fed one fault at a
time it says ``false``, and ``true`` for the sound data. And the
controls — the plain reference with one guarantee broken, in the
program's place — come out not correct through the generator itself."""

import copy
import tempfile

import numpy as np
import pytest

from benchmark.compare import engine_checks, served_checks, verdict
from benchmark.generators import kv_closed
from benchmark.harness import Probe
from benchmark.reference import kv as plain
from benchmark.reference.shadow import ShadowCluster


def served_case():
    acked = {(g, bytes([g + 1, i + 1])): bytes([i]) * 4
             for g in range(3) for i in range(4)}
    proposed = dict(acked)
    proposed[(0, b"inflight")] = b"x"
    kvs = [[{k: v for (g2, k), v in acked.items() if g2 == g}
            for g in range(3)] for _ in range(3)]
    lreads = [(g, k, v) for (g, k), v in list(acked.items())[:5]]
    sample = list(acked)[:4]
    return dict(acked=acked, proposed=proposed, member_kvs=kvs,
                wal_fsyncs=[5, 5, 5], window_fsyncs=[2, 2, 2],
                lreads=lreads, restart_kvs=copy.deepcopy(kvs),
                restart_sample=sample)


def lose_acked_put(c):
    g, k = next(iter(c["acked"]))
    del c["member_kvs"][2][g][k]


def stale_read(c):
    g, k, _v = c["lreads"][0]
    c["lreads"][0] = (g, k, None)


def diverged_replica(c):
    c["member_kvs"][1][1][b"\x02\x01"] = b"other"


def value_never_proposed(c):
    for kvs in c["member_kvs"]:
        kvs[2][b"ghost"] = b"boo"


def no_fsync(c):
    c["wal_fsyncs"][0] = 0


def no_fsync_in_window(c):
    c["window_fsyncs"][1] = 0


def lost_after_restart(c):
    g, k = c["restart_sample"][0]
    del c["restart_kvs"][0][g][k]


def nothing_read(c):
    c["lreads"] = []


def read_not_served(c):
    c["lreads_unserved"] = 1


SERVED_FAULTS = [lose_acked_put, stale_read, diverged_replica,
                 value_never_proposed, no_fsync, no_fsync_in_window,
                 lost_after_restart, nothing_read, read_not_served]


def test_served_sound_data_is_correct():
    assert verdict(served_checks(**served_case()))
    c = served_case()
    c.update(restart_kvs=None, restart_sample=[], window_fsyncs=None)
    assert verdict(served_checks(**c))


@pytest.mark.parametrize("fault", SERVED_FAULTS, ids=lambda f: f.__name__)
def test_served_fault_is_not_correct(fault):
    c = served_case()
    fault(c)
    checks = served_checks(**c)
    assert not verdict(checks)
    assert sum(not ch.ok for ch in checks) >= 1


def test_no_checks_is_not_correct():
    assert not verdict([])


# -- engine ----------------------------------------------------------------------

G, W = 4, 32
# Groups 1 and 3 draw the same leader slot; 3 is not in the sample.
SLOTS = {3: np.array([0, 1, 2, 1], np.int32),
         5: np.array([0, 3, 4, 3], np.int32)}


def shadow(r, g, rounds=24, control=False):
    from benchmark.reference.raft import quorum
    from benchmark.reference.raft.logger import DefaultLogger, set_logger

    set_logger(DefaultLogger(level=2))
    sound = quorum.MajorityConfig.committed_index
    if control:
        quorum.MajorityConfig.committed_index = (
            lambda self, acked: max((acked(v) or 0 for v in self),
                                    default=0))
    try:
        sh = ShadowCluster(r, heartbeat_timeout=4, group=g,
                           deterministic_timeouts=True,
                           auto_compact_window=W, max_ents=4,
                           deliver_shape="merged")
        lead = int(SLOTS[r][g])
        sh.round(campaigns=[lead])
        for _ in range(4):
            sh.round()
        for _ in range(rounds):
            sh.round(tick=True, proposals={lead: 2})
        return sh
    finally:
        quorum.MajorityConfig.committed_index = sound


def engine_state(r, shadows):
    """The engine's arrays as they would be if it equalled the
    reference: built from the reference itself."""
    n = G * r
    st = {f: np.zeros(n, np.int64)
          for f in ("term", "role", "lead", "commit", "last", "snap_index")}
    st["log_term"] = np.zeros((n, W), np.int64)
    st["randomized_timeout"] = np.arange(n)
    for g, sh in enumerate(shadows):
        rows = sh.snapshot_state()
        for s in range(r):
            i = g * r + s
            (st["term"][i], st["role"][i], st["lead"][i], st["commit"][i],
             st["last"][i]) = rows[s]
            log = sh.log_terms(s)
            st["snap_index"][i] = log[0][0] - 1 if log else rows[s][4]
            for idx, t in log:
                st["log_term"][i, idx % W] = t
    return st


@pytest.fixture(scope="module", params=[3, 5], ids=["R3", "R5"])
def shadows(request):
    """(replicas of a group, the sound reference of each group)."""
    r = request.param
    return r, [shadow(r, g) for g in range(G)]


def run_engine_checks(st, shadows):
    r, ref = shadows
    return engine_checks(st, G, r, W, SLOTS[r], [0, 1, 2],
                         lambda g: ref[g].snapshot_state(),
                         lambda g, s: ref[g].log_terms(s))


def test_engine_sound_state_is_correct(shadows):
    assert verdict(run_engine_checks(engine_state(*shadows), shadows))


def engine_log_differs(st, r):
    i = 1 * r + 0
    st["log_term"][i, int(st["last"][i]) % W] += 1


def engine_commit_behind(st, r):
    st["commit"][2 * r + 1] -= 1


def engine_group_committed_nothing(st, r):
    st["commit"][3 * r:4 * r] = 0


def engine_class_unequal(st, r):
    st["last"][3 * r + 2] += 1


ENGINE_FAULTS = [engine_log_differs, engine_commit_behind,
                 engine_group_committed_nothing, engine_class_unequal]


@pytest.mark.parametrize("fault", ENGINE_FAULTS, ids=lambda f: f.__name__)
def test_engine_fault_is_not_correct(shadows, fault):
    st = engine_state(*shadows)
    fault(st, shadows[0])
    assert not verdict(run_engine_checks(st, shadows))


def test_engine_control_commit_without_quorum_is_not_correct(shadows):
    """The control: the reference with the quorum rule broken, put in
    the program's place; at five replicas as at three."""
    r = shadows[0]
    control = [shadow(r, g, control=True) for g in range(G)]
    checks = run_engine_checks(engine_state(r, control), shadows)
    assert not verdict(checks)
    bad = {c.name for c in checks if not c.ok}
    assert "sampled_replicas_state_differs_from_reference" in bad


# -- served controls through the generator itself ---------------------------------

TRAFFIC = {"clients": 24, "key_bytes": 8, "value_bytes": 256,
           "preload_keys_per_group": 2, "preload_inflight_per_group": 4,
           "poll_interval_ms": 1.0, "leader_refresh_ms": 250.0,
           "ramp_s": 0.1, "op_timeout_s": 5.0, "retry_after_s": 2.0}


def drive_plain(broken, read_share, seed=11):
    traffic = dict(TRAFFIC, read_share=read_share)
    load = kv_closed.make(traffic, {"num_groups": 6}, seed)
    target = plain.PlainCluster(6, 3, broken)
    kv_closed.preload(target, load, traffic)
    raw = kv_closed.run(target, load, traffic, 0.4,
                        Probe(False, 0.0, tempfile.gettempdir()))
    return raw, target.checks(raw, check_lread=True)


@pytest.mark.parametrize("read_share", [0.0, 0.5, 1.0])
def test_plain_reference_is_correct(read_share):
    raw, checks = drive_plain(None, read_share)
    assert verdict(checks), [c for c in checks if not c.ok]
    assert raw["failed"] == 0 and raw["attempted"] > 0
    assert raw["ops_per_s"] > 0 and raw["op_p95_ms"] > 0


@pytest.mark.parametrize("broken", plain.BROKEN)
@pytest.mark.parametrize("read_share", [0.0, 1.0])
def test_control_is_not_correct(broken, read_share):
    _raw, checks = drive_plain(broken, read_share)
    assert not verdict(checks)
