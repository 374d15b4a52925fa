"""The trace reduction on the recorded chip trace, and the bytes
function against a hand count."""

import os

import pytest

from benchmark.reduce import roofline
from benchmark.reduce.trace import (
    op_kind,
    reduce_trace,
    scope_of,
    scope_share_pct,
)

from .util import XPROF


@pytest.fixture(scope="module")
def reduced():
    if not os.path.isdir(XPROF):
        pytest.skip("artifacts/tpu_r05/xprof is not in this checkout")
    return reduce_trace(XPROF)


# ROADMAP's hand reduction of the same trace (PR 5, 16 rounds at
# G=65536): device ms per named_scope and the share of 1,635 ms.
ROADMAP_TABLE = [("raft_route", 1424.7, 87.2), ("raft_deliver", 150.9, 9.2),
                 ("raft_emit", 12.9, None), ("raft_tick", 4.7, None),
                 ("raft_propose", 3.3, None), ("raft_control", 0.9, None),
                 ("unscoped", 37.3, None)]


@pytest.mark.parametrize("scope,ms,share", ROADMAP_TABLE,
                         ids=[r[0] for r in ROADMAP_TABLE])
def test_reduction_gives_roadmaps_table(reduced, scope, ms, share):
    assert reduced["scope_s"][scope] * 1e3 == pytest.approx(ms, abs=0.5)
    if share is not None:
        assert scope_share_pct(reduced, scope) == pytest.approx(
            share, abs=0.05)


def test_busy_window_and_modules(reduced):
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(1.635, abs=0.002)
    assert 0.0 <= reduced["idle_share_pct"] < 0.1
    assert reduced["leaf_s"] <= reduced["busy_s"]
    loop = reduced["modules"]["jit_closed_loop"]
    assert loop["count"] == 1
    assert loop["seconds"] == pytest.approx(1.63525, abs=1e-4)
    top = dict(reduced["device_ops"])
    assert top["raft_route/copy"] == pytest.approx(0.838, abs=0.001)
    assert top["raft_route/reshape"] == pytest.approx(0.586, abs=0.001)
    assert len(reduced["device_ops"]) <= 10
    assert 0.0 <= reduced["gap_s"] <= reduced["span_s"] - reduced["busy_s"]


def test_route_roofline_of_the_recorded_trace_is_a_small_percent(reduced):
    # 16 rounds of steady appends at G=65536, R=3, E=4: the append lane
    # and its responses every round, the heartbeat's two every fourth.
    need = roofline.lane_bytes(65536 * 3, 3, [0, 16, 4, 0, 16, 4],
                               [21, 49, 17, 10, 22, 13])
    assert need == 2 * 65536 * 9 * (16 * (49 + 22) + 4 * (17 + 13))
    pct = roofline.roofline_pct(need, reduced["scope_s"]["raft_route"],
                                "TPU v5 lite")
    assert 0.05 < pct < 0.3  # 93 MB a round over 89 ms, of 819 GB/s


@pytest.mark.parametrize("text,scope", [
    ("jit(closed_loop)/while/body/closed_call/raft_route/reshape",
     "raft_route"),
    ("jit(closed_loop)/while/body/jit(step_round)/vmap(raft_deliver)/while:",
     "raft_deliver"),
    ("jit(step_round)/vmap(raft_emit)/select_n", "raft_emit"),
    ("jit(pack)/concatenate", "unscoped"),
    (None, "unscoped"),
])
def test_scope_of(text, scope):
    assert scope_of(text) == scope


def test_op_kind():
    assert op_kind("%copy.288 = s32[65536,3]{1,0} copy(...)") == "copy"
    assert op_kind("%select_reduce_fusion.12 = ...") == "select_reduce_fusion"
    assert op_kind("fusion.3") == "fusion"


def test_lane_bytes_against_a_hand_count_at_two_groups():
    # G=2, R=3, E=4: 6 sender rows x 3 targets = 18 slots a lane. A round
    # in which the append lane (49 bytes a slot) and its responses (22)
    # ran: each slot read once and written once.
    slots = [21, 49, 17, 10, 22, 13]
    assert roofline.lane_bytes(6, 3, [0, 1, 0, 0, 1, 0], slots) == (
        2 * 18 * (49 + 22)) == 2556
    # All six lanes: 132 bytes a slot where the count until PR 52 had
    # six times 50.
    assert roofline.lane_bytes(6, 3, [1] * 6, slots) == 2 * 18 * 132
    assert roofline.lane_bytes(65536 * 3, 3, [1] * 6, slots) == 155_713_536
    for gone in ("route_bytes", "route_slots", "SLOT_BOOL_FIELDS",
                 "SLOT_WORD_FIELDS"):
        assert not hasattr(roofline, gone)


def test_a_device_not_in_the_table_of_peaks_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.roofline_pct(1.0, 1.0, "cpu")


# -- the one walk over the ops against the plain functions ---------------------------


def plain(ops):
    """What ``_plane`` has to give, by the plain functions beside it."""
    from benchmark.reduce.trace import _leaf_seconds, _union, idle_gaps

    total, merged = _union([(s, s + d) for s, d, _n in ops])
    return (_leaf_seconds(ops), total, merged[0][0], merged[-1][1],
            idle_gaps(ops))


def recorded_ops():
    from jax.profiler import ProfileData

    from benchmark.reduce.trace import OPS_LINE, find_xplane

    data = ProfileData.from_file(find_xplane(XPROF))
    return [[(e.start_ns, e.duration_ns, e.name) for e in line.events]
            for p in data.planes if p.name.startswith("/device:TPU:")
            for line in p.lines if line.name == OPS_LINE]


def made_ops(seed: int):
    """Ops that nest, tie, touch and pause, shuffled: loops with
    children that start with them, end with them or last no time, gaps
    under and over ``MIN_GAP_NS``, names that end together."""
    import random

    rnd = random.Random(seed)
    ops, t = [], 0.0
    for i in range(rnd.randint(1, 60)):
        t += rnd.choice([0.0, 0.0005, 0.5, 3.0, 1500.0, 2500.0])
        d = rnd.choice([0.0, 1.0, 10.0, 100.0, 1000.0])
        kind = rnd.choice(["copy", "fusion", "while"])
        ops.append((t, d, f"%op.{i} = f32[] {kind}()"))
        for c in range(rnd.randint(0, 3)):
            at = t + rnd.choice([0.0, d / 4])
            ops.append((at, rnd.choice([0.0, d / 4, t + d - at]),
                        f"%kid.{i}.{c} = f32[] add()"))
        t += d * rnd.choice([0.5, 1.0, 1.0])
    rnd.shuffle(ops)
    return ops


@pytest.mark.parametrize("seed", range(8))
def test_the_one_walk_gives_what_the_plain_functions_give(seed):
    from benchmark.reduce.trace import _plane

    for k in range(40):
        ops = made_ops(1000 * seed + k)
        assert _plane(ops) == plain(ops), (seed, k)


def test_the_one_walk_on_the_recorded_trace():
    from benchmark.reduce.trace import _plane

    if not os.path.isdir(XPROF):
        pytest.skip("artifacts/tpu_r05/xprof is not in this checkout")
    planes = recorded_ops()
    assert planes and all(len(ops) > 1000 for ops in planes)
    for ops in planes:
        assert _plane(ops) == plain(ops)
