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
    need = 16 * roofline.route_bytes(65536, 3, 4)
    pct = roofline.roofline_pct(need, reduced["scope_s"]["raft_route"],
                                "TPU v5 lite")
    assert 0.3 < pct < 0.7  # 354 MB a round over 89 ms, of 819 GB/s


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


def test_route_bytes_against_a_hand_count_at_two_groups():
    # G=2, R=3, E=4: N = 6 sender rows x 3 targets x 6 kinds = 108 slots.
    # A slot is 2 bools + 8 int32 words + 4 int32 entry terms = 50 bytes,
    # read once and written once.
    assert roofline.route_slots(2, 3) == 108
    assert roofline.route_bytes(2, 3, 4) == 2 * 50 * 108 == 10800
    assert roofline.route_bytes(65536, 3, 4) == 353_894_400


def test_a_device_not_in_the_table_of_peaks_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        roofline.roofline_pct(1.0, 1.0, "cpu")
