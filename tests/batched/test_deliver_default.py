"""The one deliver: what ``deliver_shape`` still names, that no loop
sits in the round, and what the engine's lane counter reads.

``deliver_shape`` admits "auto" and "vectorized", two names of one
program, and nothing else. No per-sender loop in deliver: the lowered
round holds no ``while`` and the closed loop exactly one (the round
scan), so a sender loop that comes back fails here and not in a
benchmark. The lane counter (``MultiRaftEngine.lane_rounds``) says in
how many scan rounds each inbox lane was occupied: what deliver's lane
skip saves.

Round-step programs: none new. ``CELL`` and ``R5`` are
``test_scan_faults``' (the benchmark's ``engine100k-r3`` and
``engine10k-r5`` at 8 groups), and the lowerings below compile nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.analysis import sentinels
from etcd_tpu.batched import MultiRaftEngine
from etcd_tpu.batched.step import (KIND_APP, KIND_APP_RESP, KIND_HB,
                                   KIND_HB_RESP, KIND_VOTE, KIND_VOTE_RESP,
                                   NUM_KINDS)

from .test_scan_faults import CELL, R5

ROUNDS = 64  # a call of every live cell


# -- (a) one deliver, whatever it is called ----------------------------------------


def test_auto_is_one_shape_on_cpu_and_on_tpu():
    """"auto" and "vectorized" key one program on any platform (no
    backend is asked), and ``validate()`` refuses every other name,
    the two deleted shapes' included."""
    named = R5._replace(deliver_shape="vectorized")
    assert R5.deliver_shape == "auto"
    assert R5.validate().resolved() == named == named.validate().resolved()
    before = set(sentinels.compile_keys("round_step"))
    a, b = MultiRaftEngine(R5), MultiRaftEngine(named)
    assert a.cfg == b.cfg == named
    new = set(sentinels.compile_keys("round_step")) - before
    assert len(new) <= 1 and all("'vectorized'" in k for k in new)
    for other in ("lanes", "merged", "", "Vectorized", "scan"):
        with pytest.raises(ValueError, match="deliver has one order"):
            R5._replace(deliver_shape=other).validate()
        with pytest.raises(ValueError, match="deliver has one order"):
            MultiRaftEngine(R5._replace(deliver_shape=other))


# -- (b) no sender loop in the lowered programs ----------------------------------


def _lowered_texts(cfg):
    """(resolved config, one round, the 64-round closed loop) as
    lowered text; lowering compiles nothing and adds no compile key."""
    eng = MultiRaftEngine(cfg)
    keys = {kind: set(sentinels.compile_keys(kind))
            for kind in ("round_step", "closed_loop")}
    zb, zi = eng._zeros_b, eng._zeros_i
    one = jax.jit(eng._step).lower(
        eng.state, eng.inbox, zb, zb, zi, zb).as_text()
    loop = eng._closed_loop.lower(
        eng.state, eng.inbox, zb, zi, eng._tel(), eng._flt(), eng._lanes,
        None, ROUNDS).as_text()
    for kind, before in keys.items():
        assert set(sentinels.compile_keys(kind)) == before
    return eng.cfg, one, loop


@pytest.mark.parametrize("cfg", [R5, CELL], ids=["r5_append", "r3_elections"])
def test_the_resolved_round_holds_no_loop_and_the_scan_one(cfg):
    resolved, one, loop = _lowered_texts(cfg)
    assert resolved.deliver_shape == "vectorized"
    assert one.count("stablehlo.while") == 0, (
        "a loop is back inside the round: deliver folds each lane once "
        "over the sender axis and scans nothing")
    assert "stablehlo.reduce" in one  # the text is the program's
    assert loop.count("stablehlo.while") == 1, "only the round scan loops"
    for text in (one, loop):
        assert text.count("stablehlo.sort") == 0, (
            "a sort is back in the round: the quorum index is an "
            "elementwise order statistic (kernels.quorum_committed), "
            "which the TPU compiler fuses; a sort it never does")


# -- (c) the lane counter ----------------------------------------------------------


def _settled_append_engine():
    """``drivers/engine.py``'s set-up at 8 groups of 5: slot 0 of every
    group elected without ticks, then 2 proposals a round on it."""
    eng = MultiRaftEngine(R5)
    r = eng.cfg.num_replicas
    leaders = np.arange(eng.cfg.num_groups) * r
    eng.campaign(leaders)
    eng.run_rounds(ROUNDS, tick=False)
    assert (eng.leaders() == 0).all()
    props = jnp.zeros((eng.cfg.num_instances,), jnp.int32)
    return eng, props.at[jnp.asarray(leaders)].set(2)


def test_lane_rounds_of_a_settled_append_schedule():
    eng, props = _settled_append_engine()
    settle = eng.lane_rounds()
    assert settle.shape == (NUM_KINDS,) and settle.dtype == np.int32
    # The election went through the vote lanes, inside the settle scan
    # (campaign() itself is a step_round, which is not counted).
    assert settle[KIND_VOTE] > 0 and settle[KIND_VOTE_RESP] > 0
    eng.run_rounds(ROUNDS, propose_n=props)  # the heartbeat's phase settles
    before = eng.lane_rounds()
    eng.run_rounds(ROUNDS, propose_n=props)
    eng.run_rounds_pipelined(ROUNDS, chunk=16, propose_n=props)
    got = eng.lane_rounds() - before
    beats = 2 * ROUNDS // eng.cfg.heartbeat_timeout
    want = np.zeros(NUM_KINDS, np.int64)
    want[[KIND_APP, KIND_APP_RESP]] = 2 * ROUNDS
    want[[KIND_HB, KIND_HB_RESP]] = beats
    assert got.tolist() == want.tolist(), (
        "votes never, appends and their acks every round, heartbeats "
        "and theirs one round in heartbeat_timeout")
    assert (eng.commits().min(axis=1) > 0).all()


def test_lane_rounds_counts_every_lane_under_an_outage_with_prevote():
    eng = MultiRaftEngine(CELL)
    r = eng.cfg.num_replicas
    leaders = np.arange(eng.cfg.num_groups) * r
    eng.campaign(leaders)
    eng.run_rounds(16, tick=False)
    before = eng.lane_rounds()
    sched = np.zeros((ROUNDS, r), bool)
    sched[8:40, 0] = True  # the leaders' node, for three election timeouts
    props = jnp.full((eng.cfg.num_instances,), 2, jnp.int32)
    eng.run_rounds(ROUNDS, propose_n=props, isolate=sched)
    got = eng.lane_rounds() - before
    assert (got > 0).all(), got.tolist()
    assert (got <= ROUNDS).all()
    assert (eng.leaders() != 0).any(), "the outage elected nobody"


def test_lane_rounds_pipelined_under_a_schedule_equal_run_rounds():
    """The counter rides both entry points' one scan: the same rounds
    with the same node schedule, chunked and pipelined, read the same
    lanes as one ``run_rounds`` call a chunk."""
    r = CELL.num_replicas
    sched = np.zeros((ROUNDS, r), bool)
    sched[8:40, 0] = True
    counts = []
    for pipelined in (False, True):
        eng = MultiRaftEngine(CELL)
        eng.campaign(np.arange(eng.cfg.num_groups) * r)
        eng.run_rounds(16, tick=False)
        props = jnp.full((eng.cfg.num_instances,), 2, jnp.int32)
        before = eng.lane_rounds()
        if pipelined:
            eng.run_rounds_pipelined(ROUNDS, chunk=16, tick=True,
                                     propose_n=props, isolate=sched)
        else:
            for t in range(0, ROUNDS, 16):
                eng.run_rounds(16, tick=True, propose_n=props,
                               isolate=sched[t:t + 16])
        counts.append((eng.lane_rounds() - before).tolist())
    assert counts[0] == counts[1]
    assert all(c > 0 for c in counts[0]), counts[0]
