"""Regression coverage for the restarted-member progress wedge
(ISSUE 4 / ROADMAP PR 2 open item, now fixed).

Mechanism (root-caused with the kernel telemetry invariant sweep —
see CHANGES.md PR 4): a follower that loses acked log entries (torn
WAL tail, out of raft's durability model) rejects the leader's probe
at ``next-1`` with a hint BELOW the leader's stale-high ``match``;
``_leader_app_resp`` then set ``next = hint+1 <= match`` — an illegal
progress state the reference's ``Next >= Match+1`` invariant makes
unreachable — after which every re-ack at-or-below ``match`` failed
``updated = match < m.index`` and was dropped wholesale. ``next``
froze, ``probe_sent`` pinned, and the missing suffix was never sent.

The fix repairs ``match`` downward from the follower's own rejection
evidence (always safe: commit is monotone), letting the normal
reject/backtrack/resend cycle re-heal the log.

The deterministic kernel-level test runs in tier-1; the stochastic
TCP chaos repro (the scenario the wedge was first seen in) is
slow-marked.
"""

import tempfile

import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.state import REPLICATE
from etcd_tpu.batched.step import NUM_KINDS, empty_msgs
from etcd_tpu.batched.telemetry import decode_invariants


def test_torn_follower_heals_deterministically():
    """Leader holds stale-high match for a follower whose acked suffix
    is torn away; the group must re-converge (pre-fix: next pinned
    <= match, follower frozen a suffix behind forever).

    The config is value-identical to tests/batched/test_telemetry.py's
    CFG_ON so the jitted round program is shared within a tier-1 run
    (_step_round_jit caches by config value)."""
    cfg = BatchedConfig(
        num_groups=2, num_replicas=3, window=32, max_ents_per_msg=4,
        max_props_per_round=4, election_timeout=1 << 20,
        heartbeat_timeout=1, telemetry=True,
    )
    eng = MultiRaftEngine(cfg)
    n = cfg.num_instances
    eng.campaign([0])
    for _ in range(4):
        eng.step_round()
    assert eng.leaders()[0] == 0
    props = jnp.zeros((n,), jnp.int32).at[0].set(4)
    for _ in range(3):
        eng.step_round(propose_n=props)
    for _ in range(4):
        eng.step_round()
    st = eng.state
    assert int(st.match[0, 1]) >= 13  # follower fully acked

    # Torn-tail restart of follower instance 1: its log rolls back to
    # index 4 while the leader's match stays stale-high (entries the
    # follower acked — and the leader may have committed — are gone:
    # the durability violation real torn tails inflict). The gap (>= 9
    # entries) exceeds max_ents_per_msg, so pre-fix every re-accepted
    # probe acked at-or-below the stale match and was dropped.
    st = eng.state
    eng.state = st._replace(
        last=st.last.at[1].set(4),
        commit=st.commit.at[1].set(4),
        applied=st.applied.at[1].set(4),
    )
    eng.inbox = empty_msgs(
        (cfg.num_instances, cfg.num_replicas, NUM_KINDS),
        cfg.max_ents_per_msg)

    eng.step_round(tick=True, propose_n=props)  # fresh traffic
    for _ in range(39):
        eng.step_round(tick=True)
    st = eng.state
    last = np.asarray(st.last)[:3]
    assert (last == last[0]).all(), (
        f"progress wedge: follower last {last.tolist()}, leader "
        f"match {np.asarray(st.match[0]).tolist()} "
        f"next {np.asarray(st.next[0]).tolist()}")
    assert (np.asarray(st.commit)[:3] == int(st.last[0])).all()
    # Leader progress legal and replicating again.
    assert (np.asarray(st.next[0]) > np.asarray(st.match[0])).all()
    assert (np.asarray(st.pr_state[0]) == REPLICATE).all()
    # The invariant sweep stayed clean END-OF-ROUND throughout: the
    # repair happens in the same round the rejection is processed.
    _counters, inv = eng.telemetry()
    assert (inv == 0).all(), [decode_invariants(int(b)) for b in inv]


@pytest.mark.slow
@pytest.mark.chaos
def test_tcp_restart_torn_tail_no_wedge():
    """The original stochastic repro:
    TCP transport, failpoint crash/restart + crash/torn-tail/restart.
    Pre-fix this wedged on ~10-30% of attempts with the illegal
    `next <= match` progress state pinned for the rest of the run —
    which the on-device invariant sweep trips persistently, so the
    regression assertion is `invariant_trips() == 0` plus quorum-level
    hash parity. (STRICT parity is deliberately not asserted: torn
    tails tear fsync'd acked bytes, and a torn member that wins an
    election can force a survivor to overwrite an entry it already
    applied — an out-of-contract KV divergence no protocol heals;
    see run_invariant_checks.)"""
    from etcd_tpu.batched.faults import ChaosHarness, FaultSpec
    from etcd_tpu.functional import multiraft_hash_check

    spec = FaultSpec(drop=0.06, dup=0.06, delay=0.1,
                     delay_max_s=0.05, reorder=0.25)
    for seed in (424242, 424243, 424244):
        d = tempfile.mkdtemp(prefix="wedge-regress-")
        h = ChaosHarness(d, seed=seed, spec=spec, num_members=3,
                         num_groups=12, transport="tcp")
        try:
            h.wait_leaders()
            h.run_workload(15, prefix=b"vfy")
            h.crash_on_failpoint(2, "after_save")
            h.run_workload(6, prefix=b"mid", per_put_timeout=15.0)
            h.restart(2)
            h.wait_leaders()
            h.crash(3)
            h.torn_tail(3)
            h.restart(3)
            h.wait_leaders()
            h.touch_all_groups()
            h.plan.quiesce()
            try:
                multiraft_hash_check(h.alive(), timeout=60.0,
                                     allow_lag=1)
                trips = h.invariant_trips()
                assert trips == 0, (
                    f"seed {seed}: {trips} illegal-progress invariant "
                    "trips — the progress wedge is back")
            except AssertionError:
                h.dump_flight_recorders(reason="wedge-regression")
                raise
        finally:
            h.stop()
