"""Deliver on adversarial schedules: contested elections, torn-tail
rejection and repair, ReadIndex confirmation.

The round's deliver folds each inbox lane once over the sender axis
(step.py _deliver_vectorized). Its order contract is what the shadow
oracle steps message by message, and test_differential.py holds the
program to it on the common envelope; THIS module drives the schedules
where the order inside a lane decides the outcome — split votes broken
by sender order, a reject hint against a divergent tail, acks
confirming a read — and compares the program with the oracle after
every round on every ``STATE_FIELDS`` field the oracle exposes. The
fields it does not expose (the read batch, the send flags) are held
between the program's two forms: each schedule runs once more with the
lane ``lax.cond``s as selects (``lane_skip=False``, what a mesh-sharded
member runs) beside the default, every field equal after every round.

Engine configs reuse test_differential.py's values (G=2/R=3/W=64/E=16/
P=4, ET=1<<20, unbounded inflight), so the round-step program here is
the one the lockstep suite compiles; its ``laneskip=0`` twin is this
module's own entry against ROUND_STEP_SHAPE_BUDGET.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.shadow import ShadowCluster
from etcd_tpu.batched.state import CANDIDATE, LEADER, PRECANDIDATE
from etcd_tpu.batched.step import make_step_round

R = 3
ET = 1 << 20

# Every protocol-visible field of BatchedState (send flags included:
# two programs must agree on what the NEXT round will emit, not just on
# the HardState face).
STATE_FIELDS = (
    "term", "vote", "role", "lead", "log_term", "snap_index",
    "snap_term", "last", "commit", "applied", "match", "next",
    "pr_state", "probe_sent", "pending_snapshot", "recent_active",
    "inflight", "votes", "read_seq", "read_index", "read_acks",
    "read_ready", "read_req_latch", "send_append", "send_heartbeat",
    "send_vote_req", "transferee", "transfer_sent",
)
# Of those, what the oracle's raft struct holds per replica, and per
# peer on the rows where it is live state: progress on a leader, the
# tally on a candidate.
ORACLE_SCALARS = ("term", "vote", "role", "lead", "snap_index", "last",
                  "commit")
ORACLE_PROGRESS = ("match", "next", "pr_state", "probe_sent",
                   "recent_active")


def make_engine(groups=2, lane_skip=True):
    cfg = BatchedConfig(
        num_groups=groups,
        num_replicas=R,
        window=64,
        max_ents_per_msg=16,
        max_props_per_round=4,
        election_timeout=ET,
        heartbeat_timeout=1,
        max_inflight=1 << 20,
    )
    eng = MultiRaftEngine(cfg)
    if not lane_skip:
        eng._step = make_step_round(eng.cfg, lane_skip=False)
    return eng


def assert_states_equal(engines, rnd, context):
    ref_name, ref = engines[0]
    for name, eng in engines[1:]:
        for f in STATE_FIELDS:
            a = np.asarray(getattr(ref.state, f))
            b = np.asarray(getattr(eng.state, f))
            assert (a == b).all(), (
                f"{context} round {rnd}: {name} diverges from "
                f"{ref_name} on {f}:\n{a}\nvs\n{b}")


def oracle_fields(shadows):
    """field -> per-instance values as the oracle holds them; None
    where the oracle's value is not live state (progress off a leader,
    votes off a candidate)."""
    out = {f: [] for f in ORACLE_SCALARS + ORACLE_PROGRESS
           + ("votes", "log_term")}
    for shadow in shadows:
        for slot, node in enumerate(shadow.nodes):
            r = node.raft
            role = int(r.state)
            first = r.raft_log.first_index()
            for f, v in zip(ORACLE_SCALARS, (
                    r.term, r.vote, role, r.lead, first - 1,
                    r.raft_log.last_index(), r.raft_log.committed)):
                out[f].append(v)
            prs = [r.prs.progress[s + 1] for s in range(R)]
            for f, vals in zip(ORACLE_PROGRESS, (
                    [p.match for p in prs], [p.next for p in prs],
                    [int(p.state) for p in prs],
                    [p.probe_sent for p in prs],
                    [p.recent_active for p in prs])):
                out[f].append(vals if role == LEADER else None)
            cast = r.prs.votes
            out["votes"].append(
                [int(cast[s + 1]) if s + 1 in cast else -1
                 for s in range(R)]
                if role in (CANDIDATE, PRECANDIDATE) else None)
            out["log_term"].append(shadow.log_terms(slot))
    return out


def assert_equals_oracle(eng, shadows, rnd, context):
    want = oracle_fields(shadows)
    w = eng.cfg.window
    for f, rows in want.items():
        dev = np.asarray(getattr(eng.state, f))
        for i, v in enumerate(rows):
            if v is None:
                continue
            if f == "log_term":
                got = [(idx, int(dev[i, idx % w])) for idx, _t in v]
            else:
                got = dev[i].tolist()
            assert got == v, (
                f"{context} round {rnd} instance {i}: program {f}="
                f"{got}, oracle {v}")


def run_schedule(schedule, context, lane_skip_twin=False):
    """Drive the schedule through the program and the oracle (or, with
    ``lane_skip_twin``, through the program's two forms) and compare
    after EVERY round. Returns the default engine."""
    eng = make_engine()
    cfg = eng.cfg
    twin = make_engine(lane_skip=False) if lane_skip_twin else None
    shadows = [] if lane_skip_twin else [
        ShadowCluster(R, election_timeout=ET, heartbeat_timeout=1)
        for _ in range(cfg.num_groups)]
    n = cfg.num_instances
    for rnd, step in enumerate(schedule):
        camp = np.zeros(n, bool)
        props = np.zeros(n, np.int32)
        iso = np.zeros(n, bool)
        read = np.zeros(n, bool)
        per_group = [dict(campaigns=[], proposals={}, isolate=[])
                     for _ in range(cfg.num_groups)]
        for g, s in step.get("campaign", []):
            camp[g * R + s] = True
            per_group[g]["campaigns"].append(s)
        for (g, s), k in step.get("propose", {}).items():
            props[g * R + s] = k
            per_group[g]["proposals"][s] = k
        for g, s in step.get("isolate", []):
            iso[g * R + s] = True
            per_group[g]["isolate"].append(s)
        # The oracle is handed no read: in these schedules a ReadIndex
        # batch, which rides the heartbeat lanes, changes none of the
        # fields it exposes.
        for g, s in step.get("read", []):
            read[g * R + s] = True
        tick = step.get("tick", False)
        for e in (eng, twin):
            if e is not None:
                e.step_round(
                    tick=tick,
                    campaign_mask=jnp.asarray(camp),
                    propose_n=jnp.asarray(props),
                    isolate=jnp.asarray(iso),
                    read_req=jnp.asarray(read),
                )
        for shadow, kw in zip(shadows, per_group):
            shadow.round(tick=tick, **kw)
        if lane_skip_twin:
            assert_states_equal(
                [("conds", eng), ("selects", twin)], rnd, context)
        else:
            assert_equals_oracle(eng, shadows, rnd, context)
    return eng


def contested_elections():
    """All three replicas campaign in the same round (guaranteed split
    vote), then staggered re-campaigns contest the follow-up term: the
    vote lane's winner and the tally fold must grant and reject as the
    oracle does one message at a time."""
    return (
        [{"campaign": [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (1, 2)]}]
        + [{} for _ in range(3)]
        # Two-way contest at the next term; sender-order tie-breaks.
        + [{"campaign": [(0, 1), (0, 2), (1, 0), (1, 2)]}]
        + [{} for _ in range(4)]
        # A clean winner, then load.
        + [{"campaign": [(0, 0), (1, 2)]}]
        + [{} for _ in range(4)]
        + [{"propose": {(0, 0): 3, (1, 2): 2}}]
        + [{} for _ in range(4)]
    )


def torn_tail_repair():
    """Partitioned leader appends a divergent tail; the new leader's
    probe is rejected with a hint and the tail truncated on heal: the
    reject/repair column fold (incl. the stale-high match repair
    masks)."""
    iso = [(0, 0)]
    return (
        [{"campaign": [(0, 0)]}]
        + [{} for _ in range(4)]
        + [{"propose": {(0, 0): 2}}]
        + [{} for _ in range(3)]
        + [{"isolate": iso, "propose": {(0, 0): 3}}]
        + [{"isolate": iso} for _ in range(2)]
        + [{"isolate": iso, "campaign": [(0, 1)]}]
        + [{"isolate": iso} for _ in range(4)]
        + [{"isolate": iso, "propose": {(0, 1): 2}}]
        + [{"isolate": iso} for _ in range(4)]
        + [{"tick": True}]
        + [{} for _ in range(6)]
    )


def readindex_confirmation():
    """ReadIndex batches confirm via ctx-echoing heartbeat acks: the
    hb-resp lane's quorum over the acks of one round."""
    return (
        [{"campaign": [(0, 0), (1, 1)]}]
        + [{} for _ in range(4)]
        + [{"propose": {(0, 0): 2, (1, 1): 1}}]
        + [{} for _ in range(3)]
        + [{"read": [(0, 0), (1, 1)]}]
        + [{} for _ in range(4)]
        # Re-open a second batch while acks for nothing are pending.
        + [{"read": [(0, 0)]}]
        + [{} for _ in range(4)]
    )


def elected(eng):
    # The last campaign round must actually have elected leaders.
    assert (eng.leaders() >= 0).all()


def repaired(eng):
    c = eng.commits()
    assert (c[0] == c[0][0]).all() and c[0][0] >= 4


def confirmed(eng):
    seq, idx, ready = eng.read_states()
    assert ready[0] and idx[0] >= 0
    assert seq[0] == 2 and seq[R + 1] == 1


def test_contested_elections_agree():
    elected(run_schedule(contested_elections(), "contested elections"))


def test_torn_tail_rejection_repair_agree():
    repaired(run_schedule(torn_tail_repair(), "torn-tail repair"))


def test_readindex_confirmation_agrees():
    confirmed(run_schedule(readindex_confirmation(), "readindex"))


@pytest.mark.parametrize("schedule,outcome", [
    (contested_elections, elected),
    (torn_tail_repair, repaired),
    (readindex_confirmation, confirmed),
], ids=["contested_elections", "torn_tail_repair", "readindex"])
def test_lane_conds_as_selects_equal_the_conds(schedule, outcome):
    """The one fork left in deliver: with ``lane_skip`` each lane's fold
    sits under a ``lax.cond`` on the batch's occupancy; without it (a
    mesh-sharded member, rawnode.py) the predicate is per instance and
    the cond is a select. An unoccupied lane is an identity, so the two
    must agree on every field, the read batch and send flags too."""
    outcome(run_schedule(schedule(), schedule.__name__,
                         lane_skip_twin=True))


def test_vectorized_pipelined_matches_serial():
    """The pipelined closed loop (donated buffers, chunked scans) must
    equal serial single-round stepping — the frontier-sweep gate,
    pinned as a test."""
    a = make_engine()
    b = make_engine()
    n = a.cfg.num_instances
    camp = np.zeros(n, bool)
    camp[[0, R]] = True
    for eng in (a, b):
        eng.step_round(campaign_mask=jnp.asarray(camp))
    props = jnp.zeros((n,), jnp.int32).at[jnp.asarray([0, R])].set(2)
    a.run_rounds_pipelined(24, chunk=6, tick=True, propose_n=props)
    for _ in range(24):
        b.step_round(tick=True, propose_n=props)
    assert_states_equal([("serial", b), ("pipelined", a)], 24,
                        "pipelined vs serial")
    assert a.commits().min() > 0


def test_hosted_narrow_message_staging():
    """cfg.narrow_lanes now covers the message path (ISSUE 14
    satellite): the hosted staging buffers build int8 wire types /
    int16 entry counts (rawnode._build_inbox), the kernel widens at
    deliver entry, and pack_outbox widens before shifting bytes. A
    three-member hosted exchange (campaign → replicate → commit)
    proves the dtype contract end to end."""
    from etcd_tpu.batched.rawnode import BatchedRawNode

    g = 4
    cfg = BatchedConfig(
        num_groups=g, num_replicas=R, window=16, max_ents_per_msg=4,
        max_props_per_round=2, election_timeout=1 << 20,
        heartbeat_timeout=1, narrow_lanes=True,
        deliver_shape="vectorized",
    )
    rns = {
        mid: BatchedRawNode(
            cfg,
            groups=np.arange(g, dtype=np.int32),
            slots=np.full(g, mid - 1, np.int32),
        )
        for mid in (1, 2, 3)
    }
    with rns[1]._lock:
        inbox = rns[1]._build_inbox()
    assert np.asarray(inbox.type).dtype == np.int8
    assert np.asarray(inbox.n_ents).dtype == np.int16
    assert np.asarray(inbox.term).dtype == np.int32

    def pump(rounds):
        for _ in range(rounds):
            for mid, rn in rns.items():
                rd = rn.advance_round()
                blk = rd.msg_block
                if blk is not None and len(blk):
                    for to, sub in sorted(
                            blk.split_by_target().items()):
                        rns[to].step_block(sub)
                for row, m in rd.messages:
                    rns[m.to].step(row, m)
                rn.advance()

    rns[1].campaign(list(range(g)))
    pump(4)
    for row in range(g):
        rns[1].propose(row, b"narrow-%d" % row)
    pump(6)
    commits = np.asarray(rns[1].state.commit)
    assert (commits >= 2).all(), commits
    # Round-tripped state keeps the narrow storage dtypes.
    assert np.asarray(rns[1].state.role).dtype == np.int8
