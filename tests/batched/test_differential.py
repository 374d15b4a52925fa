"""Lockstep differential test: the batched device engine must reproduce
the reference-semantics oracle cluster state field-for-field after every
round, for schedules in the common envelope (explicit campaigns,
leader proposals, heartbeat ticks, full-instance partitions).

This is the batched-engine analog of the trace-parity suite: the oracle
(etcd_tpu.raft) is itself verified bit-for-bit against the reference's
testdata, so agreement here chains the batched engine to the reference.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.shadow import ShadowCluster

R = 3
ET = 1 << 20  # no timer elections inside the differential envelope


def make_pair(groups=2):
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
    shadows = [ShadowCluster(R, election_timeout=ET, heartbeat_timeout=1)
               for _ in range(groups)]
    return cfg, eng, shadows


def make_pair_r5():
    """Five replicas, on ``test_scan_faults.R5`` (the benchmark's
    ``engine10k-r5`` at 8 groups: W=32, E=4, P=2, heartbeat every 4
    ticks, auto-compacting ring; a program that file builds)."""
    from .test_scan_faults import R5

    eng = MultiRaftEngine(R5)
    cfg = eng.cfg
    shadows = [
        ShadowCluster(
            cfg.num_replicas, election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, group=g,
            auto_compact_window=cfg.window, max_ents=cfg.max_ents_per_msg,
            max_props=cfg.max_props_per_round)
        for g in range(cfg.num_groups)]
    return cfg, eng, shadows


PAIRS = {"r3": make_pair, "r5": make_pair_r5}


def device_state(eng, cfg):
    """[(term, role, lead, commit, last)] per instance."""
    t = np.asarray(eng.state.term)
    ro = np.asarray(eng.state.role)
    le = np.asarray(eng.state.lead)
    c = np.asarray(eng.state.commit)
    la = np.asarray(eng.state.last)
    return [
        tuple(int(x) for x in (t[i], ro[i], le[i], c[i], la[i]))
        for i in range(cfg.num_instances)
    ]


def device_log(eng, cfg, inst):
    st = eng.state
    si = int(st.snap_index[inst])
    last = int(st.last[inst])
    ring = np.asarray(st.log_term[inst])
    return [(i, int(ring[i % cfg.window])) for i in range(si + 1, last + 1)]


def run_lockstep(cfg, eng, shadows, schedule):
    """schedule: list of dicts with optional keys campaign (list of
    (group, slot)), propose (dict (group, slot) -> n), tick (bool),
    isolate (list of (group, slot)). Compares state after every round."""
    n, r = cfg.num_instances, cfg.num_replicas
    for rnd, step in enumerate(schedule):
        camp = np.zeros(n, bool)
        props = np.zeros(n, np.int32)
        iso = np.zeros(n, bool)
        per_group = {g: {"campaigns": [], "proposals": {}, "isolate": []}
                     for g in range(cfg.num_groups)}
        for g, s in step.get("campaign", []):
            camp[g * r + s] = True
            per_group[g]["campaigns"].append(s)
        for (g, s), k in step.get("propose", {}).items():
            props[g * r + s] = k
            per_group[g]["proposals"][s] = k
        for g, s in step.get("isolate", []):
            iso[g * r + s] = True
            per_group[g]["isolate"].append(s)
        tick = step.get("tick", False)

        eng.step_round(
            tick=tick,
            campaign_mask=jnp.asarray(camp),
            propose_n=jnp.asarray(props),
            isolate=jnp.asarray(iso),
        )
        for g, shadow in enumerate(shadows):
            shadow.round(
                campaigns=per_group[g]["campaigns"],
                proposals=per_group[g]["proposals"],
                tick=tick,
                isolate=per_group[g]["isolate"],
            )

        dev = device_state(eng, cfg)
        for g, shadow in enumerate(shadows):
            host = shadow.snapshot_state()
            for s in range(r):
                assert dev[g * r + s] == host[s], (
                    f"round {rnd} group {g} slot {s}: "
                    f"device {dev[g * r + s]} vs host {host[s]}"
                )
    # Final: full log-term comparison.
    for g, shadow in enumerate(shadows):
        for s in range(r):
            assert device_log(eng, cfg, g * r + s) == shadow.log_terms(s), (
                f"log mismatch group {g} slot {s}"
            )


@pytest.mark.parametrize("pair", ["r3", "r5"])
def test_election_and_replication_lockstep(pair):
    cfg, eng, shadows = PAIRS[pair]()
    p = cfg.max_props_per_round
    schedule = (
        [{"campaign": [(0, 0), (1, 2)]}]
        + [{} for _ in range(4)]
        + [{"propose": {(0, 0): 2, (1, 2): 1}}]
        + [{} for _ in range(3)]
        + [{"propose": {(0, 0): min(3, p)}}]
        + [{} for _ in range(3)]
        + [{"tick": True}] * cfg.heartbeat_timeout  # heartbeats fire
        + [{} for _ in range(3)]
    )
    run_lockstep(cfg, eng, shadows, schedule)
    # Sanity: everyone converged on the proposals.
    c = eng.commits()
    assert (c[0] == c[0][0]).all() and c[0][0] >= 3 + min(3, p)


@pytest.mark.parametrize("pair", ["r3", "r5"])
def test_partition_divergence_and_heal_lockstep(pair):
    """Old leader keeps appending while partitioned; majority side elects
    a new leader at a higher term; on heal the old leader's divergent
    tail is truncated via the reject-hint probe path
    (ref: raft.go:1109-1236). Group 0 carries the schedule; the
    others idle."""
    cfg, eng, shadows = PAIRS[pair]()
    p = cfg.max_props_per_round
    iso0 = [(0, 0)]
    schedule = (
        [{"campaign": [(0, 0)]}]
        + [{} for _ in range(4)]
        + [{"propose": {(0, 0): 2}}]
        + [{} for _ in range(3)]
        # Partition the leader; it appends 2 uncommitted entries.
        + [{"isolate": iso0, "propose": {(0, 0): 2}}]
        + [{"isolate": iso0} for _ in range(2)]
        # Majority side elects slot 1 at term 2 and commits new entries.
        # (One settling round between commit-advance and the next
        # proposal keeps the host inside the one-append-per-round
        # envelope the device's flag-coalescing implies.)
        + [{"isolate": iso0, "campaign": [(0, 1)]}]
        + [{"isolate": iso0} for _ in range(4)]
        + [{"isolate": iso0, "propose": {(0, 1): min(3, p)}}]
        + [{"isolate": iso0} for _ in range(4)]
        # Heal: heartbeat brings the old leader back; divergent tail is
        # replaced via reject-hint probing.
        + [{"tick": True}] * cfg.heartbeat_timeout
        + [{} for _ in range(6)]
    )
    run_lockstep(cfg, eng, shadows, schedule)
    st = device_state(eng, cfg)
    # All replicas agree; slot 1 leads at term 2.
    assert st[1][1] == 2 and st[1][0] == 2
    # commits equal
    assert len({st[s][3] for s in range(cfg.num_replicas)}) == 1
