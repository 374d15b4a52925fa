"""BASELINE.md benchmark configs #2-#5: the multi-Raft shard sweep.

    python -m etcd_tpu.tools.bench_sweep [--configs 2,3,4] [--quick]

#2  1k-shard,  3 replicas — leader append path (steady proposals)
#3  10k-shard, 5 replicas — commit index + Progress tracker on device
#4  100k-shard, 3 replicas — randomized elections + vote-tally kernel
#5  1M-shard,  3 replicas — JointConfig membership (half the groups run
    a joint config, commit = min of both quorum halves) + a ReadIndex
    batch opened on every leader each measured block, confirmed via
    heartbeat-ack quorum on device

Each config prints one JSON line naming the platform and whether the
sizes are the BASELINE ones or ``--quick``'s; nothing is shrunk unless
``--quick`` is given. Config #1 (raftexample 3-node single
group) is covered by the raftexample suite + demo, not this sweep.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp


def _steady_rate(groups: int, replicas: int, rounds: int, calls: int,
                 lanes_minor: bool) -> dict:
    from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

    cfg = BatchedConfig(
        num_groups=groups, num_replicas=replicas, window=32,
        max_ents_per_msg=4, max_props_per_round=2,
        election_timeout=1 << 20, heartbeat_timeout=4,
        auto_compact=True, lanes_minor=lanes_minor,
    )
    eng = MultiRaftEngine(cfg)
    eng.campaign([g * replicas for g in range(groups)])
    eng.run_rounds(4, tick=False)
    assert (eng.leaders() == 0).all()
    props = jnp.zeros((cfg.num_instances,), jnp.int32)
    props = props.at[jnp.arange(groups) * replicas].set(2)
    eng.run_rounds(rounds, tick=True, propose_n=props)
    jax.block_until_ready(eng.state.commit)
    t0 = time.perf_counter()
    for _ in range(calls):
        eng.run_rounds(rounds, tick=True, propose_n=props)
    jax.block_until_ready(eng.state.commit)
    dt = time.perf_counter() - t0
    assert eng.commits().min() > 0
    return {
        "groups": groups,
        "replicas": replicas,
        "group_rounds_per_sec": round(groups * rounds * calls / dt, 1),
    }


def _election_rate(groups: int, replicas: int, rounds: int, calls: int,
                   lanes_minor: bool) -> dict:
    """Config #4: randomized timer elections — every group keeps
    ticking with a short election timeout, continuously re-electing via
    the vote-tally kernel."""
    from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

    cfg = BatchedConfig(
        num_groups=groups, num_replicas=replicas, window=16,
        max_ents_per_msg=2, max_props_per_round=1,
        election_timeout=4, heartbeat_timeout=1,
        auto_compact=True, lanes_minor=lanes_minor,
    )
    eng = MultiRaftEngine(cfg)
    eng.run_rounds(rounds, tick=True)  # warmup: natural elections fire
    jax.block_until_ready(eng.state.term)
    t0 = time.perf_counter()
    for _ in range(calls):
        eng.run_rounds(rounds, tick=True)
    jax.block_until_ready(eng.state.term)
    dt = time.perf_counter() - t0
    terms = eng.terms()
    assert int(terms.max()) > 0, "no elections fired"
    return {
        "groups": groups,
        "replicas": replicas,
        "group_rounds_per_sec": round(groups * rounds * calls / dt, 1),
        "max_term_reached": int(terms.max()),
        "leaders_now": int((eng.leaders() >= 0).sum()),
    }


def _joint_readindex_rate(groups: int, replicas: int, rounds: int,
                          calls: int, lanes_minor: bool) -> dict:
    """Config #5: steady appends with half the groups in a joint
    config (commit takes both quorum halves) and a ReadIndex batch
    opened on every leader per measured block."""
    import numpy as np

    from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

    cfg = BatchedConfig(
        num_groups=groups, num_replicas=replicas, window=32,
        max_ents_per_msg=4, max_props_per_round=2,
        election_timeout=1 << 20, heartbeat_timeout=4,
        auto_compact=True, lanes_minor=lanes_minor,
    )
    eng = MultiRaftEngine(cfg)
    # Half the groups run joint {all} x {all-but-last} — a real two-
    # quorum commit rule (bulk mask upload, one device op).
    half = groups // 2
    st = eng.state
    vout = np.zeros((cfg.num_instances, replicas), bool)
    joint = np.zeros((cfg.num_instances,), bool)
    # Joint groups are exactly [0, half): two slice writes, no loop.
    vout[: half * replicas, : replicas - 1] = True
    joint[: half * replicas] = True
    eng.state = st._replace(
        voter_out=jnp.asarray(vout), in_joint=jnp.asarray(joint))

    eng.campaign([g * replicas for g in range(groups)])
    eng.run_rounds(4, tick=False)
    assert (eng.leaders() == 0).all()
    props = jnp.zeros((cfg.num_instances,), jnp.int32)
    props = props.at[jnp.arange(groups) * replicas].set(2)
    leader_rows = jnp.zeros((cfg.num_instances,), bool).at[
        jnp.arange(groups) * replicas].set(True)

    def block() -> None:
        # One ReadIndex batch per leader, then the steady rounds (the
        # acks confirm within them — read_only.go's heartbeat quorum).
        eng.step_round(read_req=leader_rows, propose_n=props)
        eng.run_rounds(rounds - 1, tick=True, propose_n=props)

    block()  # warmup/compile
    jax.block_until_ready(eng.state.commit)
    t0 = time.perf_counter()
    for _ in range(calls):
        block()
    jax.block_until_ready(eng.state.commit)
    dt = time.perf_counter() - t0
    seq, idx, ready = eng.read_states()
    lead_idx = [g * replicas for g in range(groups)]
    confirmed = int(sum(1 for i in lead_idx if ready[i]))
    assert eng.commits().min() > 0
    assert confirmed > 0, "no ReadIndex batch ever confirmed"
    return {
        "groups": groups,
        "replicas": replicas,
        "joint_groups": half,
        "group_rounds_per_sec": round(groups * rounds * calls / dt, 1),
        "read_batches_confirmed": confirmed,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="2,3,4,5")
    ap.add_argument("--quick", action="store_true",
                    help="small G (CI-sized run)")
    ap.add_argument("--lanes-minor", type=int, default=-1,
                    help="-1 auto (tpu: minor), 0 major, 1 minor")
    args = ap.parse_args()
    want = {int(c) for c in args.configs.split(",")}

    platform = jax.devices()[0].platform
    lm = (platform == "tpu" if args.lanes_minor < 0
          else bool(args.lanes_minor))
    q = args.quick

    runs = {
        2: ("append-path", lambda: _steady_rate(
            1024 if q else 1024, 3, 16, 4, lm)),
        3: ("commit+progress-R5", lambda: _steady_rate(
            2048 if q else 10240, 5, 16, 4, lm)),
        4: ("randomized-elections", lambda: _election_rate(
            4096 if q else 102400, 3, 16, 4, lm)),
        5: ("joint+readindex-scale", lambda: _joint_readindex_rate(
            16384 if q else 1048576, 3, 8, 2, lm)),
    }
    for c in sorted(want):
        name, fn = runs[c]
        res = fn()
        res.update({"config": c, "name": name, "platform": platform,
                    "quick": q,
                    "layout": "minor" if lm else "major"})
        print(json.dumps(res))


if __name__ == "__main__":
    main()
