#!/usr/bin/env python
"""Fused-round smoke: a tiny-G liveness run of the round under the
transfer guard.

One engine drives a schedule — contested election, steady proposals, a
partition round, a ReadIndex batch — with every warm dispatch inside
``ETCD_TPU_TRANSFER_GUARD=disallow``. Commits must have advanced, the
contested election must have seated the leader the delivery order
picks, and the ReadIndex batch must have confirmed. This is the
check.sh/CI face; the round is held to the oracle field by field in
tests/batched/test_deliver_shapes.py and test_differential.py.

    python tools/fused_smoke.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("ETCD_TPU_TRANSFER_GUARD", "disallow")

G, R = 4, 3


def main() -> int:
    import jax.numpy as jnp
    import numpy as np

    from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

    eng = MultiRaftEngine(BatchedConfig(
        num_groups=G, num_replicas=R, window=32,
        max_ents_per_msg=4, max_props_per_round=2,
        election_timeout=1 << 20, heartbeat_timeout=1,
    ))

    n = G * R
    camp = np.zeros(n, bool)
    camp[[g * R + g % R for g in range(G)]] = True
    # Contested re-election in group 0: BOTH followers campaign in the
    # same round (split self-votes; the shared voter breaks the tie by
    # sender order).
    camp2 = np.zeros(n, bool)
    camp2[[1, 2]] = True
    props = jnp.zeros((n,), jnp.int32)
    props = props.at[jnp.asarray([g * R + g % R for g in range(G)])].set(2)
    iso = np.zeros(n, bool)
    iso[0] = True
    read = np.zeros(n, bool)
    read[[g * R + g % R for g in range(G)]] = True

    eng.step_round(campaign_mask=jnp.asarray(camp))
    for _ in range(3):
        eng.step_round()
    eng.step_round(propose_n=props)
    for _ in range(2):
        eng.step_round()
    eng.step_round(campaign_mask=jnp.asarray(camp2))
    for _ in range(3):
        eng.step_round()
    eng.step_round(propose_n=props, isolate=jnp.asarray(iso))
    for _ in range(2):
        eng.step_round()
    eng.step_round(read_req=jnp.asarray(read))
    for _ in range(3):
        eng.step_round()

    commits = eng.commits()
    assert commits.min() >= 2, commits
    # Group 0's contested re-election must have produced a new leader
    # at a higher term (sender-order tie-break: slot 1).
    role = np.asarray(eng.state.role)
    assert role[1] == 2 and np.asarray(eng.state.term)[1] >= 2, role[:3]
    _seq, idx, ready = eng.read_states()
    # Groups 1.. kept their seeded leaders (group 0's read lands on a
    # deposed row and is a no-op).
    lead_rows = [g * R + g % R for g in range(1, G)]
    assert all(ready[i] for i in lead_rows), ready
    assert all(idx[i] >= 0 for i in lead_rows)

    print(json.dumps({
        "fused_smoke": "ok",
        "deliver": eng.cfg.deliver_shape,
        "groups": G,
        "commit_min": int(commits.min()),
        "transfer_guard": os.environ["ETCD_TPU_TRANSFER_GUARD"],
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
