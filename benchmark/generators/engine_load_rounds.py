"""Generator ``engine_load_rounds``: the closed-loop engine under YCSB's
core workload A over Zipfian keys, an *open* loop counted in rounds:
what a group is offered is a function of the round and not of what has
been committed.

Every live cell before this one offers every group the same thing in
the same round. Here the key space is hash-sharded over the groups, so
a group's share of the operations is the sum of its keys' (``popularity``
below), and in every round each group is offered its own updates and
asked its own reads, drawn on the device from (seed, round, group)
against the group's two thresholds (``MultiRaftEngine.run_rounds(load=
...)``). With ``ops`` = ``ops_per_group_round`` x groups operations a
round, a group's demand is ``lambda_u = ops x update_proportion x
popularity[g]`` updates and ``lambda_r`` reads a round, and the
arrival law the configuration states under ``assumed`` is

* updates: Binomial(P, min(1, lambda_u / P)), P the configuration's
  ``max_props_per_round``: P independent draws a round, each below
  ``update_thr[g]``; a group whose demand is P or more is offered P in
  every round (its threshold is one no draw can miss), and what it
  demands above P is the clients' to retry: said on the run's
  ``[bench:load]`` line (``over_capacity_share``), not modelled;
* reads: one request where some read arrived, probability
  ``1 - exp(-lambda_r)``: a leader serves every waiting read from one
  ReadIndex batch.

From the seed: which replica of each group leads (the campaign the
driver runs in set-up) and the draws' seed. The popularity table is the
deployment's and no seed's. ``run`` and ``preload`` are
``engine_reconf_rounds``'s as that file has them (the window is whole
calls: a "period" here is one call).
"""

from __future__ import annotations

import numpy as np

from .engine_reconf_rounds import preload, run  # noqa: F401

# A threshold no draw can miss (``etcd_tpu.batched.engine.LOAD_ALWAYS``;
# the yardstick's own copy of the rule is ``reference/shadow_load.py``).
ALWAYS = 0xFFFFFFFF
FNV_OFFSET, FNV_PRIME = 0xCBF29CE484222325, 0x100000001B3
CHUNK = 1 << 22  # records hashed at a time: 32 MB an array


def make(traffic: dict, sizes: dict, seed: int) -> dict:
    groups, r = int(sizes["num_groups"]), int(sizes["num_replicas"])
    rng = np.random.default_rng([seed, 0xE4701])
    slots = rng.integers(0, r, size=groups).astype(np.int32)
    rpc = int(traffic["rounds_per_call"])
    read, update = (float(traffic["read_proportion"]),
                    float(traffic["update_proportion"]))
    if abs(read + update - 1.0) > 1e-12 or min(read, update) < 0:
        raise ValueError("read_proportion and update_proportion must be "
                         "shares that sum to 1")
    per_group = float(traffic["ops_per_group_round"])
    if per_group <= 0:
        raise ValueError("ops_per_group_round must be positive")
    return {
        "seed": seed,
        "draw_seed": seed & ALWAYS,
        "leader_slots": slots,
        "replicas": r,
        "groups": groups,
        "ops_per_round": per_group * groups,
        "read_proportion": read,
        "update_proportion": update,
        # The most a group can be offered a round; what the readers of
        # the engine cells call by this name.
        "proposals_per_round": int(sizes["max_props_per_round"]),
        "rounds_per_call": rpc,
        "period_rounds": rpc,
        "tick": bool(traffic["tick"]),
    }


def fnv1a64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each value, as unsigned 64 bits:
    FNV-1a over the value's eight octets, lowest first."""
    val = values.astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= val & np.uint64(0xFF)
            h *= np.uint64(FNV_PRIME)
            val >>= np.uint64(8)
    return h


def popularity(groups: int, records_per_group: int, constant: float
               ) -> np.ndarray:
    """[groups] float64, summing to 1: the share of all operations that
    falls on each group. Record of popularity rank i (0 the hottest)
    has probability ``(i + 1) ** -constant / zeta`` and lives in group
    ``fnv1a64(i) mod groups``; in chunks, so that 2**26 records cost
    seconds and megabytes."""
    records = groups * int(records_per_group)
    out = np.zeros(groups, np.float64)
    for lo in range(0, records, CHUNK):
        rank = np.arange(lo, min(lo + CHUNK, records), dtype=np.uint64)
        weight = (rank + np.uint64(1)).astype(np.float64) ** -constant
        group = (fnv1a64(rank) % np.uint64(groups)).astype(np.int64)
        out += np.bincount(group, weights=weight, minlength=groups)
    return out / out.sum()


def lambdas(load: dict, pop: np.ndarray):
    """(updates, reads) demanded of each group a round."""
    ops = load["ops_per_round"] * pop
    return ops * load["update_proportion"], ops * load["read_proportion"]


def _threshold(p: np.ndarray) -> np.ndarray:
    """A probability as the uint32 a 32-bit draw has to be below; 1,
    and whatever rounds to it, as ALWAYS."""
    return np.where(p >= 1.0, ALWAYS, np.minimum(
        np.floor(p * 2.0 ** 32), ALWAYS)).astype(np.uint32)


def thresholds(load: dict, pop: np.ndarray):
    """(update_thr, read_thr) uint32 [groups] of the arrival law above."""
    lam_u, lam_r = lambdas(load, pop)
    p = load["proposals_per_round"]
    return (_threshold(np.minimum(lam_u / p, 1.0)),
            _threshold(-np.expm1(-lam_r)))


def summary(load: dict, pop: np.ndarray) -> dict:
    """What the table says of the deployment, for the run's
    ``[bench:load]`` line."""
    lam_u, lam_r = lambdas(load, pop)
    p = load["proposals_per_round"]
    per_draw = np.minimum(lam_u / p, 1.0)
    offered = 1.0 - (1.0 - per_draw) ** p
    asked = -np.expm1(-lam_r)
    return {
        "groups": len(pop),
        "ops_per_round": load["ops_per_round"],
        "hottest_group_share": float(pop.max()),
        "median_group_share": float(np.median(pop)),
        "saturated_groups": int((lam_u >= p).sum()),
        "groups_above_half_capacity": int((lam_u >= p / 2).sum()),
        "over_capacity_share": float(
            np.maximum(lam_u - p, 0).sum() / lam_u.sum()),
        "groups_offered_an_update_pct": float(100 * offered.mean()),
        "groups_asked_a_read_pct": float(100 * asked.mean()),
        "groups_active_pct": float(
            100 * (1.0 - (1.0 - offered) * (1.0 - asked)).mean()),
        "updates_offered_a_round": float((p * per_draw).sum()),
    }
