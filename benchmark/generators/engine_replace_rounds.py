"""Generator ``engine_replace_rounds``: the closed-loop engine under
steady writes and ReadIndex reads while the nodes of a three-replica
store are replaced one at a time: four nodes, of which one is always
the empty spare; a fresh replica joins there as a learner, is carried
by a snapshot, catches up by appends and is swapped for the voter on
the next node in one joint change of two ops, whose machine is then
switched off and whose slot is reset: the next period's spare.

Stands beside ``engine_reconf_rounds.py`` and is not an edit of it
(``run`` and ``preload``, which know no schedule, are imported from
it): that generator drains a node that stays a member, and no replica
is born or retired in it. Here, in *every* round,
``proposals_per_round`` entries are offered to every replica (the
device appends on whoever leads) and, with ``reads``, one ReadIndex
request. Over that, a cycle of ``period_rounds`` rounds, counted from
the first round after settle. In period k the spare is node
e = (e0 + k) mod R with e0 from the seed, the node retired is
d = (e + 1) mod R, and n = (e + 2) mod R, m = (e + 3) mod R stay; the
rounds are the traffic file's:

* ``add_learner_round`` on: the simple change {AddLearnerNode e} is on
  offer;
* ``transfer_from_round`` on, until ``leave_round``: every leader on
  node d is asked to hand leadership to node n, and is not offered a
  change (``etcdctl move-leader`` before the machine goes);
* ``swap_round`` on: {JointExplicit, AddNode e, RemoveNode d} is on
  offer; a leader takes it only once its row for e is REPLICATE (the
  program's stand-in for etcd's ``isLearnerReady``);
* ``retire_from_round`` on, for the rest of the period: node d is
  switched off, cut off both ways, before the joint configuration is
  left;
* ``cut_from_round``, for ``cut_rounds``: node n is cut off both ways,
  for less than an election timeout. With d off and n away the
  outgoing half {d, n, m} has no majority while the incoming
  {n, m, e} has, so from the cut's round number
  ``stall_from_cut_round`` to its last no group in a joint
  configuration may commit: those rounds are marked;
* ``leave_round`` on, into the next period: LeaveJoint is on offer;
* ``wipe_round``: slot d of every group is reset to the empty replica.

An offer stands from its round until the next one's, round after round:
a leader takes it when it can, and one that no longer fits the group's
configuration is not taken again, so each is appended once. Every edge
falls inside a call of ``rounds_per_call`` rounds and none on a call's
first round. The window is whole periods (``engine_reconf_rounds.run``).
"""

from __future__ import annotations

from typing import List

import numpy as np

from .engine_reconf_rounds import preload, run  # noqa: F401

# The kinds of change a row offers (``row()["conf"]``: (kind, node,
# second node); the nodes None where the kind names none).
ADD_LEARNER, SWAP, LEAVE = "add_learner", "swap", "leave"
EDGES = ("add_learner_round", "transfer_from_round", "swap_round",
         "retire_from_round", "cut_from_round", "leave_round", "wipe_round")


def make(traffic: dict, sizes: dict, seed: int) -> dict:
    groups, r = int(sizes["num_groups"]), int(sizes["num_replicas"])
    rng = np.random.default_rng([seed, 0xE3401])
    spare = int(rng.integers(0, r))
    seated = np.asarray([s for s in range(r) if s != spare], np.int32)
    slots = seated[rng.integers(0, r - 1, size=groups)]
    rpc = int(traffic["rounds_per_call"])
    period = int(traffic["period_rounds"])
    if period % rpc:
        raise ValueError("period_rounds must be whole calls")
    if r < 4:
        raise ValueError("a replacement needs a spare slot beside three "
                         "voters: num_replicas >= 4")
    load = {
        "seed": seed,
        "leader_slots": slots,
        "first_spare_node": spare,
        "replicas": r,
        "proposals_per_round": int(traffic["proposals_per_round"]),
        "reads": bool(traffic["reads"]),
        "rounds_per_call": rpc,
        "tick": bool(traffic["tick"]),
        "period_rounds": period,
        "cut_rounds": int(traffic["cut_rounds"]),
        "stall_from_cut_round": int(traffic["stall_from_cut_round"]),
    }
    last = 0
    for name in EDGES:
        at = load[name] = int(traffic[name])
        if at % rpc == 0 or (at + load["cut_rounds"]) % rpc == 0:
            raise ValueError(f"{name} falls on a call's first round")
        if not last < at < period:
            raise ValueError(f"{name} is out of order or past the period")
        last = at
    if load["cut_from_round"] + load["cut_rounds"] > load["leave_round"]:
        raise ValueError("the cut must end before the joint configuration "
                         "is left")
    return load


def nodes(load: dict, period: int):
    """(e, d, n, m) of a period: the spare, the node retired, the
    transfers' target, the fourth."""
    r = load["replicas"]
    e = (load["first_spare_node"] + period) % r
    return e, (e + 1) % r, (e + 2) % r, (e + 3) % r


def row(load: dict, rnd: int) -> dict:
    """What round ``rnd`` (counted from the first after settle) asks:
    ``drained`` and ``transfer_to`` (nodes, or None), ``conf`` ((kind,
    node, second node) or None), ``cut``, ``retired`` and ``wipe``
    (nodes, or None), ``stall`` and ``reads``."""
    period, t = divmod(rnd, load["period_rounds"])
    e, d, n, _m = nodes(load, period)
    out = {"drained": None, "transfer_to": None, "conf": None, "cut": None,
           "retired": None, "wipe": None, "stall": False,
           "reads": load["reads"]}
    if load["transfer_from_round"] <= t < load["leave_round"]:
        out["drained"], out["transfer_to"] = d, n
    if t < load["add_learner_round"]:
        # The last period's LeaveJoint is still on offer (nothing is,
        # before the first period's learner).
        out["conf"] = (LEAVE, None, None) if period else None
    elif t < load["swap_round"]:
        out["conf"] = (ADD_LEARNER, e, None)
    elif t < load["leave_round"]:
        out["conf"] = (SWAP, e, d)
    else:
        out["conf"] = (LEAVE, None, None)
    if t >= load["retire_from_round"]:
        out["retired"] = d
    k = t - load["cut_from_round"]
    if 0 <= k < load["cut_rounds"]:
        out["cut"] = n
        out["stall"] = k >= load["stall_from_cut_round"]
    if t == load["wipe_round"]:
        out["wipe"] = d
    return out


def rows(load: dict, first_round: int, rounds: int) -> List[dict]:
    return [row(load, first_round + i) for i in range(rounds)]


def applies(load: dict, first_round: int, last_round: int) -> np.ndarray:
    """[R] configuration changes each node's replicas apply themselves
    in rounds [first_round, last_round), provided no change is applied
    within a few rounds of either end (the driver's windows begin and
    end on a call's first round, where no edge falls): in a period n
    and m apply three (the learner, the swap, LeaveJoint), e two (the
    swap and LeaveJoint: it has the learner from its snapshot) and d
    two (the learner and the swap: it is off when the joint
    configuration is left)."""
    out = np.zeros(load["replicas"], np.int64)
    period = load["period_rounds"]
    for k in range(first_round // period, -(-last_round // period)):
        e, d, n, m = nodes(load, k)
        for edge, who in (("add_learner_round", (d, n, m)),
                          ("swap_round", (e, d, n, m)),
                          ("leave_round", (e, n, m))):
            if first_round <= k * period + load[edge] < last_round:
                out[list(who)] += 1
    return out
