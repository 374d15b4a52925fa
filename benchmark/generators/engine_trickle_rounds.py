"""Generator ``engine_trickle_rounds``: the closed-loop engine under
steady writes and ReadIndex reads while a node is decommissioned range
by range: four nodes, every group's empty slot on the new node e, and a
rebalancer that moves the replicas on node d = e + 1 to e a few groups
at a time under etcd's snapshot budget (a member sends at most
``maxInFlightMsgSnap`` snapshots at a time, so a batch is
``sending_members`` x that many moves: the configuration's
``rebalance``).

Stands beside ``engine_replace_rounds.py`` and is not an edit of it
(``run`` and ``preload``, which know no schedule, are
``engine_reconf_rounds``'s as that file has them): there every group
replaces the same node in the same round, period after period, with a
node-wide cut in the middle. Here, in *every* round,
``proposals_per_round`` entries are offered to every replica and, with
``reads``, one ReadIndex request; and every ``batch_every_rounds``
rounds, from round 0 (counted from the first after settle) for
``schedule_rounds`` rounds, the rebalancer starts the next batch of
``batch_groups`` groups, drawn without replacement by the seed over the
whole id range (``starts``: a group's start round, NEVER for the groups
it does not reach; ``batches``: which groups it drew, batch by batch).
A group that has started runs one cycle of ``cycle_rounds`` rounds,
the replacement cell's with its edges where that cell has them
(``row``: a group's round ``rnd - starts[g]`` of it), and is steady
before and after. With e from the seed, d = e + 1 the node drained,
n = e + 2 the transfers' target and m = e + 3 for the whole run:

* ``add_learner_round`` on: {AddLearnerNode e} is on offer;
* ``transfer_from_round`` on, until ``leave_round``: the group's
  leader, if on d, is asked to hand leadership to n, and is not
  offered a change;
* ``swap_round`` on: {JointExplicit, AddNode e, RemoveNode d} is on
  offer; a leader takes it only once its row for e is REPLICATE;
* ``retire_from_round`` on, for the rest of the cycle: *that group's*
  replica on d is switched off, cut off both ways;
* ``leave_round`` on: LeaveJoint is on offer;
* ``wipe_round``: slot d of that group is reset to the empty replica.

No node is cut off as a whole (``cut_rounds`` 0: the blip is a node's,
not a group's). Every batch starts on a multiple of
``batch_every_rounds``, and every edge a count is held to where a call
ends (the learner, the hand-over, the swap, the machine switched off,
the wipe: all 8 past a multiple of 16) falls inside a call of
``rounds_per_call`` rounds and none on a call's first round, whatever
the batch. ``leave_round`` is the replacement cell's 96 and so falls
on a call's first round for one batch in four (ISSUE 42 gives both the
96 and the rule; the 96 is kept, and nothing is counted at it: a group
leaves its joint configuration two or three rounds after the offer,
thirty before its cycle ends). The window is whole cycles
(``engine_reconf_rounds.run``, which counts ``period_rounds``: the
cycle's length here).
"""

from __future__ import annotations

import numpy as np

from .engine_reconf_rounds import preload, run  # noqa: F401

ADD_LEARNER, SWAP, LEAVE = "add_learner", "swap", "leave"
EDGES = ("add_learner_round", "transfer_from_round", "swap_round",
         "retire_from_round", "leave_round", "wipe_round")
NEVER = np.iinfo(np.int32).max


def make(traffic: dict, sizes: dict, seed: int) -> dict:
    groups, r = int(sizes["num_groups"]), int(sizes["num_replicas"])
    if r < 4:
        raise ValueError("a move needs an empty slot beside three voters: "
                         "num_replicas >= 4")
    rng = np.random.default_rng([seed, 0xE4201])
    spare = int(rng.integers(0, r))
    seated = np.asarray([s for s in range(r) if s != spare], np.int32)
    slots = seated[rng.integers(0, r - 1, size=groups)]
    rpc = int(traffic["rounds_per_call"])
    cycle = int(traffic["cycle_rounds"])
    every = int(traffic["batch_every_rounds"])
    size = int(traffic["batch_groups"])
    if cycle % rpc:
        raise ValueError("cycle_rounds must be whole calls")
    if int(traffic["cut_rounds"]):
        raise ValueError("a trickle cuts no node off as a whole: "
                         "cut_rounds must be 0")
    if every <= 0 or size <= 0 or cycle % every:
        raise ValueError("batch_every_rounds must divide cycle_rounds and "
                         "batch_groups be at least 1")
    load = {
        "seed": seed,
        "leader_slots": slots,
        "first_spare_node": spare,
        "replicas": r,
        "proposals_per_round": int(traffic["proposals_per_round"]),
        "reads": bool(traffic["reads"]),
        "rounds_per_call": rpc,
        "tick": bool(traffic["tick"]),
        "period_rounds": cycle,
        "cycle_rounds": cycle,
        "batch_every_rounds": every,
        "batch_groups": size,
        "batches_in_flight": cycle // every,
    }
    last = 0
    for name in EDGES:
        at = load[name] = int(traffic[name])
        # Whatever batch it is of: batches start `every` rounds apart.
        if name != "leave_round" and any(
                (at + k * every) % rpc == 0 for k in range(rpc)):
            raise ValueError(f"{name} falls on a call's first round")
        if not last < at < cycle:
            raise ValueError(f"{name} is out of order or past the cycle")
        last = at
    # The rebalancer reaches at most half the groups (so that groups it
    # never starts remain, at any size), a batch every `every` rounds;
    # a deployment of fewer groups than two batches (the CPU tests'
    # tiny copies) gets one batch of half of them.
    if size > groups // 2:
        if size > groups:
            size = groups // 2
        else:
            raise ValueError("batch_groups is more than half the groups")
    n_batches = min(int(traffic["schedule_rounds"]) // every,
                    groups // 2 // size)
    order = np.random.default_rng([seed, 0xE4202]).permutation(groups)
    load["batches"] = [np.sort(order[i * size:(i + 1) * size])
                       for i in range(n_batches)]
    starts = np.full(groups, NEVER, np.int32)
    for i, batch in enumerate(load["batches"]):
        starts[batch] = i * every
    load["starts"] = starts
    return load


def nodes(load: dict):
    """(e, d, n, m): the node the replicas move to, the node drained,
    the transfers' target, the fourth; the same all through the run."""
    r = load["replicas"]
    e = load["first_spare_node"]
    return e, (e + 1) % r, (e + 2) % r, (e + 3) % r


def row(load: dict, k: int) -> dict:
    """What a group is asked in round ``k`` of its cycle (``rnd -
    starts[g]``); the steady row, reads alone, before its start and
    from the cycle's end on: ``drained`` and ``transfer_to`` (nodes, or
    None), ``conf`` ((kind, node, second node) or None), ``retired``
    and ``wipe`` (nodes, or None); ``cut`` None and ``stall`` False
    throughout, ``reads`` the traffic's."""
    out = {"drained": None, "transfer_to": None, "conf": None, "cut": None,
           "retired": None, "wipe": None, "stall": False,
           "reads": load["reads"]}
    if not 0 <= k < load["cycle_rounds"]:
        return out
    e, d, n, _m = nodes(load)
    if load["transfer_from_round"] <= k < load["leave_round"]:
        out["drained"], out["transfer_to"] = d, n
    if load["add_learner_round"] <= k < load["swap_round"]:
        out["conf"] = (ADD_LEARNER, e, None)
    elif load["swap_round"] <= k < load["leave_round"]:
        out["conf"] = (SWAP, e, d)
    elif k >= load["leave_round"]:
        out["conf"] = (LEAVE, None, None)
    if k >= load["retire_from_round"]:
        out["retired"] = d
    if k == load["wipe_round"]:
        out["wipe"] = d
    return out


def cycle(load: dict) -> list:
    """The cycle's rows, round 0 to its last."""
    return [row(load, k) for k in range(load["cycle_rounds"])]


def moves(load: dict, edge: str, rounds_done: int, slack: int = 0) -> int:
    """Groups whose cycle round ``load[edge]`` fell inside the first
    ``rounds_done`` rounds, with ``slack`` rounds more after it."""
    starts = load["starts"].astype(np.int64)
    return int((starts + load[edge] + slack < rounds_done).sum())
