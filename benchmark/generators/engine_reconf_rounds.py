"""Generator ``engine_reconf_rounds``: the closed-loop engine under
steady writes and ReadIndex reads while one node in turn is drained:
its leaderships moved off, its replicas demoted to learners through a
joint configuration and promoted back through another, with a short
cut of a second node while the second joint configuration stands.

Stands beside ``engine_faults_rounds.py`` and is not an edit of it:
that generator knows one kind of event, a node cut off. Here, in
*every* round, ``proposals_per_round`` entries are offered to every
replica (the device appends on whoever leads) and, with ``reads``, one
ReadIndex request (a leader opens a batch at its commit index when none
is in flight, else the request waits). Over that, a drain cycle of
``period_rounds`` rounds, counted from the first round after settle,
for node d = (d0 + period number) mod R with d0 from the seed and
n = (d + 1) mod R; the rounds are the traffic file's:

* ``transfer_from_round`` on, until the demotion is left: every leader
  on node d is asked to hand leadership to node n (``etcdctl
  move-leader`` before maintenance);
* ``demote_round`` on: the change {JointExplicit, AddLearnerNode d} is
  on offer to every replica not on node d (a leader still there is
  asked for the hand-over again and not for its own demotion);
* ``leave_demotion_round`` on: the empty change, LeaveJoint;
* ``promote_round`` on: {JointExplicit, AddNode d};
* ``cut_from_round``, for ``cut_rounds``: node n is cut off both ways,
  for less than an election timeout. With it away the incoming half
  {a, b, d} keeps a majority and the outgoing {a, b} does not, so from
  the cut's round number ``stall_from_cut_round`` to its last (once
  what was in flight has landed) no group in a joint configuration may
  commit: those rounds are marked;
* ``leave_promotion_round`` on, into the next period: LeaveJoint.

A change stays on offer from its round until the next one's, round
after round: a leader takes it when it can (not while the one before
is unapplied, not with a hand-over in flight) and an offer that no
longer fits the group's configuration is not taken again, so each is
appended once, late in a group that was busy and not never. Every edge
falls inside a call of ``rounds_per_call`` rounds and none on a call's
first round. The window is whole periods: calls go on until ``seconds``
have passed and their count is a multiple of the calls in a period, so
every run measures the same mix.

The ``raw`` keys are ``engine_rounds``'s, so every per-layer metric of
the engine cells reads here too; ``telemetry``, ``watch`` and the rest
of ``window_counters()`` are what the driver read as the window opened
and closed.
"""

from __future__ import annotations

import statistics
import time
from typing import List

import numpy as np

from ..harness import say

# The kinds of change a row offers (``row()["conf"]``: (kind, node)).
DEMOTE, LEAVE, PROMOTE = "demote", "leave", "promote"
EDGES = ("transfer_from_round", "demote_round", "leave_demotion_round",
         "promote_round", "cut_from_round", "leave_promotion_round")


def make(traffic: dict, sizes: dict, seed: int) -> dict:
    groups, r = int(sizes["num_groups"]), int(sizes["num_replicas"])
    rng = np.random.default_rng([seed, 0xE3201])
    slots = rng.integers(0, r, size=groups).astype(np.int32)
    rpc = int(traffic["rounds_per_call"])
    period = int(traffic["period_rounds"])
    if period % rpc:
        raise ValueError("period_rounds must be whole calls")
    load = {
        "seed": seed,
        "leader_slots": slots,
        "first_drained_node": int(rng.integers(0, r)),
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
    if load["cut_from_round"] + load["cut_rounds"] > (
            load["leave_promotion_round"]):
        raise ValueError("the cut must end inside the second joint "
                         "configuration")
    return load


def row(load: dict, rnd: int) -> dict:
    """What round ``rnd`` (counted from the first after settle) asks:
    ``drained`` and ``transfer_to`` (nodes, or None), ``conf`` ((kind,
    node), the node None for LEAVE, or None), ``cut`` (node or None),
    ``stall`` and ``reads``."""
    r = load["replicas"]
    period, t = divmod(rnd, load["period_rounds"])
    d = (load["first_drained_node"] + period) % r
    n = (d + 1) % r
    out = {"drained": None, "transfer_to": None, "conf": None, "cut": None,
           "stall": False, "reads": load["reads"]}
    if load["transfer_from_round"] <= t < load["leave_demotion_round"]:
        out["drained"], out["transfer_to"] = d, n
    if t < load["demote_round"]:
        # The last period's LeaveJoint is still on offer (nothing is,
        # before the first period's demotion).
        out["conf"] = (LEAVE, None) if period else None
    elif t < load["leave_demotion_round"]:
        out["conf"] = (DEMOTE, d)
    elif t < load["promote_round"]:
        out["conf"] = (LEAVE, None)
    elif t < load["leave_promotion_round"]:
        out["conf"] = (PROMOTE, d)
    else:
        out["conf"] = (LEAVE, None)
    k = t - load["cut_from_round"]
    if 0 <= k < load["cut_rounds"]:
        out["cut"] = n
        out["stall"] = k >= load["stall_from_cut_round"]
    return out


def rows(load: dict, first_round: int, rounds: int) -> List[dict]:
    return [row(load, first_round + i) for i in range(rounds)]


def preload(target, load: dict, traffic: dict) -> None:
    """Nothing to load: the engine's log is its own."""


def run(target, load: dict, traffic: dict, seconds: float, probe) -> dict:
    rpc = load["rounds_per_call"]
    trace_calls = int(traffic.get("trace_calls", 2))
    per_period = load["period_rounds"] // rpc
    call_s = []
    target.window_opens()
    t0 = time.perf_counter()
    while (time.perf_counter() - t0 < seconds or not call_s
           or len(call_s) % per_period):
        t_call = time.perf_counter()
        target.call()
        call_s.append(time.perf_counter() - t_call)
    window_s = time.perf_counter() - t0
    target.window_closes()
    # The trace is taken after the window, over ``trace_calls`` whole
    # calls of the same program, from the round the window ended at (a
    # period's first, since the window is whole periods): a whole
    # period only where the traffic file makes ``trace_calls`` x
    # ``rounds_per_call`` its ``period_rounds``; else the period's
    # first calls, and what the schedule does later in the period is
    # in the window and not in the trace (PERF.md section 4 names it
    # for each cell).
    traced = 0
    if probe.want:
        probe.start()
        for _ in range(trace_calls):
            target.call()
            traced += 1
        probe.stop()
        target.traced_closes()
    groups = target.groups
    rounds = rpc * len(call_s)
    med = statistics.median(call_s)
    out = {
        "window_s": window_s,
        "attempted": rounds,
        "failed": 0,
        "calls": len(call_s),
        "rounds": rounds,
        "periods": len(call_s) // per_period,
        "rounds_per_call": rpc,
        "traced_calls": traced,
        "group_rounds_per_s": groups * rounds / window_s,
        "call_s_median": med,
        "call_s_min": min(call_s),
        "call_s_max": max(call_s),
        "ms_per_round_median": med / rpc * 1e3,
        "call_s": call_s,
        "groups": groups,
        "replicas": load["replicas"],
        "proposals_per_round": load["proposals_per_round"],
        **target.window_counters(),
    }
    say("calls", n=len(call_s), median_s=med, min_s=min(call_s),
        max_s=max(call_s), rate_by_median=groups * rpc / med)
    return out
