"""Generator ``engine_faults_rounds``: the closed-loop engine under
steady proposals while one node in turn is cut off and healed.

Stands beside ``engine_rounds.py`` and is not an edit of it: that
generator proposes on a leader slot fixed for the run and knows no
fault. Here ``proposals_per_round`` entries are offered to *every*
replica in every round (the device appends on whoever leads), and the
rounds after settle follow a fault schedule made from the seed: in each
period of ``period_rounds`` rounds, node k (slot k of every group) is
cut off both ways for ``cut_rounds`` rounds from round
``cut_from_round``, with k = (k0 + period number) mod R and k0 from the
seed. Never two nodes at once, so a quorum is always there. Both edges
fall inside a scan: a call is ``rounds_per_call`` rounds of one
program, fenced. The window is as many whole calls as ``seconds``
admits and never fewer than one whole period, so no window closes
without an outage in it.

The ``raw`` keys are ``engine_rounds``'s, so every per-layer metric of
the engine cells reads here too; ``telemetry`` and ``commits`` are what
the driver snapshot as the window opened and closed.
"""

from __future__ import annotations

import statistics
import time
from typing import Optional

import numpy as np

from ..harness import say


def make(traffic: dict, sizes: dict, seed: int) -> dict:
    groups, r = int(sizes["num_groups"]), int(sizes["num_replicas"])
    rng = np.random.default_rng([seed, 0xE701])
    slots = rng.integers(0, r, size=groups).astype(np.int32)
    rpc = int(traffic["rounds_per_call"])
    period = int(traffic["period_rounds"])
    if period % rpc:
        raise ValueError("period_rounds must be whole calls")
    return {
        "seed": seed,
        "leader_slots": slots,
        "first_cut_node": int(rng.integers(0, r)),
        "replicas": r,
        "proposals_per_round": int(traffic["proposals_per_round"]),
        "rounds_per_call": rpc,
        "tick": bool(traffic["tick"]),
        "period_rounds": period,
        "cut_from_round": int(traffic["cut_from_round"]),
        "cut_rounds": int(traffic["cut_rounds"]),
    }


def cut_node(load: dict, rnd: int) -> Optional[int]:
    """The node cut off in round ``rnd`` (counted from the first round
    after settle), or ``None``."""
    period, t = divmod(rnd, load["period_rounds"])
    lo = load["cut_from_round"]
    if lo <= t < lo + load["cut_rounds"]:
        return (load["first_cut_node"] + period) % load["replicas"]
    return None


def schedule(load: dict, first_round: int, rounds: int) -> np.ndarray:
    """bool [rounds, R]: the engine's ``isolate`` rows of these rounds."""
    out = np.zeros((rounds, load["replicas"]), bool)
    for i in range(rounds):
        k = cut_node(load, first_round + i)
        if k is not None:
            out[i, k] = True
    return out


def preload(target, load: dict, traffic: dict) -> None:
    """Nothing to load: the engine's log is its own."""


def run(target, load: dict, traffic: dict, seconds: float, probe) -> dict:
    rpc = load["rounds_per_call"]
    trace_calls = int(traffic.get("trace_calls", 2))
    min_calls = load["period_rounds"] // rpc
    call_s = []
    target.window_opens()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds or len(call_s) < min_calls:
        t_call = time.perf_counter()
        target.call()
        call_s.append(time.perf_counter() - t_call)
    window_s = time.perf_counter() - t0
    target.window_closes()
    # The trace is taken after the window, over ``trace_calls`` whole
    # calls of the same program, from the round the window ended at
    # (whole calls, not whole periods: which rounds of the period those
    # are differs from run to run). A whole period only where the
    # traffic file makes ``trace_calls`` x ``rounds_per_call`` its
    # ``period_rounds``; else the trace holds those calls' rounds and
    # the window the rest (PERF.md section 4 says it for each cell).
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
        "rounds_per_call": rpc,
        "traced_calls": traced,
        "group_rounds_per_s": groups * rounds / window_s,
        "call_s_median": med,
        "call_s_min": min(call_s),
        "call_s_max": max(call_s),
        "ms_per_round_median": med / rpc * 1e3,
        "call_s": call_s,
        "groups": groups,
        "proposals_per_round": load["proposals_per_round"],
        **target.window_counters(),
    }
    say("calls", n=len(call_s), median_s=med, min_s=min(call_s),
        max_s=max(call_s), rate_by_median=groups * rpc / med)
    return out
