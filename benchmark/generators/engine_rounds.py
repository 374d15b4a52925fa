"""Generator ``engine_rounds``: the closed-loop engine under steady
proposals.

From the seed: which replica of each group leads (the campaign the
driver runs in set-up) and which groups the comparison samples. Every
leader then proposes ``proposals_per_round`` entries in every round; a
call is ``rounds_per_call`` rounds in one scan, fenced by
``block_until_ready``, so the host does nothing but wait. The window is
as many whole calls as ``seconds`` admits, the rate is all their
group-rounds over all their time.
"""

from __future__ import annotations

import statistics
import time

import numpy as np

from ..harness import say


def make(traffic: dict, sizes: dict, seed: int) -> dict:
    groups, r = int(sizes["num_groups"]), int(sizes["num_replicas"])
    rng = np.random.default_rng([seed, 0xE601])
    slots = rng.integers(0, r, size=groups).astype(np.int32)
    return {
        "seed": seed,
        "leader_slots": slots,
        "proposals_per_round": int(traffic["proposals_per_round"]),
        "rounds_per_call": int(traffic["rounds_per_call"]),
        "tick": bool(traffic["tick"]),
    }


def preload(target, load: dict, traffic: dict) -> None:
    """Nothing to load: the engine's log is its own."""


def run(target, load: dict, traffic: dict, seconds: float, probe) -> dict:
    rpc = load["rounds_per_call"]
    trace_calls = int(traffic.get("trace_calls", 2))
    call_s = []
    t0 = time.perf_counter()
    target.window_opens()
    while time.perf_counter() - t0 < seconds:
        t_call = time.perf_counter()
        target.call()
        call_s.append(time.perf_counter() - t_call)
    window_s = time.perf_counter() - t0
    target.window_closes()
    # The trace is taken after the window, over whole calls of the same
    # program: opening and closing it would otherwise fall between the
    # window's calls.
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
    }
    say("calls", n=len(call_s), median_s=med, min_s=min(call_s),
        max_s=max(call_s),
        rate_by_median=groups * rpc / med)
    return out
