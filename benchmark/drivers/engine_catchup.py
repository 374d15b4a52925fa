"""Driver ``engine_catchup``: ``engine_faults``' deployment with the log
at etcd's documented depth (``BatchedConfig.log_runs``: 5,120 entries
kept behind the applied index, appends of 64, 512 in flight), a node in
turn away for half a period and carried back by appends.

A subclass of ``drivers/engine_faults.Driver`` and not an edit of it:
that driver spells ``BatchedConfig``'s arguments out and knows neither
``max_inflight`` nor the run table; here the configuration's ``sizes``
are the constructor's keywords as they stand, so a program without the
field refuses them at once. The generator, its schedule, the settle,
the warm-up, ``call`` and the reference's stepping are the parent's.

``correct`` (every limit 0; ``check``), over the state read once at the
end of a period, 1,024 rounds after a heal:

* ``catchup_checks.group_checks`` over all groups (``fault_checks``'
  five, the committed prefixes compared through the run tables);
* ``fault_checks.window_checks``: every group committed in the window,
  the invariant bitmap of every instance over every round of the run is
  zero (``runs_passed_applied`` among it), elections were started and
  won in the window; ``catchup_checks.run_checks``: no snapshot sent and
  no peer in SNAPSHOT over every instance and round of the run;
* ``catchup_checks.level_checks``: the state read ``level_rounds``
  (128) after a heal, outside the window: every replica of the healed
  node within E of its group's commit and in REPLICATE on its leader's
  row;
* ``fault_checks.quiet_checks`` after one call more with nothing
  offered and no node cut;
* class equality over all groups in every field and the sampled groups
  (one of each class) against ``reference.shadow_faults.FaultsCluster``
  in state and in the term of every index from the floor to ``last``
  (``catchup_checks.engine_checks``).

``window_counters`` hands the readers (``readers/catchup.py``) the
engine's catch-up counts at the window's two marks, the replicas that
returned inside it (the schedule's heals x groups) and the depth of the
leaders' logs as the window closed.
"""

from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from ..catchup_checks import (engine_checks, group_checks, level_checks,
                              run_checks)
from ..compare import Check
from ..fault_checks import quiet_checks, window_checks
from ..harness import say
from . import engine_faults
from .engine import fence

LEADER = 2  # BatchedState.role
# Controls (``check(control=...)``; ``benchmark/control_faults.py`` runs
# them all, ``benchmark/control.py`` the second): the reference with the
# election cell's ring of 32 (it carries the returned node by snapshots
# and holds 16 entries; the program must not agree), and the parent's
# commit without a quorum.
CONTROLS = ("reference_window_32", engine_faults.CONTROLS[0])


class Driver(engine_faults.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        super().__init__(config, traffic, seed, workdir)
        self.level_rounds = int(traffic["level_rounds"])
        self.level: Optional[dict] = None  # read outside the window
        self.in_window = False
        self.reference_window: Optional[int] = None

    def setup(self, load, gen) -> None:
        import jax.numpy as jnp

        from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

        t0 = time.perf_counter()
        # The sizes are the constructor's keywords: a program that
        # lacks one of them (log_runs) raises here, before any device
        # work.
        self.eng = eng = MultiRaftEngine(BatchedConfig(**self.sizes))
        self.cfg = cfg = eng.cfg
        if not cfg.log_runs:
            raise RuntimeError("the deep-log cell needs log_runs")
        r = cfg.num_replicas
        self.load, self.gen = load, gen
        self.rpc = int(load["rounds_per_call"])
        self.tick = bool(load["tick"])
        if load["proposals_per_round"] > cfg.max_props_per_round:
            raise ValueError("proposals_per_round exceeds the config's P")
        heal = load["cut_from_round"] + load["cut_rounds"]
        if ((heal + self.level_rounds) % self.rpc
                or heal + self.level_rounds >= load["period_rounds"]):
            raise ValueError("level_rounds after the heal must end a call "
                             "inside the period")
        slots = load["leader_slots"]
        eng.campaign(np.arange(self.groups, dtype=np.int64) * r + slots)
        eng.run_rounds(self.rpc, tick=False,
                       isolate=np.zeros((self.rpc, r), bool))
        self.settle_rounds = self.rpc
        got = eng.leaders()
        if not (got == slots).all():
            raise RuntimeError(
                f"{int((got != slots).sum())} groups did not elect the "
                "replica the seed drew")
        self.props = jnp.full((cfg.num_instances,),
                              load["proposals_per_round"], jnp.int32)
        self.call()  # warm-up: the window's own program and arguments
        fence(eng)
        self._mark("open")
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            window=cfg.window, log_runs=cfg.log_runs,
            max_ents=cfg.max_ents_per_msg, max_inflight=cfg.max_inflight,
            first_cut_node=load["first_cut_node"],
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())

    # -- what is read beside the parent's marks -----------------------------------------

    def call(self) -> None:
        super().call()
        load = self.load
        t = self.rounds_done % load["period_rounds"]
        if (not self.in_window and t == load["cut_from_round"]
                + load["cut_rounds"] + self.level_rounds):
            self.level = dict(
                self.read_state(("role", "commit", "pr_state")),
                node=self.gen.cut_node(
                    load, self.rounds_done - self.level_rounds - 1))

    def _mark(self, name: str) -> None:
        super()._mark(name)
        self.marks[name]["catchup"] = self.eng.catchup_counts()
        self.marks[name]["round"] = self.rounds_done

    def window_opens(self) -> None:
        super().window_opens()
        self.in_window = True

    def window_closes(self) -> None:
        super().window_closes()
        self.in_window = False
        # The depth of the log, of the state as the window closes: the
        # entries each leader holds above its floor.
        st = self.read_state(("role", "last", "snap_index"))
        held = (st["last"] - st["snap_index"])[st["role"] == LEADER]
        self.marks["close"]["depth"] = (
            float(np.median(held)) if len(held) else 0.0)

    def heals(self, first_round: int, rounds: int) -> int:
        """Nodes the schedule heals in these rounds: cut off in the
        round before and not in the round."""
        cut = self.gen.cut_node
        return sum(
            1 for t in range(first_round, first_round + rounds)
            if t and cut(self.load, t - 1) is not None
            and cut(self.load, t) != cut(self.load, t - 1))

    def window_counters(self) -> dict:
        a, b = self.marks["open"], self.marks["close"]
        return dict(
            super().window_counters(),
            catchup={"before": a["catchup"], "after": b["catchup"]},
            replicas_returned=self.groups * self.heals(
                a["round"], b["round"] - a["round"]),
            log_depth_entries=b.get("depth"))

    # -- the comparison, outside the window ---------------------------------------------

    def finish(self) -> dict:
        self.drain()
        while self.level is None:  # on to the next heal and past it
            self.call()
        final = super().finish()  # drains to the period's end first
        from etcd_tpu.batched.telemetry import TM_NAMES

        counters, _inv = self.eng.telemetry()
        final["totals"] = dict(zip(
            TM_NAMES, counters.sum(axis=0, dtype=np.int64).tolist()))
        return final

    def reference(self, load, sample, control: Optional[str] = None):
        """The parent's (``reference.shadow_faults.FaultsCluster`` at
        the configuration's window, E and in-flight limit: nothing of
        the deep log differs in the plain reference, whose logs are
        lists); the control ``reference_window_32`` gives it the
        election cell's ring and is otherwise sound."""
        self.reference_window = None
        if control == CONTROLS[0]:
            self.reference_window, control = 32, None
        return super().reference(load, sample, control)

    def _step_reference(self, load, g, faults_cluster):
        window = self.reference_window

        def cluster(num_replicas, **kw):
            if window is not None:
                kw["window"] = window
            return faults_cluster(num_replicas, **kw)

        return super()._step_reference(load, g, cluster)

    def check(self, load, raw, control=None) -> List[Check]:
        if control is True:  # ``benchmark/control.py``'s one control
            control = CONTROLS[1]
        if self.final is None:
            self.final = self.finish()
        state, cfg = self.final["state"], self.cfg
        t0 = time.perf_counter()
        sample = self.sample(load)
        ref = self.reference(load, sample, control or None)
        say("reference", groups=len(sample), rounds=self.rounds_done,
            seconds=time.perf_counter() - t0, sample=sample)
        a, b = self.marks["open"], self.marks["close"]
        level = self.level
        return (
            group_checks(state, self.groups, cfg.num_replicas,
                         cfg.window // 2)
            + window_checks(a["commit"], b["commit"],
                            self.final["invariants"], a["counters"],
                            b["counters"],
                            need=("elections_started", "elections_won"))
            + run_checks(self.final["totals"])
            + level_checks(level, level["node"], self.groups,
                           cfg.num_replicas, cfg.max_ents_per_msg)
            + quiet_checks(self.final["quiet"], self.groups,
                           cfg.num_replicas)
            + engine_checks(
                state, self.groups, cfg.num_replicas, self.classes(load),
                sample, lambda g: ref[g].snapshot_state(),
                lambda g, s: ref[g].log_terms(s)))
