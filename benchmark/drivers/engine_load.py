"""Driver ``engine_load``: ``drivers/engine_reconf.py``'s deployment with
every group offered its own updates and reads. The closed loop is
handed a *load plane* (``MultiRaftEngine.run_rounds(load=(update_thr,
read_thr, seed))``): two thresholds a group, from the generator
(``engine_load_rounds``: YCSB A's Zipfian keys hash-sharded over the
groups) and the configuration's ``dataset``, and the draws' seed; what
each group is offered in each round is drawn on the device. No node is
drained, cut off or reconfigured: most groups only heartbeat.

Stands beside ``engine_reconf.py`` and is not an edit of it (its
``Driver`` is the base class here: the marks, the state read back, the
sampled comparison's last part). What differs: a program whose
``run_rounds`` takes no load plane is refused as the driver is made,
before anything is built; ``setup`` builds the popularity table (span
``load.popularity``) and settles through the window's own program with
thresholds no draw can meet; a call hands the engine the same plane
every time, and the engine's own round count says where in the draws it
is; the reference is ``reference.shadow_load``: the draws replayed in
plain numpy over every group, and ``LoadCluster``, one group stepped on
its own offers.

``correct`` (every limit 0; ``check``), after one closing call with
nothing offered, on the state read once (after ``memory_peak_bytes``):

* ``fault_checks.group_checks`` over all groups;
* ``load_checks.conservation_checks`` over all groups, in exact
  integers: every replica holds its group's whole log and has committed
  it; the log grew since the load began by R x the updates the
  reference's replay offered the group, less the telemetry plane's
  ``proposals_dropped`` over the group's rows, plus its
  ``elections_won``; a group offered nothing appended nothing and kept
  its leader;
* ``load_checks.count_checks``: ``eng.load_counts()`` equals the
  replay's totals;
* ``load_checks.run_checks`` over every instance and every round: the
  invariant bitmap zero, no snapshot sent, no election started, no read
  confirmed below an earlier commit of its group;
* ``load_checks.window_checks``: the window offered, asked, committed
  and confirmed something;
* the sampled groups (``sample``: by popularity rank, the hottest, those
  about the median and the coldest, a third each of
  ``shadow_groups``) against the reference in state, log, masks, read
  state and history.

The per-layer entries ``load.*`` read what ``window_counters`` hands
the generator's ``raw``.
"""

from __future__ import annotations

import inspect
import time
from typing import List, Optional

import numpy as np

from ..compare import Check
from ..fault_checks import group_checks
from ..harness import say
from ..load_checks import (conservation_checks, count_checks, run_checks,
                           sampled_engine_checks, window_checks)
from ..reconf_checks import sample_checks
from . import engine_reconf
from .engine import fence

# Controls (``check(control=...)``): each steps the reference on other
# offers than the program drew; the comparison then has to fail. The
# draws a round late: every group is offered in round t what it was to
# be offered in round t - 1. Uniform popularity: every group's
# thresholds those of 1 / groups of the operations, at the same
# ``ops_per_group_round``.
CONTROLS = ("draws_a_round_late", "uniform_popularity")


def takes_a_load_plane() -> bool:
    from etcd_tpu.batched import MultiRaftEngine

    return "load" in inspect.signature(
        MultiRaftEngine.run_rounds).parameters


class Driver(engine_reconf.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        if not takes_a_load_plane():
            raise RuntimeError(
                "this program's MultiRaftEngine.run_rounds takes no load "
                "plane (load=): it cannot offer each group its own updates "
                "and reads")
        super().__init__(config, traffic, seed, workdir)
        self.quiet_rounds = 0  # after the timed rounds, nothing offered

    def setup(self, load, gen) -> None:
        from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
        from etcd_tpu.batched.telemetry import TM_INDEX
        from etcd_tpu.obs import spans

        s = self.sizes
        cfg = BatchedConfig(
            num_groups=self.groups,
            num_replicas=int(s["num_replicas"]),
            window=int(s["window"]),
            max_ents_per_msg=int(s["max_ents_per_msg"]),
            max_props_per_round=int(s["max_props_per_round"]),
            election_timeout=int(s["election_timeout"]),
            heartbeat_timeout=int(s["heartbeat_timeout"]),
            pre_vote=bool(s["pre_vote"]),
            check_quorum=bool(s["check_quorum"]),
            auto_compact=bool(s["auto_compact"]),
            lanes_minor=bool(s["lanes_minor"]),
            deliver_shape=s["deliver_shape"],
            telemetry=bool(s["telemetry"]),
            conf_entries=bool(s["conf_entries"]),
        )
        t0 = time.perf_counter()
        self.eng = eng = MultiRaftEngine(cfg)
        self.cfg = cfg = eng.cfg
        g_n, r = self.groups, cfg.num_replicas
        self.load, self.gen = load, gen
        self.rpc = int(load["rounds_per_call"])
        self.tick = bool(load["tick"])
        data = self.config["dataset"]
        with spans.span("load.popularity", groups=g_n):
            t_pop = time.perf_counter()
            self.popularity = gen.popularity(
                g_n, int(data["records_per_group"]),
                float(data["zipfian_constant"]))
            pop_s = time.perf_counter() - t_pop
        self.thr = gen.thresholds(load, self.popularity)
        say("load", popularity_s=pop_s, **gen.summary(load, self.popularity))
        slots = load["leader_slots"]
        eng.campaign(np.arange(g_n, dtype=np.int64) * r + slots)
        # Settle with the timers off and nothing offered, through the
        # window's own program: thresholds no draw can meet. The engine
        # counts its load rounds from its first, so the timed rounds'
        # draws begin at round `settle_rounds`.
        nothing = np.zeros(g_n, np.uint32)
        self.nothing = (nothing, nothing, load["draw_seed"])
        self.plane = (*self.thr, load["draw_seed"])
        eng.run_rounds(self.rpc, tick=False, load=self.nothing)
        self.settle_rounds = self.rpc
        got = eng.leaders()
        if not (got == slots).all():
            raise RuntimeError(
                f"{int((got != slots).sum())} groups did not elect the "
                "replica the seed drew")
        # Where the load begins: what the conservation law counts from.
        counters, _inv = eng.telemetry()
        self.before = {
            "last": np.asarray(eng.state.last).reshape(g_n, r).max(
                axis=1).astype(np.int64),
            "counters": counters.sum(axis=0, dtype=np.int64),
            **{name: counters[:, TM_INDEX[name]].reshape(g_n, r).sum(
                axis=1, dtype=np.int64)
               for name in ("proposals_dropped", "elections_won")}}
        self.call()  # warm-up: the window's own program and arguments
        fence(eng)
        self._mark("open")
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            tiles=eng._tiles,
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())

    def call(self) -> None:
        """One scan of ``rounds_per_call`` rounds of the load plane,
        fenced."""
        self.eng.run_rounds(self.rpc, tick=self.tick, load=self.plane)
        fence(self.eng)
        self.calls += 1
        self.rounds_done += self.rpc

    # -- the counters, as the window opens and closes -----------------------------------

    def _mark(self, name: str) -> None:
        """The base's, the load plane's counts, and the entries that
        stand appended and uncommitted (each group's last index less
        its highest commit)."""
        super()._mark(name)
        mark = self.marks[name]
        last = np.asarray(self.eng.state.last).reshape(
            self.groups, self.cfg.num_replicas).max(axis=1)
        mark["load"] = self.eng.load_counts()
        mark["uncommitted"] = int(
            (last.astype(np.int64) - mark["commit"]).sum())

    def window_counters(self) -> dict:
        a, b = self.marks["open"], self.marks["close"]
        moved = lambda name: (  # noqa: E731
            b["counters"][name] - a["counters"][name])
        return dict(super().window_counters(), load={
            **{k: b["load"][k] - a["load"][k] for k in b["load"]},
            "dropped": moved("proposals_dropped"),
            "unoffered_committed": moved("elections_won"),
            "uncommitted_open": a["uncommitted"]})

    # -- the comparison, outside the window -------------------------------------------

    def drain(self) -> None:
        """One closing call with nothing offered: what is in flight
        lands, and every replica ends level and committed."""
        if not self.quiet_rounds:
            self.eng.run_rounds(self.rpc, tick=self.tick, load=self.nothing)
            fence(self.eng)
            self.quiet_rounds = self.rpc

    def finish(self) -> dict:
        from etcd_tpu.batched.telemetry import TM_INDEX

        final = super().finish()
        counters, _inv = self.eng.telemetry()
        g_n, r = self.groups, self.cfg.num_replicas
        for name in ("proposals_dropped", "elections_won"):
            final[name] = counters[:, TM_INDEX[name]].reshape(g_n, r).sum(
                axis=1, dtype=np.int64) - self.before[name]
        final["load"] = self.eng.load_counts()
        return final

    def _planes(self, load, control: Optional[str]):
        """(thresholds, rounds the draws are late by) the reference is
        stepped on."""
        if control not in (None,) + CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        thr = self.thr
        if control == CONTROLS[1]:
            thr = self.gen.thresholds(
                load, np.full(self.groups, 1.0 / self.groups))
        return thr, int(control == CONTROLS[0])

    def reference(self, load, sample, control: Optional[str] = None):
        """The plain reference of the sampled groups, each stepped on
        its own offers through the rounds the engine ran."""
        from ..reference.raft.logger import DefaultLogger, set_logger
        from ..reference.shadow_load import LoadCluster, group_offers

        set_logger(DefaultLogger(level=2))
        (upd, rd), late = self._planes(load, control)
        self.derailed = []
        cfg = self.cfg
        out = {}
        for g in sample:
            sh = LoadCluster(
                cfg.num_replicas, window=cfg.window,
                max_ents=cfg.max_ents_per_msg,
                max_props=cfg.max_props_per_round,
                election_timeout=cfg.election_timeout,
                heartbeat_timeout=cfg.heartbeat_timeout,
                max_inflight=cfg.max_inflight, pre_vote=cfg.pre_vote,
                group=int(g), deterministic_timeouts=True,
                deliver_shape=cfg.deliver_shape)
            sh.round(campaigns=[int(load["leader_slots"][g])])
            for _ in range(self.settle_rounds):
                sh.load_round(0, False, tick=False)
            for n, read in zip(*group_offers(
                    upd, rd, load["draw_seed"], int(g), self.settle_rounds,
                    self.rounds_done, cfg.max_props_per_round, shift=late)):
                sh.load_round(n, read, self.tick)
            for _ in range(self.quiet_rounds):
                sh.load_round(0, False, self.tick)
            out[int(g)] = sh
        return out

    def sample(self, load) -> List[int]:
        """Groups for the reference to follow, by popularity rank: the
        hottest, those about the median and the coldest, a third each
        of ``shadow_groups`` (the hottest take what does not divide)."""
        n = min(int(self.config.get("shadow_groups", 30)), self.groups)
        order = np.argsort(-self.popularity, kind="stable")
        k = n // 3
        hot, cold = n - 2 * k, self.groups - k
        mid = hot + (cold - hot - k) // 2  # of the ranks between them
        picked = (list(order[:hot]) + list(order[mid:mid + k])
                  + list(order[cold:]))
        return sorted(int(g) for g in picked)

    def check(self, load, raw, control=None) -> List[Check]:
        from ..reference.shadow_load import replay

        if control is True:  # ``benchmark/control.py``'s one control
            control = CONTROLS[0]
        if self.final is None:
            self.final = self.finish()
        final = self.final
        state = final["state"]
        cfg = self.cfg
        g_n, r = self.groups, cfg.num_replicas
        t0 = time.perf_counter()
        (upd, rd), late = self._planes(load, control or None)
        offered_ref, replayed = replay(
            upd, rd, load["draw_seed"], self.settle_rounds,
            self.rounds_done, cfg.max_props_per_round, shift=late)
        replay_s = time.perf_counter() - t0
        sample = self.sample(load)
        ref = self.reference(load, sample, control or None)
        rank = np.argsort(np.argsort(-self.popularity, kind="stable"))
        say("reference", groups=len(sample), rounds=self.rounds_done,
            seconds=time.perf_counter() - t0, replay_seconds=replay_s,
            replayed=replayed, sample=sample,
            popularity_ranks=[int(rank[g]) for g in sample])
        a, b = self.marks["open"], self.marks["close"]
        moved = {name: int(final["counters"][name]
                           - self.before["counters"][i])
                 for i, name in enumerate(final["counters"])}
        return (
            group_checks(state, g_n, r, cfg.window)
            + conservation_checks(
                state, g_n, r, self.before["last"], offered_ref,
                final["proposals_dropped"], final["elections_won"],
                load["leader_slots"])
            + count_checks(final["load"], replayed)
            + run_checks(final["invariants"], moved, final["watch"])
            + window_checks(
                b["load"]["offered"] - a["load"]["offered"],
                b["load"]["reads_asked"] - a["load"]["reads_asked"],
                int((b["commit"] - a["commit"]).sum()),
                int((b["reads"] - a["reads"]).sum()))
            + sampled_engine_checks(
                state, r, cfg.window, sample,
                lambda g: ref[g].snapshot_state(),
                lambda g, s: ref[g].log_terms(s))
            + sample_checks(
                state, state["history"], r, sample,
                lambda g: ref[g].membership(),
                lambda g: ref[g].read_state(),
                lambda g: ref[g].history()))
