"""Driver ``engine_trickle``: ``drivers/engine_replace.py``'s deployment
with a node decommissioned range by range. The closed loop is handed a
*phased* control schedule (``MultiRaftEngine.run_rounds(control=cycle,
starts=...)``): one cycle's rows, the replacement cell's move, and the
round at which each group enters it, from the generator
(``engine_trickle_rounds``: a batch of groups every few rounds, drawn
by the seed). Every other group is written and read in every round and
changes nothing.

Stands beside ``engine_replace.py`` and is not an edit of it (its
``Driver`` is the base class here: the rows as the engine's columns,
the marks, the state read back, the comparison's last two parts). What
differs: ``setup`` is the base's with the phased call in the place of
the lockstep one (repeated here for want of a hook: ROADMAP R0b.14);
a call hands the engine the same cycle and the same starts every
time, and the engine's own round count says where in them it is; the
reference is ``reference.shadow_trickle.TrickleCluster``, one group
stepped on its own rows; and ``correct`` holds each group to where it
stands in its own cycle when the run ends. A program whose
``run_rounds`` takes no phased schedule is refused at once, before
anything is built.

``correct`` (every limit 0; ``check``), on what the timed scans left,
after the run has gone on to the end of the current cycle length:

* ``trickle_checks.resting_checks``: ``fault_checks.group_checks`` over
  the groups in no move, on their three live slots;
* ``trickle_checks.membership_checks``: every finished move left
  {n, m, e} as voters, no learner, nothing outgoing; a group not
  started holds {d, n, m}; no group is in a joint configuration
  outside rounds 40-127 of its own cycle;
* ``trickle_checks.fresh_slot_checks``: slot d of every finished move
  is a fresh replica on every field, and so is slot e of every group
  not started;
* ``trickle_checks.move_checks``: one snapshot a move, no change
  applied by a group that has not started, one swap a move whose round
  40 and one reset a move whose round 120 fell in the run, a snapshot
  that restored a configuration for each;
* ``trickle_checks.run_checks`` over every instance and every round;
  ``trickle_checks.window_checks``: every group committed and
  confirmed reads in the window;
* class equality over all groups in every field
  (``compare.engine_checks``): what a group's run depends on is its
  first leader, ``g mod 5`` (its replicas' timeouts) and its start, so
  its batch; the groups never started are a batch of their own;
* the sampled groups against the reference in state, log, masks, read
  state and history: one group of each of the 15 classes never
  started and, for ``SAMPLE_BATCHES`` batches spread over the run, one
  of each class the batch holds (``sample``).

The per-layer entries ``trickle.*`` read what ``window_counters``
hands the generator's ``raw``; the accepted entries whose layers this
cell runs too list it and read the same ``raw`` and the trace.
"""

from __future__ import annotations

import inspect
import time
from typing import List, Optional

import numpy as np

from ..compare import Check, engine_checks
from ..fault_checks import schedule_classes
from ..harness import say
from ..reconf_checks import sample_checks
from ..trickle_checks import (fresh_slot_checks, membership_checks,
                              move_checks, resting_checks, run_checks,
                              window_checks)
from . import engine_replace
from .engine import fence
from .engine_reconf import _Derailed

# Controls (``check(control=...)``): each steps the reference on
# another schedule than the program ran; the comparison then has to
# fail. The starts a round late: every sampled mover differs, nobody
# else. Every group on the lockstep schedule (the phased argument
# dropped: each group moves from round 0): every sampled group differs
# but those of the first batch.
CONTROLS = ("starts_shifted_by_one_round",
            "every_group_on_the_lockstep_schedule")
# Rounds after an offer's own that a count at a call's end gives a
# leader to take it: every such edge is 8 past a multiple of 16 and a
# call ends on a multiple of 64, so the youngest offer a count can meet
# stood for its own round and these seven.
SLACK = 7
# Batches the sample follows beside the groups never started.
SAMPLE_BATCHES = 6


class Driver(engine_replace.Driver):
    def setup(self, load, gen) -> None:
        import jax.numpy as jnp

        from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

        if "starts" not in inspect.signature(
                MultiRaftEngine.run_rounds).parameters:
            raise RuntimeError(
                "this program's MultiRaftEngine.run_rounds takes no phased "
                "control schedule (starts=): it cannot move a few groups at "
                "a time")
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
            replace_replicas=bool(s["replace_replicas"]),
        )
        budget = self.config["rebalance"]
        if load["batch_groups"] != (
                int(budget["snapshots_in_flight_per_member"])
                * int(budget["sending_members"])):
            raise ValueError(
                "batch_groups is not the configuration's snapshot budget")
        t0 = time.perf_counter()
        self.eng = eng = MultiRaftEngine(cfg, spare=load["first_spare_node"])
        self.cfg = cfg = eng.cfg
        r = cfg.num_replicas
        self.load, self.gen = load, gen
        self.rpc = int(load["rounds_per_call"])
        self.tick = bool(load["tick"])
        if load["proposals_per_round"] > cfg.max_props_per_round:
            raise ValueError("proposals_per_round exceeds the config's P")
        slots = load["leader_slots"]
        eng.campaign(np.arange(self.groups, dtype=np.int64) * r + slots)
        # Settle with the timers off, nothing asked and nobody started,
        # through the window's own program. The engine counts its
        # phased rounds from its first, so the schedule's round 0 is
        # its round `settle_rounds`.
        _, self.cycle = self._arrays(gen.cycle(load))
        self.settle_rounds = self.rpc
        never = np.full(self.groups, gen.NEVER, np.int32)
        # (The cycle's own rows with no read asked: the program is
        # compiled for the cycle's runs of equal rows.)
        _, quiet = self._arrays([dict(row, reads=False)
                                 for row in gen.cycle(load)])
        eng.run_rounds(self.rpc, tick=False, control=quiet, starts=never)
        self.starts = np.where(
            load["starts"] == gen.NEVER, gen.NEVER,
            load["starts"].astype(np.int64) + self.settle_rounds
        ).astype(np.int32)
        got = eng.leaders()
        if not (got == slots).all():
            raise RuntimeError(
                f"{int((got != slots).sum())} groups did not elect the "
                "replica the seed drew")
        # Offered to every replica; `_propose` appends on a leader only.
        self.props = jnp.full((cfg.num_instances,),
                              load["proposals_per_round"], jnp.int32)
        # Warm-up: the window's own program and arguments, for a whole
        # cycle, so that the window opens on the steady state: eight
        # batches in flight, each at another point of the cycle.
        for _ in range(load["cycle_rounds"] // self.rpc):
            self.call()
        fence(eng)
        self._mark("open")
        e, d, n, m = gen.nodes(load)
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            moved_to=e, drained=d, transfers_to=n,
            batches=len(load["batches"]), batch_groups=load["batch_groups"],
            batch_every_rounds=load["batch_every_rounds"],
            tiles=eng._tiles,
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())

    def call(self) -> None:
        """One scan of ``rounds_per_call`` rounds: the cycle and the
        starts, as every call hands them; fenced."""
        self.eng.run_rounds(self.rpc, tick=self.tick, propose_n=self.props,
                            control=self.cycle, starts=self.starts)
        fence(self.eng)
        self.calls += 1
        self.rounds_done += self.rpc

    # -- the counters, as the window opens and closes -----------------------------------

    def _mark(self, name: str) -> None:
        """The base's, and the groups in motion: those whose replica on
        the node that stays (n) has applied the learner's change, less
        the slots reset so far."""
        super()._mark(name)
        mark = self.marks[name]
        n = self.gen.nodes(self.load)[2]
        learned = int((mark["applied"].reshape(
            self.groups, self.cfg.num_replicas)[:, n] > 0).sum())
        mark["in_motion"] = learned - int(mark["watch"]["replicas_reset"])

    def _unoffered(self, mark: dict) -> int:
        """Entries the groups have appended that nobody offered: a
        configuration change, counted where the replica on the node
        that stays (n, a voter all through) applied it, and the empty
        entry of each election won."""
        n = self.gen.nodes(self.load)[2]
        changes = mark["applied"].reshape(
            self.groups, self.cfg.num_replicas)[:, n].sum(dtype=np.int64)
        return int(changes) + mark["counters"]["elections_won"]

    def window_counters(self) -> dict:
        a, b = self.marks["open"], self.marks["close"]
        counters = super().window_counters()
        offered = (b["rounds_done"] - a["rounds_done"]) * (
            self.load["proposals_per_round"]) * self.groups
        return dict(counters, trickle={
            "in_motion_open": a["in_motion"],
            "in_motion_close": b["in_motion"],
            "offered": int(offered),
            "unoffered_committed": self._unoffered(b) - self._unoffered(a),
            "batch_groups": self.load["batch_groups"],
            "batches_in_flight": self.load["batches_in_flight"]})

    def finish(self) -> dict:
        from etcd_tpu.batched.telemetry import TM_INDEX

        final = super().finish()
        counters, _inv = self.eng.telemetry()
        r = self.cfg.num_replicas
        final["snaps"] = counters[:, TM_INDEX["sent_snapshot"]].reshape(
            self.groups, r).sum(axis=1, dtype=np.int64)
        final["applied"] = counters[
            :, TM_INDEX["conf_changes_applied"]].astype(np.int64)
        return final

    # -- the comparison, outside the window -------------------------------------------

    def reference(self, load, sample, control: Optional[str] = None):
        """The plain reference of the sampled groups, each stepped on
        its own rows through the rounds the engine ran. A ``control``
        (one of ``CONTROLS``) steps them on another schedule."""
        from ..reference.raft.logger import DefaultLogger, set_logger
        from ..reference.shadow_trickle import TrickleCluster

        set_logger(DefaultLogger(level=2))
        if control not in (None,) + CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        self.derailed = []
        out = {}
        for g in sample:
            start = int(load["starts"][g])
            if control == CONTROLS[0] and start != self.gen.NEVER:
                start += 1
            elif control == CONTROLS[1]:
                start = 0
            try:
                out[int(g)] = self._step_group(load, g, start, TrickleCluster)
            except Exception as e:
                # Only a control may take the plain reference out of
                # what its network emulation knows.
                if control is None:
                    raise
                say("reference_derailed", control=control, group=int(g),
                    error=repr(e))
                out[int(g)] = _Derailed(self.cfg.num_replicas)
                self.derailed.append(int(g))
        return out

    def _step_group(self, load, g, start: int, TrickleCluster):
        cfg = self.cfg
        sh = TrickleCluster(
            cfg.num_replicas, start=start, row=self.gen.row, load=load,
            spare=load["first_spare_node"], window=cfg.window,
            max_ents=cfg.max_ents_per_msg,
            max_props=cfg.max_props_per_round,
            election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, pre_vote=cfg.pre_vote,
            group=int(g), deterministic_timeouts=True,
            deliver_shape=cfg.deliver_shape)
        sh.round(campaigns=[int(load["leader_slots"][g])])
        for _ in range(self.settle_rounds):
            sh.round(control=self._nothing())
        for _ in range(self.rounds_done):
            sh.schedule_round(load["proposals_per_round"], self.tick)
        return sh

    def batch_of(self, load) -> np.ndarray:
        """[G] the batch each group starts in; the groups never started
        one more, of their own."""
        starts = load["starts"].astype(np.int64)
        return np.where(starts == self.gen.NEVER, len(load["batches"]),
                        starts // load["batch_every_rounds"])

    def classes(self, load) -> np.ndarray:
        return (super().classes(load) * (len(load["batches"]) + 1)
                + self.batch_of(load))

    def sampled_batches(self, load) -> List[int]:
        """``SAMPLE_BATCHES`` of the batches that had started when the
        run ended, spread over it: the first, the last, and evenly
        between (so moves long done, moves in every stretch of the
        cycle, and one whose learner is barely on offer)."""
        started = min(len(load["batches"]),
                      -(-self.rounds_done // load["batch_every_rounds"]))
        want = min(SAMPLE_BATCHES, started)
        return sorted({int(round(x))
                       for x in np.linspace(0, started - 1, want)})

    def sample(self, load) -> List[int]:
        """Seeded groups for the reference to follow: of the groups
        never started one of each class (first leader x g mod 5) and,
        for each of ``sampled_batches``, one of each class the batch
        holds, in turn while ``shadow_groups`` lasts."""
        rng = np.random.default_rng([self.seed, 0xE4203])
        base = schedule_classes(load["leader_slots"],
                                int(self.sizes["num_replicas"]),
                                int(self.sizes["election_timeout"]))
        pools = [rng.permutation(np.flatnonzero(
            load["starts"] == self.gen.NEVER))[:4096]]
        pools += [rng.permutation(load["batches"][b])
                  for b in self.sampled_batches(load)]
        picks = []
        for pool in pools:
            _, first = np.unique(base[pool], return_index=True)
            picks.append([int(pool[i]) for i in sorted(first)])
        n = min(int(self.config.get("shadow_groups", 120)), self.groups)
        out: List[int] = []
        for i in range(max(map(len, picks))):
            for pick in picks:
                if i < len(pick) and len(out) < n:
                    out.append(pick[i])
        return sorted(out)

    def check(self, load, raw, control=None) -> List[Check]:
        if control is True:  # ``benchmark/control.py``'s one control
            control = CONTROLS[0]
        if self.final is None:
            self.final = self.finish()
        final = self.final
        state = final["state"]
        cfg = self.cfg
        g_n, r = self.groups, cfg.num_replicas
        t0 = time.perf_counter()
        sample = self.sample(load)
        ref = self.reference(load, sample, control or None)
        batch = self.batch_of(load)
        say("reference", groups=len(sample), rounds=self.rounds_done,
            seconds=time.perf_counter() - t0, sample=sample,
            batches=sorted({int(batch[g]) for g in sample}),
            never_started=sum(int(batch[g]) == len(load["batches"])
                              for g in sample))
        a, b = self.marks["open"], self.marks["close"]
        cycle = load["cycle_rounds"]
        cycles, part = divmod(b["rounds_done"] - a["rounds_done"], cycle)
        e, d, _n, _m = self.gen.nodes(load)
        done = self.rounds_done
        k = done - load["starts"].astype(np.int64)
        finished = np.flatnonzero(k >= cycle)
        waiting = np.flatnonzero(k < 0)
        return (
            resting_checks(state, k, e, d, r, cfg.window, cycle,
                           load["add_learner_round"])
            + membership_checks(state, k, e, d, r, cycle,
                                load["add_learner_round"],
                                load["swap_round"])
            + fresh_slot_checks(
                state, finished, d, r, cfg.election_timeout,
                k[finished] - load["wipe_round"] - 1, load["reads"],
                "slots_reset_that_are_not_a_fresh_replica")
            + fresh_slot_checks(
                state, waiting, e, r, cfg.election_timeout,
                np.full(len(waiting), done if self.tick else 0),
                load["reads"],
                "empty_slots_of_groups_not_started_that_are_not_a_fresh_"
                "replica")
            + move_checks(
                k, final["snaps"], final["applied"], final["watch"],
                self.gen.moves(load, "swap_round", done, SLACK),
                self.gen.moves(load, "wipe_round", done), r,
                load["add_learner_round"], SLACK)
            + run_checks(final["invariants"], final["counters"],
                         final["watch"])
            + window_checks(a["commit"], b["commit"], a["reads"], b["reads"],
                            0 if part else cycles)
            + self._engine_checks(state, d, load, sample, ref)
            + sample_checks(
                state, state["history"], r, sample,
                lambda g: ref[g].membership(),
                lambda g: ref[g].read_state(),
                lambda g: ref[g].history()))

    def _engine_checks(self, state, d, load, sample, ref) -> List[Check]:
        """``compare.engine_checks`` (class equality over all groups in
        every field, the sample against the reference in state and
        log) with its first count, the groups in which some replica has
        committed nothing, taken over each group's three furthest
        replicas: a slot that is empty, before a move or after it, has
        committed nothing because it holds nothing."""
        cfg = self.cfg
        g_n, r = self.groups, cfg.num_replicas
        checks = engine_checks(
            state, g_n, r, cfg.window, self.classes(load), sample,
            lambda g: ref[g].snapshot_state(),
            lambda g, s: ref[g].log_terms(s), skip_fields=())
        name = "groups_that_committed_nothing"
        assert checks[0].name == name
        third = np.sort(state["commit"].reshape(g_n, r), axis=1)[:, r - 3]
        return [Check(name, int((third <= 0).sum()), 0)] + checks[1:]
