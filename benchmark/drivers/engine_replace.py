"""Driver ``engine_replace``: ``MultiRaftEngine`` under etcd's raft
defaults, closed loop, with the nodes of a three-replica store replaced
one at a time under writes and ReadIndex reads inside the scans: four
nodes, a group's fourth slot the empty spare.

Stands beside ``drivers/engine_reconf.py`` and is not an edit of it
(its ``Driver`` is the base class here: the calls, the marks, the
state read back, the sample): that driver builds every slot a voter,
knows four kinds of change with one slot each and a control schedule of
five columns, and asserts that no snapshot is sent. Here the
configuration's ``sizes`` ask for ``replace_replicas`` beside
``conf_entries``, the engine is built with
the seed's spare slot empty, a row offers the simple ``AddLearnerNode``
and the joint swap of two ops, retires a node and resets its slot
(``engine.CTL_RETIRE``, ``CTL_WIPE``), and a snapshot carries each new
replica.

``correct`` (every limit 0; ``check``): after the window and the traced
calls the driver runs on to the end of the current period, reads the
whole state once (after ``memory_peak_bytes`` is read), and holds it to

* ``fault_checks.group_checks`` over all groups, on the three slots
  that are not the node just retired (``replace_checks.live_view``);
* ``replace_checks.membership_checks``: every such replica's voters are
  exactly those three nodes, no learner, nothing outgoing, no joint
  configuration; ``empty_slot_checks``: the slot retired is a fresh
  replica on every field;
* ``replace_checks.window_checks``: every group committed and confirmed
  reads in the window, every replica applied exactly the changes its
  place in the cycle gives it (``generators.engine_replace_rounds
  .applies``), no group's replicas sent more than two snapshots a new
  replica;
* ``replace_checks.run_checks``, over every instance and every round of
  the run: the invariant bitmap is zero and so are the counts only the
  scan can see: read batches confirmed below an earlier commit, commits
  of a joint configuration in the rounds marked as stalled, marks
  overwritten unapplied, a vote or a campaign by a slot outside its own
  configuration, a swap taken before the new replica stood in REPLICATE
  level with its leader; one swap and one reset a group a period, and a
  snapshot that restored a configuration for each;
* class equality over all groups (``compare.engine_checks`` with
  ``fault_checks.schedule_classes``), in every field. What a group's
  run depends on, the schedule apart (every group's, node e0 from the
  seed included): the replica the seed made its first leader, one of
  the three seated, and its replicas' randomized timeouts, which at
  ``election_timeout`` 10 the residues of ``(iid + 1) * 7919`` modulo
  10 fix for every reset count (a wiped replica's count starts over in
  every group alike). With iid = 4g + s those residues depend on
  4g mod 10, so on g mod 5: at most 3 x 5 = 15 classes (checked on the
  CPU: ``tests/benchmark/test_replace.py``);
* the sampled groups (one of each class) against
  ``reference.shadow_replace.ReplaceCluster`` stepped through the same
  rounds: state and log (``compare.engine_checks``), membership masks,
  read state and history (``reconf_checks.sample_checks``).

The cell's per-layer entries (``replace.*``, ``round.lanes_run``) read
what the base class's ``window_counters`` hands the generator's ``raw``.
"""

from __future__ import annotations

import inspect
import time
from typing import List, Optional

import numpy as np

from ..compare import Check, engine_checks
from ..fault_checks import group_checks
from ..harness import say
from ..reconf_checks import sample_checks
from ..replace_checks import (empty_slot_checks, live_view,
                              membership_checks, run_checks, window_checks)
from . import engine_reconf
from .engine import fence
from .engine_reconf import _Derailed

# Controls (``check(control=...)``): each breaks, in the reference, one
# guarantee the configuration states; the comparison then has to fail.
CONTROLS = ("snapshot_restored_without_its_confstate",
            "commit_on_the_incoming_majority_alone")


class Driver(engine_reconf.Driver):
    def setup(self, load, gen) -> None:
        import jax.numpy as jnp

        from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

        if ("replace_replicas" not in BatchedConfig._fields
                or "spare" not in inspect.signature(
                    MultiRaftEngine.__init__).parameters):
            raise RuntimeError(
                "this program's BatchedConfig has no replace_replicas or its "
                "MultiRaftEngine builds no spare slot: it cannot run a "
                "replacement cell")
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
        # Settle with the timers off and nothing asked, through the
        # window's own program.
        isolate, control = self._arrays([self._nothing()] * self.rpc)
        eng.run_rounds(self.rpc, tick=False, isolate=isolate,
                       control=control)
        self.settle_rounds = self.rpc
        got = eng.leaders()
        if not (got == slots).all():
            raise RuntimeError(
                f"{int((got != slots).sum())} groups did not elect the "
                "replica the seed drew")
        # Offered to every replica; `_propose` appends on a leader only.
        self.props = jnp.full((cfg.num_instances,),
                              load["proposals_per_round"], jnp.int32)
        self.call()  # warm-up: the window's own program and arguments
        fence(eng)
        self._mark("open")
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            first_spare_node=load["first_spare_node"],
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())

    @staticmethod
    def _nothing() -> dict:
        return {"drained": None, "transfer_to": None, "conf": None,
                "cut": None, "retired": None, "wipe": None, "stall": False,
                "reads": False}

    def _arrays(self, rows: List[dict]):
        """The generator's rows as the engine's two schedules:
        (isolate bool [rounds, R], control int32 [rounds, 7])."""
        from etcd_tpu.batched import engine as e
        from etcd_tpu.batched import state as st

        kinds = {self.gen.ADD_LEARNER: st.CONF_ADD_LEARNER,
                 self.gen.SWAP: st.CONF_SWAP, self.gen.LEAVE: st.CONF_LEAVE}
        isolate = np.zeros((len(rows), self.cfg.num_replicas), bool)
        control = np.zeros((len(rows), e.control_cols(self.cfg)), np.int32)
        for i, row in enumerate(rows):
            if row["cut"] is not None:
                isolate[i, row["cut"]] = True
            if row["drained"] is not None:
                control[i, e.CTL_FROM] = row["drained"] + 1
                control[i, e.CTL_TO] = row["transfer_to"] + 1
            if row["conf"] is not None:
                kind, node, node2 = row["conf"]
                control[i, e.CTL_CONF] = st.conf_code(
                    kinds[kind], node or 0, node2 or 0)
            control[i, e.CTL_READS] = int(row["reads"])
            control[i, e.CTL_STALL] = int(row["stall"])
            if row["retired"] is not None:
                control[i, e.CTL_RETIRE] = row["retired"] + 1
            if row["wipe"] is not None:
                control[i, e.CTL_WIPE] = row["wipe"] + 1
        return isolate, control

    def _mark(self, name: str) -> None:
        from etcd_tpu.batched.telemetry import TM_INDEX

        super()._mark(name)
        counters, _inv = self.eng.telemetry()
        self.marks[name]["snaps"] = counters[
            :, TM_INDEX["sent_snapshot"]].reshape(
                self.groups, self.cfg.num_replicas).sum(
                    axis=1, dtype=np.int64)

    # -- the comparison, outside the window -------------------------------------------

    def reference(self, load, sample, control: Optional[str] = None):
        """The plain reference of the sampled groups, stepped through
        the rounds the engine ran. A ``control`` (one of ``CONTROLS``)
        breaks a guarantee the configuration states: a node restores a
        snapshot's log and keeps the configuration it has (the parent
        program's snapshot handler), so a fresh replica never learns
        that it is a member; or an entry commits in a joint configuration on
        the incoming majority alone, so that the stalled rounds commit."""
        from ..reference.raft import quorum
        from ..reference.raft.logger import DefaultLogger, set_logger
        from ..reference.shadow_replace import ReplaceCluster

        set_logger(DefaultLogger(level=2))
        if control not in (None,) + CONTROLS:
            raise ValueError(f"unknown control {control!r}")
        sound = quorum.JointConfig.committed_index
        if control == CONTROLS[1]:
            quorum.JointConfig.committed_index = (
                lambda self, acked: self.incoming.committed_index(acked))
        self.derailed = []
        try:
            out = {}
            for g in sample:
                try:
                    out[int(g)] = self._step_reference(
                        load, g, ReplaceCluster, control == CONTROLS[0])
                except Exception as e:
                    # Only a broken guarantee may take the plain
                    # reference out of what its network emulation knows.
                    if control is None:
                        raise
                    say("reference_derailed", control=control, group=int(g),
                        error=repr(e))
                    out[int(g)] = _Derailed(self.cfg.num_replicas)
                    self.derailed.append(int(g))
            return out
        finally:
            quorum.JointConfig.committed_index = sound

    def _step_reference(self, load, g, ReplaceCluster, no_confstate: bool):
        cfg = self.cfg
        sh = ReplaceCluster(
            cfg.num_replicas, spare=load["first_spare_node"],
            restore_without_confstate=no_confstate, window=cfg.window,
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
        for rnd in range(self.rounds_done):
            row = self.gen.row(load, rnd)
            sh.round(offer=load["proposals_per_round"], tick=self.tick,
                     isolate=[s for s in (row["cut"], row["retired"])
                              if s is not None],
                     control=row)
        return sh

    def _engine_checks(self, state, d, load, sample, ref) -> List[Check]:
        """``compare.engine_checks`` (class equality over all groups in
        every field, the sample against the reference in state and
        log) with its first count, the groups in which some replica
        has committed nothing, taken over the live slots: the spare
        has committed nothing because it holds nothing."""
        cfg = self.cfg
        g_n, r = self.groups, cfg.num_replicas
        checks = engine_checks(
            state, g_n, r, cfg.window, self.classes(load), sample,
            lambda g: ref[g].snapshot_state(),
            lambda g, s: ref[g].log_terms(s), skip_fields=())
        name = "groups_that_committed_nothing"
        assert checks[0].name == name
        live = live_view(state, g_n, r, d)["commit"].reshape(g_n, r - 1)
        return [Check(name, int((live.min(axis=1) <= 0).sum()), 0)
                ] + checks[1:]

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
        say("reference", groups=len(sample), rounds=self.rounds_done,
            seconds=time.perf_counter() - t0, sample=sample)
        a, b = self.marks["open"], self.marks["close"]
        period = load["period_rounds"]
        periods, part = divmod(b["rounds_done"] - a["rounds_done"], period)
        run_periods = self.rounds_done // period
        # The node retired in the period that just ended: the spare.
        d = self.gen.nodes(load, run_periods - 1)[1]
        return (
            group_checks(live_view(state, g_n, r, d), g_n, r - 1, cfg.window)
            + membership_checks(state, g_n, r, d)
            + empty_slot_checks(
                state, g_n, r, d, cfg.election_timeout,
                period - load["wipe_round"] - 1, load["reads"])
            + window_checks(
                a["commit"], b["commit"], a["reads"], b["reads"],
                a["applied"], b["applied"],
                self.gen.applies(load, a["rounds_done"], b["rounds_done"]),
                a["snaps"], b["snaps"], 0 if part else periods)
            + run_checks(final["invariants"], final["counters"],
                         final["watch"], g_n, run_periods)
            + self._engine_checks(state, d, load, sample, ref)
            + sample_checks(
                state, state["history"], r, sample,
                lambda g: ref[g].membership(),
                lambda g: ref[g].read_state(),
                lambda g: ref[g].history()))
