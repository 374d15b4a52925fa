"""Driver ``engine_faults``: ``MultiRaftEngine`` under etcd's raft
defaults, closed loop, with a node cut off and healed inside the scans.

Stands beside ``drivers/engine.py`` and is not an edit of it: that
driver builds ``BatchedConfig`` without ``pre_vote``, ``check_quorum``
or ``telemetry``, proposes on a leader slot fixed for the run and steps
its reference with that slot's proposals, all of which is wrong once a
timer fires. Here the configuration's ``sizes`` carry those switches,
proposals are offered to every replica, each call hands the scan its
rows of the generator's fault schedule (``run_rounds(isolate=...)``:
one program for settle, warm-up, window, trace and drain), and the
telemetry plane's totals are read as the window opens and closes.

``correct`` (every limit 0; ``check``): after the window and the traced
calls the driver runs on to the end of the current period, reads the
whole state once, and holds it to (the last item apart, which is about
one call more)

* ``fault_checks.group_checks`` over all groups: one leader, never two
  in a term, replicas agreed on term and leader, committed prefixes
  equal, no replica more than half the ring behind its leader;
* ``fault_checks.window_checks``: every group committed in the window,
  the invariant bitmap of every instance over every round of the run is
  zero, and elections were started and won and snapshots sent in the
  window;
* class equality over all groups (``compare.engine_checks`` with
  ``fault_checks.schedule_classes`` as the classes): groups with the
  same seeded first leader and the same timeout-hash residues of their
  R instance ids ran the same schedule and are equal row for row in
  every field; none is skipped (the timeout lane too is a function of
  the class). At ``election_timeout`` 10 and R = 3 the residues of
  ``(3g+s+1)*7919`` modulo 10 are fixed by g mod 10, so there are at
  most 3 x 10 = 30 classes (checked on the CPU:
  ``tests/benchmark/test_faults.py``);
* the sampled groups (one of each class while ``shadow_groups`` lasts,
  at least a third of them groups whose instance ids lie past the old
  int32 wrap of the timeout hash, iid >= 271,181) against
  ``reference.shadow_faults.FaultsCluster`` stepped through the same
  rounds, in state and in log;
* ``fault_checks.quiet_checks``: under load the node that was away is
  carried by snapshots and never replicates (at W = 32 and P = 2 the
  ring's floor passes each snapshot before its ack is back), so the
  state above cannot tell a follower that would catch up from one that
  never does. After that state is read the driver runs one call more
  of the same program with nothing offered and no node cut, and every
  replica has to stand level with its leader, in REPLICATE.

The cell's per-layer entries that read the telemetry plane
(``layer_metrics/election.*.json``) and the lane counter
(``round.lanes_run``) and the other occupancy counters read what
``window_counters`` hands the generator's ``raw``.
"""

from __future__ import annotations

import inspect
import time
from typing import Dict, List, Optional

import numpy as np

from ..compare import Check, engine_checks
from ..fault_checks import (group_checks, quiet_checks, schedule_classes,
                            window_checks)
from ..harness import say
from .engine import fence, occupancy, traced_closes, window_occupancy

# Controls (``check(control=...)``): each breaks, in the reference, one
# guarantee the configuration states; the comparison then has to fail.
CONTROLS = ("commit_without_quorum", "votes_without_log_check")
# (iid + 1) * 7919 passed 2**31 from this instance id on.
WRAPPED_FROM_IID = (2**31 - 1) // 7919


class _Derailed:
    """A control's reference group that left the protocol: equal to
    nothing."""

    def __init__(self, replicas: int) -> None:
        self.replicas = replicas

    def snapshot_state(self):
        return [()] * self.replicas

    def log_terms(self, slot: int):
        return None


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        self.config = config
        self.sizes = config["sizes"]
        self.seed = seed
        self.groups = int(self.sizes["num_groups"])
        self.eng = None
        self.calls = 0
        self.rounds_done = 0  # of the fault schedule's timeline
        self.settle_rounds = 0
        self.marks: Dict[str, dict] = {}
        self.final: Optional[dict] = None  # what `check` read, once
        self.derailed: List[int] = []  # of the last reference's groups

    def setup(self, load, gen) -> None:
        import jax.numpy as jnp

        from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

        if "isolate" not in inspect.signature(
                MultiRaftEngine.run_rounds).parameters:
            raise RuntimeError(
                "this program's MultiRaftEngine.run_rounds takes no fault "
                "schedule (isolate=): it cannot run a fault cell")
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
        )
        t0 = time.perf_counter()
        self.eng = eng = MultiRaftEngine(cfg)
        self.cfg = cfg = eng.cfg
        r = cfg.num_replicas
        self.load, self.gen = load, gen
        self.rpc = int(load["rounds_per_call"])
        self.tick = bool(load["tick"])
        if load["proposals_per_round"] > cfg.max_props_per_round:
            raise ValueError("proposals_per_round exceeds the config's P")
        slots = load["leader_slots"]
        eng.campaign(np.arange(self.groups, dtype=np.int64) * r + slots)
        # Settle with the timers off, through the window's own program.
        eng.run_rounds(self.rpc, tick=False,
                       isolate=np.zeros((self.rpc, r), bool))
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
        # For a caller that opens no window (``benchmark/control.py``):
        # everything after the warm-up is then the window.
        self._mark("open")
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            first_cut_node=load["first_cut_node"],
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())

    def call(self) -> None:
        """One scan of ``rounds_per_call`` rounds of the schedule,
        fenced."""
        self.eng.run_rounds(
            self.rpc, tick=self.tick, propose_n=self.props,
            isolate=self.gen.schedule(self.load, self.rounds_done,
                                      self.rpc))
        fence(self.eng)
        self.calls += 1
        self.rounds_done += self.rpc

    # -- the telemetry plane and the commits, as the window opens and closes ---------

    def _mark(self, name: str) -> None:
        from etcd_tpu.batched.telemetry import TM_NAMES

        counters, _inv = self.eng.telemetry()
        totals = counters.sum(axis=0, dtype=np.int64)
        self.marks[name] = {
            "counters": {n: int(v) for n, v in zip(TM_NAMES, totals)},
            "commit": self.eng.commits().max(axis=1),
            "occupancy": occupancy(self),
        }

    def window_opens(self) -> None:
        self.marks.clear()
        self._mark("open")

    def window_closes(self) -> None:
        self._mark("close")

    def traced_closes(self) -> None:
        traced_closes(self)

    def window_counters(self) -> dict:
        """For the generator's ``raw``: what ``readers/telemetry.py``,
        ``readers/lanes.py`` and the roofline read."""
        a, b = self.marks["open"], self.marks["close"]
        return {
            "telemetry": {"before": a["counters"], "after": b["counters"]},
            **window_occupancy(self, self.marks),
            "entries_committed": int((b["commit"] - a["commit"]).sum()),
        }

    # -- the comparison, outside the window -------------------------------------------

    def drain(self) -> None:
        """On to the end of the current period, so the state read is the
        one 32 rounds after a heal."""
        while self.rounds_done % self.load["period_rounds"]:
            self.call()

    def read_state(self, fields=None) -> dict:
        from etcd_tpu.batched.state import BatchedState

        return {f: np.asarray(getattr(self.eng.state, f))
                for f in fields or BatchedState._fields}

    def finish(self) -> dict:
        """What ``check`` compares, read once however often it is
        called: the state at the end of the period, then one call of
        the same program with nothing offered and no node cut (off the
        schedule's timeline: the reference does not follow it), the
        fields ``quiet_checks`` reads, and the invariant bitmap over
        every round up to there."""
        import jax.numpy as jnp

        self.drain()
        if "close" not in self.marks:
            self._mark("close")
        state = self.read_state()
        self.eng.run_rounds(
            self.rpc, tick=self.tick, propose_n=jnp.zeros_like(self.props),
            isolate=np.zeros((self.rpc, self.cfg.num_replicas), bool))
        quiet = self.read_state(("role", "commit", "last", "pr_state"))
        return {"state": state, "quiet": quiet,
                "invariants": self.eng.telemetry()[1]}

    def reference(self, load, sample, control: Optional[str] = None):
        """The plain reference of the sampled groups, stepped through
        the rounds the engine ran. A ``control`` (one of ``CONTROLS``)
        breaks a guarantee the configuration states: an entry commits
        on the leader's word alone, or a vote is granted to a log that
        is behind, so that a node just healed can win and rewrite
        committed entries."""
        from ..reference.raft import quorum
        from ..reference.raft.log import RaftLog
        from ..reference.raft.logger import DefaultLogger, set_logger
        from ..reference.shadow_faults import FaultsCluster

        set_logger(DefaultLogger(level=2))
        sound = (quorum.MajorityConfig.committed_index,
                 RaftLog.is_up_to_date)
        if control == "commit_without_quorum":
            quorum.MajorityConfig.committed_index = (
                lambda self, acked: max(
                    (acked(v) or 0 for v in self), default=0))
        elif control == "votes_without_log_check":
            RaftLog.is_up_to_date = lambda self, lasti, term: True
        elif control is not None:
            raise ValueError(f"unknown control {control!r}")
        self.derailed = []
        try:
            out = {}
            for g in sample:
                try:
                    out[int(g)] = self._step_reference(load, g,
                                                       FaultsCluster)
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
            (quorum.MajorityConfig.committed_index,
             RaftLog.is_up_to_date) = sound

    def _step_reference(self, load, g, FaultsCluster):
        cfg = self.cfg
        sh = FaultsCluster(
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
            sh.round()
        for rnd in range(self.rounds_done):
            k = self.gen.cut_node(load, rnd)
            sh.round(offer=load["proposals_per_round"], tick=self.tick,
                     isolate=() if k is None else (k,))
        return sh

    def classes(self, load) -> np.ndarray:
        return schedule_classes(load["leader_slots"],
                                int(self.sizes["num_replicas"]),
                                int(self.sizes["election_timeout"]))

    def sample(self, load) -> List[int]:
        """Seeded groups for the reference to follow: one of each class
        in the seed's order while ``shadow_groups`` lasts, taking the
        first third from the groups whose instance ids lie past the old
        hash's wrap (where there are such groups)."""
        rng = np.random.default_rng([self.seed, 0xE702])
        n = min(int(self.config.get("shadow_groups", 12)), self.groups)
        r = int(self.sizes["num_replicas"])
        order = rng.permutation(self.groups)
        classes = self.classes(load)
        picked: List[int] = []
        seen = set()

        def take(pool, upto: int) -> None:
            for g in pool:
                if len(picked) >= upto:
                    return
                if classes[g] not in seen:
                    seen.add(classes[g])
                    picked.append(int(g))

        take(order[(order + 1) * r > WRAPPED_FROM_IID], -(-n // 3))
        take(order, n)
        for g in order:  # fewer classes than groups to follow
            if len(picked) >= n:
                break
            if int(g) not in picked:
                picked.append(int(g))
        return sorted(picked)

    def check(self, load, raw, control=None) -> List[Check]:
        if control is True:  # ``benchmark/control.py``'s one control
            control = CONTROLS[0]
        if self.final is None:
            self.final = self.finish()
        state = self.final["state"]
        cfg = self.cfg
        t0 = time.perf_counter()
        sample = self.sample(load)
        ref = self.reference(load, sample, control or None)
        say("reference", groups=len(sample), rounds=self.rounds_done,
            seconds=time.perf_counter() - t0, sample=sample)
        a, b = self.marks["open"], self.marks["close"]
        return (
            group_checks(state, self.groups, cfg.num_replicas, cfg.window)
            + window_checks(a["commit"], b["commit"],
                            self.final["invariants"],
                            a["counters"], b["counters"])
            + quiet_checks(self.final["quiet"], self.groups,
                           cfg.num_replicas)
            + engine_checks(
                state, self.groups, cfg.num_replicas, cfg.window,
                self.classes(load), sample,
                lambda g: ref[g].snapshot_state(),
                lambda g, s: ref[g].log_terms(s), skip_fields=()))

    def close(self) -> None:
        self.eng = None
