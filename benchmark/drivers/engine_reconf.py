"""Driver ``engine_reconf``: ``MultiRaftEngine`` under etcd's raft
defaults, closed loop, with a node drained through joint configurations
under writes and ReadIndex reads inside the scans.

Stands beside ``drivers/engine_faults.py`` and is not an edit of it:
that driver hands the scan a fault schedule and nothing else, and its
reference knows no control plane. Here the configuration's ``sizes``
ask for ``conf_entries`` (a configuration change is an entry of the
device's log that each replica applies itself), each call hands the
scan its rows of the generator's schedule twice over
(``run_rounds(isolate=..., control=...)``: one program for settle,
warm-up, window, trace and drain), and the telemetry plane's totals
and the scan's own counts (``scan_watch``) are read as the window
opens and closes.

``correct`` (every limit 0; ``check``): after the window and the traced
calls the driver runs on to the end of the current period, reads the
whole state once (after ``memory_peak_bytes`` is read), and holds it to

* ``fault_checks.group_checks`` over all groups: one leader, never two
  in a term, replicas agreed on term and leader, committed prefixes
  equal wherever two rings hold the index, no replica more than half
  the ring behind its leader;
* ``reconf_checks.membership_checks`` over all groups: every replica's
  masks equal its leader's and, at the period's end, all three voters,
  no learner, not in a joint configuration;
* ``reconf_checks.window_checks``: every group committed and confirmed
  reads in the window, and every replica applied exactly four changes
  a whole period of it;
* ``reconf_checks.run_checks``, over every instance and every round of
  the run: the invariant bitmap is zero, no snapshot was sent (none
  carries a ConfState on the device yet), and the counts only the scan
  can see are zero: read batches confirmed with an index below a
  commit the group held before the batch opened, commits of a joint
  configuration in the rounds the schedule marks as stalled, marks of
  an unapplied change overwritten;
* class equality over all groups (``compare.engine_checks`` with
  ``fault_checks.schedule_classes`` as the classes), in every field,
  the configuration lanes and the history among them. What a group's
  run depends on, the schedule apart (which is every group's, node d0
  from the seed included): the replica the seed made its first leader
  (where its leadership sits when each drain begins) and its replicas'
  randomized timeouts (when a transfer's election ends), which at
  ``election_timeout`` 10 and R = 3 the residues of ``(3g+s+1)*7919``
  modulo 10 fix by g mod 10: at most 3 x 10 = 30 classes (checked on
  the CPU: ``tests/benchmark/test_reconf.py``);
* the sampled groups (one of each class while ``shadow_groups`` lasts)
  against ``reference.shadow_reconf.ReconfCluster`` stepped through the
  same rounds: in state and in log (``compare.engine_checks``), in
  each replica's membership masks, in its read state (``read_seq``,
  ``read_index``, ``read_ready``) and in its history, the hash of its
  state after every round (``reconf_checks.sample_checks``): the state
  at a period's end cannot tell a commit that ran ahead through the
  cut from one that stalled, the history can.

The cell's per-layer entries that read counters (``read.*``,
``reconf.*``, ``round.lanes_run``) read what ``window_counters`` hands
the generator's ``raw``.
"""

from __future__ import annotations

import inspect
import time
from typing import Dict, List, Optional

import numpy as np

from ..compare import Check, engine_checks
from ..fault_checks import group_checks, schedule_classes
from ..harness import say
from ..reconf_checks import (MASKS, membership_checks, run_checks,
                             sample_checks, window_checks)
from .engine import fence, occupancy, traced_closes, window_occupancy

# Controls (``check(control=...)``): each breaks, in the reference, one
# guarantee the configuration states; the comparison then has to fail.
CONTROLS = ("commit_on_the_incoming_majority_alone",
            "reads_confirmed_without_the_quorum")


class _Derailed:
    """A control's reference group that left the protocol: equal to
    nothing."""

    def __init__(self, replicas: int) -> None:
        self.replicas = replicas

    def snapshot_state(self):
        return [()] * self.replicas

    def log_terms(self, slot: int):
        return None

    def membership(self):
        return [()] * self.replicas

    def read_state(self):
        return [()] * self.replicas

    def history(self):
        return [-1] * self.replicas


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        self.config = config
        self.sizes = config["sizes"]
        self.seed = seed
        self.groups = int(self.sizes["num_groups"])
        self.eng = None
        self.calls = 0
        self.rounds_done = 0  # of the schedule's timeline
        self.settle_rounds = 0
        self.marks: Dict[str, dict] = {}
        self.final: Optional[dict] = None  # what `check` read, once
        self.derailed: List[int] = []  # of the last reference's groups

    def setup(self, load, gen) -> None:
        import jax.numpy as jnp

        from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

        if ("control" not in inspect.signature(
                MultiRaftEngine.run_rounds).parameters
                or "conf_entries" not in BatchedConfig._fields):
            raise RuntimeError(
                "this program's MultiRaftEngine.run_rounds takes no control "
                "schedule (control=) or its BatchedConfig no conf_entries: "
                "it cannot run a reconfiguration cell")
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
        # For a caller that opens no window (``benchmark/control.py``):
        # everything after the warm-up is then the window.
        self._mark("open")
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            first_drained_node=load["first_drained_node"],
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())

    @staticmethod
    def _nothing() -> dict:
        return {"drained": None, "transfer_to": None, "conf": None,
                "cut": None, "stall": False, "reads": False}

    def _arrays(self, rows: List[dict]):
        """The generator's rows as the engine's two schedules:
        (isolate bool [rounds, R], control int32 [rounds, CTL_COLS])."""
        from etcd_tpu.batched import engine as e
        from etcd_tpu.batched import state as st

        kinds = {self.gen.DEMOTE: st.CONF_DEMOTE, self.gen.LEAVE: st.CONF_LEAVE,
                 self.gen.PROMOTE: st.CONF_PROMOTE}
        isolate = np.zeros((len(rows), self.cfg.num_replicas), bool)
        control = np.zeros((len(rows), e.CTL_COLS), np.int32)
        for i, row in enumerate(rows):
            if row["cut"] is not None:
                isolate[i, row["cut"]] = True
            if row["drained"] is not None:
                control[i, e.CTL_FROM] = row["drained"] + 1
                control[i, e.CTL_TO] = row["transfer_to"] + 1
            if row["conf"] is not None:
                kind, node = row["conf"]
                control[i, e.CTL_CONF] = st.conf_code(kinds[kind], node or 0)
            control[i, e.CTL_READS] = int(row["reads"])
            control[i, e.CTL_STALL] = int(row["stall"])
        return isolate, control

    def call(self) -> None:
        """One scan of ``rounds_per_call`` rounds of the schedule,
        fenced."""
        isolate, control = self._arrays(
            self.gen.rows(self.load, self.rounds_done, self.rpc))
        self.eng.run_rounds(self.rpc, tick=self.tick, propose_n=self.props,
                            isolate=isolate, control=control)
        fence(self.eng)
        self.calls += 1
        self.rounds_done += self.rpc

    # -- the counters and the commits, as the window opens and closes ------------------

    def _mark(self, name: str) -> None:
        from etcd_tpu.batched.telemetry import TM_INDEX, TM_NAMES

        g_n, r = self.groups, self.cfg.num_replicas
        counters, _inv = self.eng.telemetry()
        totals = counters.sum(axis=0, dtype=np.int64)
        self.marks[name] = {
            "counters": {n: int(v) for n, v in zip(TM_NAMES, totals)},
            "watch": self.eng.scan_watch(),
            "commit": self.eng.commits().max(axis=1),
            "reads": counters[:, TM_INDEX["reads_confirmed"]].reshape(
                g_n, r).sum(axis=1, dtype=np.int64),
            "applied": counters[:, TM_INDEX["conf_changes_applied"]].copy(),
            "occupancy": occupancy(self),
            "rounds_done": self.rounds_done,
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
        ``readers/reconf.py``, ``readers/lanes.py`` and the roofline
        read."""
        a, b = self.marks["open"], self.marks["close"]
        return {
            "telemetry": {"before": a["counters"], "after": b["counters"]},
            "watch": {"before": a["watch"], "after": b["watch"]},
            **window_occupancy(self, self.marks),
            "entries_committed": int((b["commit"] - a["commit"]).sum()),
        }

    # -- the comparison, outside the window -------------------------------------------

    def drain(self) -> None:
        """On to the end of the current period: all voters again."""
        while self.rounds_done % self.load["period_rounds"]:
            self.call()

    def read_state(self) -> dict:
        from etcd_tpu.batched.state import BatchedState

        st = self.eng.state
        out = {f: np.asarray(getattr(st, f)) for f in BatchedState._fields}
        for f in st.conf._fields:
            name = f if f in MASKS else "conf_" + f
            out[name] = np.asarray(getattr(st.conf, f))
        out["history"] = self.eng.scan_history()
        return out

    def finish(self) -> dict:
        """What ``check`` compares, read once however often it is
        called: the state at the end of the period, and the invariant
        bitmap, the telemetry totals and the scan's counts over every
        round up to there."""
        from etcd_tpu.batched.telemetry import TM_NAMES

        self.drain()
        if "close" not in self.marks:
            self._mark("close")
        counters, invariants = self.eng.telemetry()
        totals = counters.sum(axis=0, dtype=np.int64)
        return {"state": self.read_state(), "invariants": invariants,
                "counters": {n: int(v) for n, v in zip(TM_NAMES, totals)},
                "watch": self.eng.scan_watch()}

    def reference(self, load, sample, control: Optional[str] = None):
        """The plain reference of the sampled groups, stepped through
        the rounds the engine ran. A ``control`` (one of ``CONTROLS``)
        breaks a guarantee the configuration states: an entry commits
        in a joint configuration on the incoming majority alone, so
        that the cut node's absence stalls nothing; or a read is
        confirmed at once, without the heartbeat quorum."""
        from ..reference.raft import quorum
        from ..reference.raft.logger import DefaultLogger, set_logger
        from ..reference.shadow_reconf import ReconfCluster

        set_logger(DefaultLogger(level=2))
        sound = quorum.JointConfig.committed_index
        if control == CONTROLS[0]:
            quorum.JointConfig.committed_index = (
                lambda self, acked: self.incoming.committed_index(acked))
        elif control not in (None, CONTROLS[1]):
            raise ValueError(f"unknown control {control!r}")
        self.derailed = []
        try:
            out = {}
            for g in sample:
                try:
                    out[int(g)] = self._step_reference(
                        load, g, ReconfCluster, control == CONTROLS[1])
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

    def _step_reference(self, load, g, ReconfCluster, lease_reads: bool):
        cfg = self.cfg
        sh = ReconfCluster(
            cfg.num_replicas, window=cfg.window,
            max_ents=cfg.max_ents_per_msg,
            max_props=cfg.max_props_per_round,
            election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, pre_vote=cfg.pre_vote,
            group=int(g), deterministic_timeouts=True,
            deliver_shape=cfg.deliver_shape,
            reads_without_quorum=lease_reads)
        sh.round(campaigns=[int(load["leader_slots"][g])])
        for _ in range(self.settle_rounds):
            sh.round(control=self._nothing())
        for rnd in range(self.rounds_done):
            row = self.gen.row(load, rnd)
            sh.round(offer=load["proposals_per_round"], tick=self.tick,
                     isolate=() if row["cut"] is None else (row["cut"],),
                     control=row)
        return sh

    def classes(self, load) -> np.ndarray:
        return schedule_classes(load["leader_slots"],
                                int(self.sizes["num_replicas"]),
                                int(self.sizes["election_timeout"]))

    def sample(self, load) -> List[int]:
        """Seeded groups for the reference to follow: one of each class
        in the seed's order while ``shadow_groups`` lasts."""
        rng = np.random.default_rng([self.seed, 0xE3202])
        n = min(int(self.config.get("shadow_groups", 12)), self.groups)
        order = rng.permutation(self.groups)
        classes = self.classes(load)
        picked: List[int] = []
        seen = set()
        for g in order:
            if len(picked) >= n:
                break
            if classes[g] not in seen:
                seen.add(classes[g])
                picked.append(int(g))
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
        final = self.final
        state = final["state"]
        cfg = self.cfg
        t0 = time.perf_counter()
        sample = self.sample(load)
        ref = self.reference(load, sample, control or None)
        say("reference", groups=len(sample), rounds=self.rounds_done,
            seconds=time.perf_counter() - t0, sample=sample)
        a, b = self.marks["open"], self.marks["close"]
        periods = (b["rounds_done"] - a["rounds_done"]) // (
            load["period_rounds"])
        whole = (b["rounds_done"] - a["rounds_done"]) % load["period_rounds"]
        return (
            group_checks(state, self.groups, cfg.num_replicas, cfg.window)
            + membership_checks(state, self.groups, cfg.num_replicas)
            + window_checks(a["commit"], b["commit"], a["reads"],
                            b["reads"], a["applied"], b["applied"],
                            0 if whole else periods)
            + run_checks(final["invariants"], final["counters"],
                         final["watch"])
            + engine_checks(
                state, self.groups, cfg.num_replicas, cfg.window,
                self.classes(load), sample,
                lambda g: ref[g].snapshot_state(),
                lambda g, s: ref[g].log_terms(s), skip_fields=())
            + sample_checks(
                state, state["history"], cfg.num_replicas, sample,
                lambda g: ref[g].membership(),
                lambda g: ref[g].read_state(),
                lambda g: ref[g].history()))

    def close(self) -> None:
        self.eng = None
