"""Driver ``engine``: ``MultiRaftEngine`` driven directly, closed loop.

Set-up is ``benchlib.make_bench_engine``'s, copied, with two changes the
issue asks for: each group's leader slot comes from the seed, and one
scan length (``rounds_per_call``) serves settle, warm-up and window, so
a cell compiles one scan program. After the window the whole state is
read back once; a seeded sample of groups is compared with
``reference.shadow.ShadowCluster`` stepped through the same schedule.

The scan's lane counter (``MultiRaftEngine.lane_rounds``) is read where
set-up ends and as the window closes, never between two calls of the
window; ``window_counters`` hands both readings to the harness's
``raw`` (``lanes``), for ``readers/lanes.py``.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from ..compare import Check, engine_checks
from ..harness import say


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int,
                 workdir: str) -> None:
        self.config = config
        self.sizes = config["sizes"]
        self.seed = seed
        self.groups = int(self.sizes["num_groups"])
        self.eng = None
        self.calls = 0
        self.settle_rounds = 0
        self.lanes: dict = {}  # lane_rounds() before and after the window

    def setup(self, load, gen) -> None:
        import jax
        import jax.numpy as jnp

        from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

        s = self.sizes
        cfg = BatchedConfig(
            num_groups=self.groups,
            num_replicas=int(s["num_replicas"]),
            window=int(s["window"]),
            max_ents_per_msg=int(s["max_ents_per_msg"]),
            max_props_per_round=int(s["max_props_per_round"]),
            election_timeout=int(s["election_timeout"]),
            heartbeat_timeout=int(s["heartbeat_timeout"]),
            auto_compact=bool(s["auto_compact"]),
            lanes_minor=bool(s["lanes_minor"]),
            deliver_shape=s["deliver_shape"],
        )
        t0 = time.perf_counter()
        self.eng = eng = MultiRaftEngine(cfg)
        self.cfg = cfg = eng.cfg
        r = cfg.num_replicas
        self.rpc = int(load["rounds_per_call"])
        self.tick = bool(load["tick"])
        slots = load["leader_slots"]
        leaders = np.arange(self.groups, dtype=np.int64) * r + slots
        eng.campaign(leaders)
        eng.run_rounds(self.rpc, tick=False)
        self.settle_rounds = self.rpc
        got = eng.leaders()
        if not (got == slots).all():
            raise RuntimeError(
                f"{int((got != slots).sum())} groups did not elect the "
                "replica the seed drew")
        if load["proposals_per_round"] > cfg.max_props_per_round:
            raise ValueError("proposals_per_round exceeds the config's P")
        props = jnp.zeros((cfg.num_instances,), jnp.int32)
        self.props = props.at[jnp.asarray(leaders)].set(
            load["proposals_per_round"])
        self.call()  # warm-up: the window's own program and arguments
        jax.block_until_ready(eng.state.commit)
        # Nothing runs between here and the window's first call, and
        # ``generators/engine_rounds.run`` starts its clock before it
        # calls ``window_opens``: read here, the window pays nothing.
        self.lanes = {"before": eng.lane_rounds().tolist()}
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())

    def call(self) -> None:
        """One scan of ``rounds_per_call`` rounds, fenced."""
        import jax

        self.eng.run_rounds(self.rpc, tick=self.tick, propose_n=self.props)
        jax.block_until_ready(self.eng.state.commit)
        self.calls += 1

    def window_opens(self) -> None:
        pass

    def window_closes(self) -> None:
        self.lanes["after"] = self.eng.lane_rounds().tolist()

    def window_counters(self) -> dict:
        """For the harness's ``raw``: what ``readers/lanes.py`` reads."""
        return {"lanes": self.lanes}

    # -- the comparison, outside the window -------------------------------------------

    def read_state(self) -> dict:
        from etcd_tpu.batched.state import BatchedState

        return {f: np.asarray(getattr(self.eng.state, f))
                for f in BatchedState._fields}

    def reference(self, load, sample, control: bool = False):
        """The plain reference of the sampled groups, stepped through
        the schedule the engine ran. ``control`` breaks the guarantee
        the configuration states first — an entry then commits on the
        leader's word alone, without a quorum — and stands in the
        program's place to show that the comparison can fail."""
        from ..reference.raft import quorum
        from ..reference.raft.logger import DefaultLogger, set_logger
        from ..reference.shadow import ShadowCluster

        set_logger(DefaultLogger(level=2))
        sound = quorum.MajorityConfig.committed_index
        if control:
            quorum.MajorityConfig.committed_index = (
                lambda self, acked: max(
                    (acked(v) or 0 for v in self), default=0))
        try:
            return self._step_reference(load, sample, ShadowCluster)
        finally:
            quorum.MajorityConfig.committed_index = sound

    def _step_reference(self, load, sample, ShadowCluster):
        cfg = self.cfg
        slots = load["leader_slots"]
        out = {}
        for g in sample:
            sh = ShadowCluster(
                cfg.num_replicas, election_timeout=cfg.election_timeout,
                heartbeat_timeout=cfg.heartbeat_timeout,
                max_inflight=cfg.max_inflight, group=int(g),
                deterministic_timeouts=True,
                auto_compact_window=cfg.window,
                max_ents=cfg.max_ents_per_msg,
                deliver_shape=cfg.deliver_shape)
            lead = int(slots[g])
            sh.round(campaigns=[lead])
            for _ in range(self.settle_rounds):
                sh.round()
            for _ in range(self.rpc * self.calls):
                sh.round(tick=self.tick,
                         proposals={lead: load["proposals_per_round"]})
            out[int(g)] = sh
        return out

    def sample(self, load) -> List[int]:
        """Seeded groups for the reference to follow: first one of each
        leader slot's class (the comparison holds every group equal to
        its class, so each class stands compared with the reference),
        then whichever come next in the seed's order."""
        rng = np.random.default_rng([self.seed, 0xE602])
        n = min(int(self.config.get("shadow_groups", 32)), self.groups)
        order = rng.permutation(self.groups)
        _slots, first = np.unique(load["leader_slots"][order],
                                  return_index=True)
        first.sort()
        picked = np.concatenate([order[first], np.delete(order, first)])
        return sorted(int(g) for g in picked[:n])

    def check(self, load, raw, control: bool = False) -> List[Check]:
        state = self.read_state()
        sample = self.sample(load)
        ref = self.reference(load, sample, control)
        return engine_checks(
            state, self.groups, self.cfg.num_replicas, self.cfg.window,
            load["leader_slots"], sample,
            lambda g: ref[g].snapshot_state(),
            lambda g, s: ref[g].log_terms(s))

    def close(self) -> None:
        self.eng = None
