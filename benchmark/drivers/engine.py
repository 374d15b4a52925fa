"""Driver ``engine``: ``MultiRaftEngine`` driven directly, closed loop.

Set-up is ``benchlib.make_bench_engine``'s, copied, with two changes the
issue asks for: each group's leader slot comes from the seed, and one
scan length (``rounds_per_call``) serves settle, warm-up and window, so
a cell compiles one scan program. After the window the whole state is
read back once; a seeded sample of groups is compared with
``reference.shadow.ShadowCluster`` stepped through the same schedule.

The scan's occupancy counters (``occupancy``: ``lane_rounds``,
``rare_rounds``, ``bulk_rounds``, ``emit_ring_rounds`` of
``MultiRaftEngine``) are read where set-up ends, as the window closes
and, when the generator says the trace has stopped (``traced_closes``),
once more after the traced calls, never between two calls that are
timed or traced; ``window_counters`` hands the readings to the
harness's ``raw`` (``occupancy``), for ``readers/lanes.py`` and
``readers/trace.route_roofline_pct``.

What every engine driver shares stands here as functions: ``fence``
(the wait for a scan, under a span of its own), ``occupancy``,
``exchange_shape``, ``traced_closes`` and ``window_occupancy``.
"""

from __future__ import annotations

import time
from typing import List

import numpy as np

from ..compare import Check, engine_checks
from ..harness import say


def fence(eng) -> None:
    """Wait until the engine's last scan has run: every
    ``block_until_ready`` of the engine drivers, under the span
    ``engine.fence`` of the recorder the engine's own spans use
    (``etcd_tpu.obs.spans``: a ``TraceAnnotation`` on the device
    trace's clock), so that the device's idle time while the host sits
    here is put down to a name (``reduce/gaps.py``)."""
    import jax

    from etcd_tpu.obs import spans

    with spans.span("engine.fence", engine=getattr(eng, "_serial", 0)):
        jax.block_until_ready(eng.state.commit)


def occupancy(driver) -> dict:
    """One reading of the counters the closed loop keeps in its carry
    of what a round ran (a few integers, a host gather each; never read
    between two timed calls), with the driver's calls so far. Three of
    them count a round in which ANY tile took the branch (``lanes``,
    ``rare``, ``bulk``); ``ring`` counts tile-rounds."""
    eng = driver.eng
    return {"lanes": eng.lane_rounds().tolist(),
            "rare": eng.rare_rounds().tolist(),
            "bulk": eng.bulk_rounds(),
            "ring": eng.emit_ring_rounds(),
            "calls": driver.calls}


def exchange_shape(eng) -> dict:
    """What one round's exchange is made of, for ``reduce/roofline
    .lane_bytes``: the rows, the R slots a row addresses, and the bytes
    of one slot of each kind lane as the program carries it
    (``step.lane_slot_bytes`` at the configuration's E and the append
    lane's head, ``step.app_head``; with a head a seventh number, the
    tail's). ``ring_tiles``: what ``emit_ring_rounds`` counts a round
    at the most (the scan's tiles; over nodes, a node's)."""
    from etcd_tpu.batched import step

    cfg = eng.cfg
    head = step.app_head(cfg)
    placed = getattr(eng, "_nodes", None) is not None
    return {"rows": int(cfg.num_instances),
            "replicas": int(cfg.num_replicas),
            "app_head": int(head),
            "slot_bytes": step.lane_slot_bytes(
                cfg.max_ents_per_msg, head).tolist(),
            "ring_tiles": int(eng._tiles) * (
                int(cfg.num_replicas) if placed else 1)}


def traced_closes(driver) -> None:
    """The reading after the traced calls: the generators call it right
    after the trace is stopped, outside every timed and traced loop, so
    that the traced calls' own lanes are this reading less the closing
    mark's (``readers/trace.traced_runs``)."""
    driver.marks["traced"] = {"occupancy": occupancy(driver)}


def window_occupancy(driver, marks: dict) -> dict:
    """``raw["occupancy"]``, what the occupancy's readers read: every
    counter at the window's two marks and, where ``traced_closes`` took
    it, after the traced calls (else ``None``); each reading says the
    calls the driver had made, and a reader holds the difference to the
    calls that were traced; with it the exchange's shape. Reads
    nothing: asked twice, it says the same."""
    return {"occupancy": {
        "before": marks["open"]["occupancy"],
        "after": marks["close"]["occupancy"],
        "traced": marks.get("traced", {}).get("occupancy"),
        **exchange_shape(driver.eng)}}


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
        self.marks: dict = {}  # occupancy() round the window

    def setup(self, load, gen) -> None:
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
        fence(eng)
        # Nothing runs between here and the window's first call, and
        # ``generators/engine_rounds.run`` starts its clock before it
        # calls ``window_opens``: read here, the window pays nothing.
        self.marks = {"open": {"occupancy": occupancy(self)}}
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())

    def call(self) -> None:
        """One scan of ``rounds_per_call`` rounds, fenced."""
        self.eng.run_rounds(self.rpc, tick=self.tick, propose_n=self.props)
        fence(self.eng)
        self.calls += 1

    def window_opens(self) -> None:
        pass

    def window_closes(self) -> None:
        self.marks["close"] = {"occupancy": occupancy(self)}

    def traced_closes(self) -> None:
        traced_closes(self)

    def window_counters(self) -> dict:
        """For the harness's ``raw``: what ``readers/lanes.py`` and the
        roofline read. A caller that closed no window gets nothing."""
        if "close" not in self.marks:
            return {}
        return window_occupancy(self, self.marks)

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
