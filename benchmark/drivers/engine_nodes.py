"""Driver ``engine_nodes``: ``drivers/engine_replace.py``'s deployment
with its nodes on chips of their own. ``MultiRaftEngine(nodes=...)`` is
handed as many of the host's devices as a group has slots; device s
holds slot s of every group, the round's messages cross the chips'
interconnect as one all-to-all a kind lane inside the scan, and a node
the schedule cuts off, switches off or wipes is a chip's rows.

Stands beside ``engine_replace.py`` and is not an edit of it (its
``Driver`` is the base class here: the schedules, the marks, the
reference's rounds, every comparison). What differs: the engine is
built over the devices; the state is read back in the logical order
``g * R + s`` (``eng.logical``: the engine keeps its arrays a node
after the other), so the checks, the classes and the sample read what
they read from one chip; the reference is
``reference.shadow_replace_nodes.NodesCluster``, which knows nothing of
chips; and after every call the engine's count of lanes that crossed
the interconnect (``eng.lane_exchanges()``, a few integers) is noted,
for ``readers/nodes.py``, with the bytes of a slot of each lane as the
program carries it (``drivers/engine.exchange_shape``).

``correct`` is ``engine_replace``'s, every limit 0, on what the timed
scans left on the four chips.
"""

from __future__ import annotations

import inspect
import time

import numpy as np

from ..harness import say
from . import engine_replace
from .engine import exchange_shape, fence

CONTROLS = engine_replace.CONTROLS


class Driver(engine_replace.Driver):
    def setup(self, load, gen) -> None:
        import jax
        import jax.numpy as jnp

        from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

        if "nodes" not in inspect.signature(
                MultiRaftEngine.__init__).parameters:
            raise RuntimeError(
                "this program's MultiRaftEngine takes no nodes (nodes=): it "
                "cannot place a group's replicas on chips of their own")
        s = self.sizes
        r = int(s["num_replicas"])
        devices = jax.devices()
        if len(devices) < r:
            raise RuntimeError(
                f"a node a chip needs {r} devices; JAX found {len(devices)}")
        cfg = BatchedConfig(
            num_groups=self.groups,
            num_replicas=r,
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
        nodes = devices[:r]
        self.eng = eng = MultiRaftEngine(
            cfg, spare=load["first_spare_node"], nodes=nodes)
        self.cfg = cfg = eng.cfg
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
        # Lanes that crossed, as each call ended: [0] is before the
        # first.
        self.crossed = [eng.lane_exchanges().tolist()]
        self.call()  # warm-up: the window's own program and arguments
        fence(eng)
        self._mark("open")
        say("engine", build_elect_warm_s=time.perf_counter() - t0,
            deliver=cfg.deliver_shape, lanes_minor=cfg.lanes_minor,
            first_spare_node=load["first_spare_node"],
            leaders_per_slot=np.bincount(slots, minlength=r).tolist())
        say("nodes", nodes=[
            {"id": d.id, "coords": list(getattr(d, "coords", ())),
             "kind": d.device_kind} for d in nodes],
            rows_a_node=self.groups, tiles=eng._tiles,
            tile_rows=eng.tile_rows)

    def call(self) -> None:
        super().call()
        self.crossed.append(self.eng.lane_exchanges().tolist())

    def _mark(self, name: str) -> None:
        super()._mark(name)
        self.marks[name]["call"] = len(self.crossed) - 1

    def window_counters(self) -> dict:
        """``engine_replace``'s, and for ``readers/nodes.py`` the lanes
        that had crossed the interconnect as each call ended, with the
        calls at which the window opened and closed and the shapes one
        exchange has."""
        shape = exchange_shape(self.eng)
        return dict(super().window_counters(), ici={
            "after_call": [list(c) for c in self.crossed],
            "open": self.marks["open"]["call"],
            "close": self.marks["close"]["call"],
            "tile_rows": int(self.eng.tile_rows),
            "tiles": int(self.eng._tiles),
            "replicas": shape["replicas"],
            "slot_bytes": shape["slot_bytes"],
        })

    def read_state(self) -> dict:
        """The base class's, every array in the logical order (the
        history comes in it already)."""
        return {f: v if f == "history" else self.eng.logical(v)
                for f, v in super().read_state().items()}

    def _step_reference(self, load, g, _cluster, no_confstate: bool):
        from ..reference.shadow_replace_nodes import NodesCluster

        return super()._step_reference(load, g, NodesCluster, no_confstate)
