"""Host-facing wrapper around the batched step kernel.

``MultiRaftEngine`` is the BatchedRawNode of the north star: it keeps
the full multi-group SoA state on device, exposes the same logical
contract as ``raft.RawNode`` (tick / campaign / propose / step / ready
watermarks / advance) but batched over every group at once, and runs
closed-loop rounds entirely on device (deliver → tick → propose → emit →
route), faults included: a scan takes a per-round schedule of nodes cut
off the network (``run_rounds(isolate=...)``), so an outage begins and
heals inside one program. Inside a scan the network moves only what
was sent: the inbox rides as its six kind lanes and a round exchanges
the lanes some instance of the batch wrote (``step.route_lanes``, on the
occupancy vector deliver's lane conds skip on); a lane nobody wrote
holds what ``empty_msgs`` holds, ``valid`` false and every field zero,
in the scan and in ``eng.inbox`` after it (``lane_rounds()`` counts the
rounds each lane was occupied, so exchanged). Entry payloads never touch the device: the host keeps them in
an arena keyed by (group, index), and the commit watermarks streaming
back from the device drive payload application — mirroring how the
reference applies committed entries after the Ready loop (ref:
server/etcdserver/raft.go:158-315).
"""

from __future__ import annotations

import itertools
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.sentinels import note_compile_key, warm_guard
from ..obs import spans
from .compile_cache import enable_compile_cache

# Never-reused engine identity for transfer-guard warm keys (itertools
# .count is atomic under the GIL).
_ENGINE_SERIAL = itertools.count()
from .state import BatchedConfig, BatchedState, init_state, LEADER, I32
from .step import (MsgSlots, NUM_KINDS, empty_msgs, lane_occupancy,
                   make_step_round, route, route_lanes, split_lanes,
                   stack_lanes)


class MultiRaftEngine:
    """Host calls are spans of the round-span recorder (obs/spans.py):
    ``engine.init``, ``engine.step_round`` and ``engine.run_rounds``
    (one a scan, so one a chunk of ``run_rounds_pipelined``), with
    member 0, the call's number as ``round`` and the engine's serial,
    the scan's ``rounds`` and ``isolated`` (rounds x nodes its fault
    schedule cut off; 0 with none) as stats. A span ends when the
    program is enqueued: the host's share of a call, not the device's."""

    def __init__(self, cfg: BatchedConfig, start_index: int = 0):
        self._serial = next(_ENGINE_SERIAL)
        self._calls = 0  # spans opened: the id the next one takes
        with self._span("engine.init"):
            self._init(cfg, start_index)

    def _span(self, name: str, **stats) -> "spans.Span":
        call = self._calls
        self._calls = call + 1
        return spans.span(name, 0, call, engine=self._serial, **stats)

    def _init(self, cfg: BatchedConfig, start_index: int) -> None:
        # deliver_shape="auto" becomes "vectorized" here, so self.cfg
        # reads as the compile key does.
        self.cfg = cfg = cfg.validate().resolved()
        # Round programs are expensive to build; cache compilations
        # across processes.
        enable_compile_cache()
        self.state = init_state(cfg, start_index)
        self.inbox = empty_msgs(
            (cfg.num_instances, cfg.num_replicas, NUM_KINDS),
            cfg.max_ents_per_msg,
            narrow=cfg.narrow_lanes,
        )
        self._step = make_step_round(cfg)

        def step_round(st, inbox, *masks):
            # The eager round hands the round program what the scan
            # hands it, lanes and their occupancy, so the two share
            # one trace of it (a cold start traces the round once,
            # not twice). `_step` is read when this is first traced.
            lanes = split_lanes(inbox)
            return self._step(st, lanes, *masks,
                              lane_any=lane_occupancy(lanes))

        self._round = jax.jit(step_round)
        n = cfg.num_instances
        self._zeros_b = jnp.zeros((n,), bool)
        self._zeros_i = jnp.zeros((n,), I32)
        # Scan rounds in which each kind lane held a message for any
        # instance (lane_rounds()): carried through the closed loop.
        self._lanes = jnp.zeros((NUM_KINDS,), I32)
        # In-device telemetry accumulator (cfg.telemetry): per-instance
        # counter totals + OR-folded invariant bitmaps, accumulated
        # inside the closed-loop scan with no per-round host sync.
        if cfg.telemetry:
            from .telemetry import NUM_COUNTERS

            self._tel_counters = jnp.zeros((n, NUM_COUNTERS), I32)
            self._tel_invariants = jnp.zeros((n,), I32)
        self.telemetry_hub = None
        # Step output positions past (state, outbox): aux is absent on
        # the engine's step (with_aux=False), then telemetry, then the
        # fleet summary vector — indexed here once instead of fragile
        # out[-1] reads that break when a second plane is on.
        self._tel_pos = 2
        self._fleet_pos = 2 + (1 if cfg.telemetry else 0)
        # In-device fleet-summary accumulator (cfg.fleet_summary): one
        # flat [L] i32 frame; delta fields (sum_mask) add across
        # rounds, snapshot fields keep the latest round's value — both
        # inside the scan carry, zero per-round host sync.
        if cfg.fleet_summary:
            from ..obs.fleet import FleetLayout

            self._fleet_layout = FleetLayout(
                n, cfg.num_replicas, cfg.num_groups)
            self._fleet_vec = jnp.zeros((self._fleet_layout.size,), I32)
            self._fleet_summask = jnp.asarray(
                self._fleet_layout.sum_mask())
            # The device carry is i32 and its ACC_SUM fields aggregate
            # ALL rows into a few buckets (hist_commit_delta gains N
            # counts per round), so an undrained closed loop would
            # wrap after ~2^31/N rounds at large G — silently, and
            # ingest_totals' delta clamp would then eat every later
            # frame. drain_fleet() folds the device sums into this
            # i64 host base and RESETS them, so the public totals are
            # unbounded while the on-device window stays small; any
            # consumer that reads the histograms drains periodically
            # (the hosted path ingests per round and never uses this).
            self._fleet_base = np.zeros(self._fleet_layout.size,
                                        np.int64)
            self._fleet_sum_np = self._fleet_layout.sum_mask()
        self.fleet_hub = None

        def closed_loop(st, inbox, ticks, props, tel, flt, lanes, isolate,
                        rounds):
            # `isolate` is None (no fault: the scan is traced as it
            # always was) or the bool [rounds, R] node schedule, one
            # row a round as the scan's xs.
            # jitlint: waive(tracer-branch) -- None is an empty pytree: the branch is on the argument's structure at trace time, never on a device value
            if isolate is not None:
                slots = jnp.arange(n, dtype=I32) % cfg.num_replicas

            def body(carry, cut):
                # `occ` is the inbox's lane occupancy, [K] bool: what
                # deliver's lane conds skip on and route_lanes' are
                # told was there.
                st, inbox, occ, tel, flt, lanes = carry
                lanes = lanes + occ
                iso = self._zeros_b
                # jitlint: waive(tracer-branch) -- as above: a scan without xs hands its body None
                if cut is not None:
                    # Row t widened to [N] where it is used: node s is
                    # slot s of every group.
                    for s in range(cfg.num_replicas):
                        iso = iso | ((slots == s) & cut[s])
                out = self._step(
                    st, inbox, ticks, self._zeros_b, props, iso,
                    self._zeros_i, self._zeros_b, lane_any=occ,
                )
                st, outbox = out[:2]
                if cfg.telemetry:
                    fr = out[self._tel_pos]
                    tel = (tel[0] + fr.counters, tel[1] | fr.invariants)
                if cfg.fleet_summary:
                    fv = out[self._fleet_pos]
                    flt = jnp.where(self._fleet_summask, flt + fv, fv)
                # The lanes somebody wrote this round are exchanged,
                # those that held last round's messages wiped, the rest
                # left as they are (step.route_lanes). The exchange
                # permutes slots inside a lane, so the outbox's
                # occupancy is the next inbox's.
                sent = jnp.any(outbox.valid, axis=(0, 1))
                inbox = route_lanes(cfg, outbox, sent, (inbox, occ))
                return (st, inbox, sent, tel, flt, lanes), None

            # The inbox rides the scan as its K kind lanes, each an
            # array of its own, and is stacked back once at the exit.
            # A caller's inbox may hold anything in a lane with no
            # valid slot (the eager round exchanges emit's unsent
            # request fields too), so such a lane is wiped here, once
            # a call: inside the scan an empty lane is all zeros.
            inbox = split_lanes(inbox)
            occ = lane_occupancy(inbox)
            inbox = tuple(
                jax.tree.map(
                    lambda x, _k=k: jnp.where(occ[_k], x, jnp.zeros_like(x)),
                    inbox[k])
                for k in range(NUM_KINDS))
            (st, inbox, _, tel, flt, lanes), _ = jax.lax.scan(
                body, (st, inbox, occ, tel, flt, lanes), isolate,
                length=rounds
            )
            inbox = stack_lanes(inbox)
            # The scalar fence is a SEPARATE output buffer: pipelined
            # callers block on it to bound queue depth without holding
            # (and thereby breaking) a donated state buffer.
            return st, inbox, tel, flt, lanes, st.commit[0]

        # State and inbox are donated: run_rounds/run_rounds_pipelined
        # reassign both from the return value, so XLA writes round k+1
        # into round k-1's freed SoA buffers instead of allocating.
        # (The telemetry accumulator rides the carry undonated — it is
        # tiny next to the SoA state and donation would complicate the
        # telemetry-off path, which must stay byte-identical.)
        self._closed_loop = jax.jit(
            closed_loop, static_argnames=("rounds",), donate_argnums=(0, 1)
        )
        note_compile_key("closed_loop", f"{cfg}")
        # Transfer-guard warm keys (analysis.sentinels): the guard wraps
        # dispatch only AFTER a (program, statics) pair has compiled
        # once — compilation legitimately transfers host constants. The
        # round program is shared per config (step._step_round_jit), so
        # its warmth is keyed by config, not engine identity; the
        # per-engine closed-loop wrapper is keyed by a monotonic serial
        # (NOT id(self): CPython reuses freed addresses, and a stale
        # warm key would put a new engine's compile inside the guard).
        self._wkey_step = f"round_step/{hash((cfg, False, n))}"

    # -- driving --------------------------------------------------------------

    def step_round(
        self,
        tick: bool = False,
        campaign_mask: Optional[jnp.ndarray] = None,
        propose_n: Optional[jnp.ndarray] = None,
        isolate: Optional[jnp.ndarray] = None,
        transfer_to: Optional[jnp.ndarray] = None,
        read_req: Optional[jnp.ndarray] = None,
    ) -> None:
        """One round: deliver pending messages, optionally tick every
        instance, run host control ops (leader transfer, ReadIndex),
        append proposals on leaders, route the outbox. `isolate` cuts
        instances off the network for this round."""
        ticks = (
            jnp.ones_like(self._zeros_b) if tick else self._zeros_b
        )
        camp = campaign_mask if campaign_mask is not None else self._zeros_b
        props = propose_n if propose_n is not None else self._zeros_i
        iso = isolate if isolate is not None else self._zeros_b
        transfer = transfer_to if transfer_to is not None else self._zeros_i
        reads = read_req if read_req is not None else self._zeros_b
        # Inside the guard the dispatch must be all-device: any implicit
        # transfer (an eager scalar op, a stray host array) is a hard
        # error when ETCD_TPU_TRANSFER_GUARD=disallow (tests, benches).
        with self._span("engine.step_round"), warm_guard(self._wkey_step):
            out = self._round(
                self.state, self.inbox, ticks, camp, props, iso,
                transfer, reads,
            )
            self.state, outbox = out[:2]
            if self.cfg.telemetry:
                fr = out[self._tel_pos]
                self._tel_counters = self._tel_counters + fr.counters
                self._tel_invariants = self._tel_invariants | fr.invariants
            if self.cfg.fleet_summary:
                fv = out[self._fleet_pos]
                self._fleet_vec = jnp.where(
                    self._fleet_summask, self._fleet_vec + fv, fv)
            self.inbox = route(self.cfg, outbox)

    def _tel(self):
        """Telemetry carry for the closed loop (empty pytree when off)."""
        if self.cfg.telemetry:
            return (self._tel_counters, self._tel_invariants)
        return ()

    def _set_tel(self, tel) -> None:
        if self.cfg.telemetry:
            self._tel_counters, self._tel_invariants = tel

    def _flt(self):
        """Fleet-summary carry for the closed loop (empty when off)."""
        if self.cfg.fleet_summary:
            return self._fleet_vec
        return ()

    def _set_flt(self, flt) -> None:
        if self.cfg.fleet_summary:
            self._fleet_vec = flt

    def _schedule(self, isolate, rounds: int):
        """(device schedule or None, rounds x nodes cut) of a call."""
        if isolate is None:
            return None, 0
        sched = np.asarray(isolate, bool)
        if sched.shape != (rounds, self.cfg.num_replicas):
            raise ValueError(
                f"isolate must be [rounds, R] = "
                f"{(rounds, self.cfg.num_replicas)}, got {sched.shape}")
        return jnp.asarray(sched), int(sched.sum())

    def _scan(self, rounds: int, ticks, props, isolate):
        """One closed-loop scan enqueued; returns its scalar fence."""
        sched, isolated = self._schedule(isolate, rounds)
        # `rounds` is a static arg: each new value compiles a new scan
        # program (and so does the first call with a schedule), so
        # warmth (and thus the transfer guard) is per value.
        key = f"closed_loop/{self._serial}/{rounds}" + (
            "" if sched is None else "/isolate")
        with self._span("engine.run_rounds", rounds=rounds,
                        isolated=isolated), warm_guard(key):
            self.state, self.inbox, tel, flt, lanes, fence = self._closed_loop(
                self.state, self.inbox, ticks, props, self._tel(),
                self._flt(), self._lanes, sched, rounds
            )
        self._lanes = lanes
        self._set_tel(tel)
        self._set_flt(flt)
        return fence

    def run_rounds(self, rounds: int, tick: bool = True,
                   propose_n: Optional[jnp.ndarray] = None,
                   isolate=None) -> None:
        """Closed-loop simulation of `rounds` rounds, faults included,
        without leaving the device (one fused lax.scan program).
        `isolate`, bool [rounds, R], cuts node s (slot s of every
        group) off the network in round t where ``isolate[t, s]``: it
        neither receives nor sends, and keeps ticking — what
        ``step_round(isolate=...)`` does to single instances, as the
        scan's per-round input."""
        ticks = jnp.ones_like(self._zeros_b) if tick else self._zeros_b
        props = propose_n if propose_n is not None else self._zeros_i
        self._scan(rounds, ticks, props, isolate)

    def run_rounds_pipelined(self, rounds: int, chunk: int = 16,
                             depth: int = 2, tick: bool = True,
                             propose_n: Optional[jnp.ndarray] = None,
                             isolate=None) -> None:
        """Double-buffered round pipelining: split `rounds` into scan
        chunks and keep up to `depth` chunks in flight — chunk k+1 is
        enqueued while chunk k's scan executes, and because the state
        carry is donated, XLA writes chunk k+1's output into chunk
        k-1's freed buffers. Dispatch gaps between scans vanish without
        device memory growing with `rounds`.

        Blocking is on the per-chunk scalar fence (an independent
        output), never on donated state; the final chunk is left in
        flight — callers that need completion block on
        ``self.state.commit`` as usual. `isolate` is ``run_rounds``'
        node schedule over all `rounds`; each chunk takes its rows."""
        if rounds <= 0:
            return
        if chunk <= 0:
            # A non-positive chunk would dispatch zero-round scans
            # forever (done never advances) — a silent host hang.
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        ticks = jnp.ones_like(self._zeros_b) if tick else self._zeros_b
        props = propose_n if propose_n is not None else self._zeros_i
        fences: deque = deque()
        done = 0
        while done < rounds:
            n = min(chunk, rounds - done)
            fences.append(self._scan(
                n, ticks, props,
                None if isolate is None else isolate[done:done + n]))
            done += n
            while len(fences) > depth:
                # jitlint: waive(sync-in-loop) -- the sync IS the pipelining contract: block on the per-chunk scalar fence to bound queue depth at `depth` without holding a donated buffer
                jax.block_until_ready(fences.popleft())

    def campaign(self, instance_ids) -> None:
        mask = self._zeros_b.at[jnp.asarray(instance_ids)].set(True)
        self.step_round(campaign_mask=mask)

    def transfer_leader(self, leader_instance: int, target_slot: int) -> None:
        """Ask the leader instance to hand leadership to target_slot
        (ref: raft.go:1339 MsgTransferLeader on the leader)."""
        tr = self._zeros_i.at[leader_instance].set(target_slot + 1)
        self.step_round(transfer_to=tr)

    def read_index(self, instance_ids) -> None:
        """Open a ReadIndex batch on the given leader instances
        (ref: v3_server.go sendReadIndex → MsgReadIndex)."""
        req = self._zeros_b.at[jnp.asarray(instance_ids)].set(True)
        self.step_round(read_req=req)

    def read_states(self) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
        """(seq, index, ready) per instance — the ReadState watermarks
        the host read loop waits on (ref: read_only.go advance →
        Ready.ReadStates)."""
        return (
            np.asarray(self.state.read_seq),
            np.asarray(self.state.read_index),
            np.asarray(self.state.read_ready),
        )

    def set_membership(self, group: int, voters, voters_out=(),
                       learners=(), joint: bool = False) -> None:
        """Upload new membership masks for every replica row of `group`
        — the confchange apply point (ref: confchange/confchange.go
        EnterJoint/LeaveJoint/Simple; the host Changer computes the
        slot sets, the device only sees masks)."""
        r = self.cfg.num_replicas
        rows = jnp.arange(group * r, (group + 1) * r)

        def mask(slots) -> jnp.ndarray:
            slots = list(slots)  # materialize once: iterators welcome
            m = jnp.zeros((r,), bool)
            return m.at[jnp.asarray(slots, I32)].set(True) if slots else m

        vin, vout, lrn = mask(voters), mask(voters_out), mask(learners)
        st = self.state
        self.state = st._replace(
            voter=st.voter.at[rows].set(vin),
            voter_out=st.voter_out.at[rows].set(vout),
            learner=st.learner.at[rows].set(lrn),
            in_joint=st.in_joint.at[rows].set(bool(joint)),
        )

    # -- telemetry (device → host gather; cfg.telemetry only) -----------------

    def telemetry(self) -> "tuple[np.ndarray, np.ndarray]":
        """(counters [N, NUM_COUNTERS], invariants [N]) — monotone
        per-instance totals accumulated in-device since the last reset
        (column order: telemetry.TM_NAMES). One host gather; no
        per-round sync ever happened."""
        assert self.cfg.telemetry, "engine built with telemetry=False"
        return (np.asarray(self._tel_counters),
                np.asarray(self._tel_invariants))

    def drain_telemetry(self, hub=None) -> "tuple[np.ndarray, np.ndarray]":
        """Fold the accumulated totals into `hub` (or the attached
        ``telemetry_hub``) via its monotone-totals path; returns the
        fetched (counters, invariants)."""
        counters, inv = self.telemetry()
        hub = hub or self.telemetry_hub
        if hub is not None:
            hub.ingest_totals(counters, inv)
        return counters, inv

    # -- fleet summary (device → host gather; cfg.fleet_summary only) ---------

    def fleet_frame(self) -> np.ndarray:
        """The accumulated [L] SummaryFrame (obs/fleet.FleetLayout
        order, int64): delta fields are monotone sums across rounds
        (device window + drained i64 base — see __init__), snapshot
        fields hold the LAST round's census/top-k. One host gather; no
        per-round sync ever happened."""
        assert self.cfg.fleet_summary, (
            "engine built with fleet_summary=False")
        vec = np.asarray(self._fleet_vec).astype(np.int64)
        return np.where(self._fleet_sum_np, self._fleet_base + vec, vec)

    def drain_fleet(self, hub=None) -> np.ndarray:
        """Fold the accumulated frame into `hub` (or the attached
        ``fleet_hub``) via its monotone-totals path, then bank the
        device window's sums into the i64 base and reset them on
        device (bounds the i32 carry far below wrap); returns the
        fetched monotone vector."""
        dev = np.asarray(self._fleet_vec).astype(np.int64)
        vec = np.where(self._fleet_sum_np, self._fleet_base + dev, dev)
        hub = hub or self.fleet_hub
        if hub is not None:
            hub.ingest_totals(vec)
        self._fleet_base += np.where(self._fleet_sum_np, dev, 0)
        self._fleet_vec = jnp.where(
            self._fleet_summask, 0, self._fleet_vec)
        return vec

    # -- observation (device → host gathers, debug/Ready watermarks) ----------

    def leaders(self) -> np.ndarray:
        """Per group: leader replica slot, or -1."""
        role = np.asarray(self.state.role).reshape(
            self.cfg.num_groups, self.cfg.num_replicas
        )
        is_lead = role == LEADER
        return np.where(is_lead.any(axis=1), is_lead.argmax(axis=1), -1)

    def lane_rounds(self) -> np.ndarray:
        """[NUM_KINDS] closed-loop rounds (``run_rounds``,
        ``run_rounds_pipelined``; ``step_round`` is not counted) in
        which each inbox lane — vote, append, heartbeat and their
        responses, in kind order — held a message for any instance:
        the rounds in which deliver ran that lane's fold for the
        batch (the lane skip, step._deliver_vectorized) and the
        rounds before them in which route() exchanged it
        (step.route_lanes; lanes run a round over 6 is the share of
        the exchange that ran). Accumulated in the scan's carry; one
        host gather, no per-round sync."""
        return np.asarray(self._lanes)

    def commits(self) -> np.ndarray:
        """Per-instance commit watermarks [G, R] — the host applies
        payloads from its arena up to these."""
        return np.asarray(self.state.commit).reshape(
            self.cfg.num_groups, self.cfg.num_replicas
        )

    def terms(self) -> np.ndarray:
        return np.asarray(self.state.term).reshape(
            self.cfg.num_groups, self.cfg.num_replicas
        )
