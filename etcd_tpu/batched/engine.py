"""Host-facing wrapper around the batched step kernel.

``MultiRaftEngine`` is the BatchedRawNode of the north star: it keeps
the full multi-group SoA state on device, exposes the same logical
contract as ``raft.RawNode`` (tick / campaign / propose / step / ready
watermarks / advance) but batched over every group at once, and runs
closed-loop rounds entirely on device (deliver → tick → propose → emit →
route), faults and the control plane included: a scan takes a per-round
schedule of nodes cut off the network (``run_rounds(isolate=...)``) and,
beside it, one of what the control plane asks (``control=...``: which
node hands its leaderships to which, which configuration change is on
offer, whether reads are asked), so an outage begins and heals, a
leadership moves, a ReadIndex batch opens and confirms, and a
configuration change is appended and applied by each replica at its own
apply point (``BatchedConfig.conf_entries``) inside one program; with
``BatchedConfig.replace_replicas`` a group may keep a slot empty
(``MultiRaftEngine(cfg, spare=...)``), a fresh replica joins there as a
learner and is carried by a snapshot that states the configuration, a
node is retired and its slot reset (``CTL_RETIRE``, ``CTL_WIPE``), all
inside the scan. The control schedule is a row a round for every group
alike, or *phased* (``run_rounds(control=cycle, starts=...)``): one
cycle's rows and the round at which each group enters it, so that a
rebalancer's batch of groups moves while every other group runs steady
beside it; or the scan draws what each group is offered itself
(``run_rounds(load=...)``: a *load plane*, two thresholds a group and a
seed, so that a few hot groups append in every round while most only
heartbeat). Inside
a scan the network moves only what
was sent: inbox and outbox ride as six kind lanes (each with the
fields its messages use alone, ``step.LANE_FIELDS``) and a round exchanges
the lanes some instance of the batch wrote (``step.route_lanes``, on the
occupancy vector deliver's lane conds skip on); a lane nobody wrote
holds what ``empty_msgs`` holds, ``valid`` false and every field zero,
in the scan and in ``eng.inbox`` after it (``lane_rounds()`` counts the
rounds each lane was occupied, so exchanged; ``rare_rounds()`` those in
which a heartbeat lane held the rare message type that makes deliver
run its whole handler, ``step.lane_occupancy``; ``emit_ring_rounds()``
the tile-rounds in which emit read the log ring for the terms its
messages state, ``step._emit``; ``bulk_rounds()`` the rounds in which
an append stated more entries than the head of a split append lane
holds, so that deliver ran the lane whole, emit built the entries' tail
and the exchange moved it: ``step.app_head``, a configuration whose
appends are sized for catch-up, E=64 for P=2; in every other round the
lane is the head's few columns and the tail rides the carry untouched).
A call over more rows
than one tile holds (``scan_tiles``: TILE_ROWS, from the shape alone)
runs tile by tile: a tile is a block of whole groups, adjacent rows of
``eng.state`` (row ``g * R + s`` as ever: the row order does not
change), stepped through all the call's rounds before the next tile
sees its first, so that the chip works on a block small enough for its
fast memory; groups share nothing, so the result is the one scan's.
What crosses tiles is put together once a call: ``lane_rounds()``
counts a round for a lane that held a message in any tile, the
``scan_watch()`` counts add up, the fence is the last tile's; the lane
skip is each tile's own, so a lane nobody of a tile wrote is zeros on
that tile's rows. The eager round runs in the same tiles (and donates
state and inbox there), so a configuration traces the round at one
shape. Given the devices that are its nodes (``nodes=``) the engine
places slot s of every group on device s: a node's rows are G (a row a
group), the nodes walk the same tiles of groups together, and a tile's
round ends in the exchange between them, one all-to-all a kind lane
over the interconnect for the lanes some node wrote
(``step.exchange_lanes``; the nodes agree on the occupancy first,
``step.agree_lanes``, because a node cut off writes nothing where its
peers do and a collective in a branch only some take never returns),
counted in the carry like the lanes (``lane_exchanges()``); a node cut
off, retired or wiped is a device's rows; the state then lives node
after node (row ``s * G + g``) and every method takes and hands back
the logical order. Entry payloads never touch the device: the host keeps them in
an arena keyed by (group, index), and the commit watermarks streaming
back from the device drive payload application — mirroring how the
reference applies committed entries after the Ready loop (ref:
server/etcdserver/raft.go:158-315).
"""

from __future__ import annotations

import functools
import itertools
from collections import deque
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis.sentinels import note_compile_key, warm_guard
from ..obs import spans
from .compile_cache import enable_compile_cache

# Never-reused engine identity for transfer-guard warm keys (itertools
# .count is atomic under the GIL).
_ENGINE_SERIAL = itertools.count()
from .state import (CANDIDATE, CONF_SWAP, LEADER, PRECANDIDATE, REPLICATE,
                    BatchedConfig, BatchedState, I32, conf_decode, init_state)
from .step import (BULK_APP, KIND_APP, MsgSlots, NUM_KINDS, NUM_OCC, T_APP,
                   agree_lanes, app_head, empty_msgs, exchange_lanes,
                   lane_occupancy, make_step_round, route, route_lanes,
                   settled, split_lanes, stack_lanes)


# Columns of a scan's control schedule, int32 [rounds, CTL_COLS], one
# row a round (``run_rounds(control=...)``). A row asks every leader on
# node CTL_FROM - 1 (slot s of every group is node s) to hand
# leadership to slot CTL_TO - 1 (0: no transfer), offers every other
# instance the configuration change CTL_CONF (a ``state.conf_code``; 0
# none; the node that is asked to hand over is being drained and is not
# offered it), asks every instance for a read where CTL_READS is not 0,
# and, where CTL_STALL is not 0, says that no replica in a joint
# configuration may advance its commit in this round (``scan_watch``
# counts those that do).
CTL_FROM, CTL_TO, CTL_CONF, CTL_READS, CTL_STALL = range(5)
CTL_COLS = 5
# Two columns more for a configuration with ``replace_replicas``
# (``control_cols``; every other configuration's schedule, and so its
# compiled scan, keeps the five): node CTL_RETIRE - 1 is switched off
# in this round, cut off both ways as ``isolate`` cuts a node off
# (0: none; a retired machine is no network fault, and the span says
# which was which), and every replica on node CTL_WIPE - 1 is reset to
# the empty replica in this round's control phase (0: none): the slot
# of a retired machine handed to a fresh process. CTL_CONF holds the
# wide ``state.conf_code`` there, second slot included.
CTL_RETIRE, CTL_WIPE = 5, 6
# A *phased* schedule (``run_rounds(control=..., starts=...)``) is one
# cycle's rows and, a group, the round at which it enters the cycle: a
# rebalancer that moves a few ranges at a time, where the form above
# moves every group in the same round. `control` is then int32
# [cycle_rounds, control_cols], the columns as they are, and `starts`
# int32 [num_groups], counted in the rounds the engine's phased scans
# have run (``MultiRaftEngine.phase_round``, carried across calls). In
# round t group g reads row ``t - starts[g]`` of the cycle for what is a
# group's own (CTL_FROM, CTL_TO, CTL_CONF, CTL_RETIRE, CTL_WIPE); a
# group whose start is in the future (NEVER: never) or whose cycle has
# ended reads the steady row: no transfer, no change on offer, nobody
# retired, nothing wiped. What is a round's and no group's (CTL_READS,
# CTL_STALL) is read per round, from row ``t mod cycle_rounds``. On the
# device the cycle is its runs of equal rows (`_cycle_runs`: an edge a
# run) and a row of the batch finds its own by as many compares on
# ``t - start`` (scope ``raft_phase``); the scan's per-round input is
# three scalars, in these columns.
PH_ROUND, PH_READS, PH_STALL = range(3)
NEVER = np.iinfo(np.int32).max
# A *load plane* (``run_rounds(load=(update_thr, read_thr, seed))``) is
# the third form of a scan's input: no two groups are offered the same
# thing. `update_thr` and `read_thr` are uint32 [num_groups], `seed` an
# integer below 2**32. In round t, counted in the rounds the engine's
# load scans have run (``MultiRaftEngine.load_round``, carried across
# calls), group g draws one 32-bit word a stream,
#
#     base = fmix32(seed + t * LOAD_ROUND_MUL)
#     u_k  = fmix32(base ^ (g * LOAD_GROUP_MUL + k * LOAD_STREAM_MUL))
#
# in uint32 arithmetic (``fmix32``: murmur3's finalizer), and is offered
# one update for each of the streams k = 0 .. max_props_per_round - 1
# with ``u_k < update_thr[g]``, and a read where stream
# max_props_per_round has ``u < read_thr[g]``. A threshold of
# LOAD_ALWAYS is one no draw can miss (a probability of exactly 1), 0
# one none can meet. The offer goes to every replica of the group
# (`_propose` appends on a leader only). The scan's per-round input is
# the round's number, one scalar; the thresholds are widened to rows
# once a call and sliced once a tile (scope ``raft_load``).
LOAD_ROUND_MUL, LOAD_GROUP_MUL, LOAD_STREAM_MUL = (
    0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D)
LOAD_ALWAYS = 0xFFFFFFFF
# What a scan with a load plane counts in its carry
# (``MultiRaftEngine.load_counts``), a group and not a row, in this
# order: updates offered, group-rounds in which a read was asked, and
# group-rounds in which either was.
LOAD_COUNT_NAMES = ("offered", "reads_asked", "active")
U32 = jnp.uint32


def fmix32(x):
    """murmur3's 32-bit finalizer on uint32 (wraps as the device's
    arithmetic does)."""
    x = (x ^ (x >> 16)) * U32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * U32(0xC2B2AE35)
    return x ^ (x >> 16)


def load_base(seed, t):
    """A load plane's word of round `t` (the LOAD_* comment above):
    uint32 `seed`, any integer `t`."""
    return fmix32(seed + t.astype(U32) * U32(LOAD_ROUND_MUL))


def load_word(base, group_key, k: int):
    """Stream `k`'s 32-bit draw of the groups whose keys (``g *
    LOAD_GROUP_MUL``, uint32) `group_key` holds, in the round whose
    word is `base`."""
    return fmix32(base ^ (group_key + U32(k * LOAD_STREAM_MUL & LOAD_ALWAYS)))


def _add_limbs(counts, add):
    """[K, 2] two-limb counts (high, low: `_LIMB`) and [K] more."""
    low = counts[:, 1] + add
    return jnp.stack(
        [counts[:, 0] + (low >> _LIMB), low & ((1 << _LIMB) - 1)], axis=1)


def _limbs_total(counts) -> np.ndarray:
    """[..., 2] two-limb counts as int64 [...]."""
    c = np.asarray(counts).astype(np.int64)
    return (c[..., 0] << _LIMB) + c[..., 1]


# What the closed loop counts of catch-up, a round, in its carry (with
# cfg.log_runs; ``catchup_counts``): instance-rounds in which a replica
# that is not cut off stands more than E below its group's commit;
# appends that leave with their previous index more than E below the
# commit they state (to a peer not yet level); the entries those carry.
CATCHUP_NAMES = ("behind_rounds", "catchup_appends", "catchup_entries")


def group_max(x, slots, r: int):
    """[N]: the largest `x` among the R rows of each row's group (rows
    ``g * R + s``, `slots` each row's s), on N whole: the R - 1 rows
    either side, shifted and masked to the row's own group (route()'s
    idiom; no [G, R] reshape takes N out of the lanes)."""
    n = x.shape[0]
    xp = jnp.pad(x, (r - 1, r - 1))
    out = x
    for d in range(1 - r, r):
        if d:
            inside = (slots + d >= 0) & (slots + d < r)
            out = jnp.where(
                inside, jnp.maximum(out, xp[r - 1 + d:r - 1 + d + n]), out)
    return out


def catchup_round(cfg: BatchedConfig, st, appends: MsgSlots, iso, slots):
    """[3] int32, CATCHUP_NAMES of one round: the state after
    it, the append lane of its outbox as it leaves (a row cut off sends
    nothing) and the rows cut off in it."""
    e = cfg.max_ents_per_msg
    behind = ~iso & (
        group_max(st.commit, slots, cfg.num_replicas) - st.commit > e)
    deep = (appends.valid & (appends.type == T_APP)
            & (appends.index + e < appends.commit))
    return jnp.stack([
        jnp.sum(behind.astype(I32)), jnp.sum(deep.astype(I32)),
        jnp.sum(jnp.where(deep, appends.n_ents.astype(I32), 0))])


def control_cols(cfg: BatchedConfig) -> int:
    """The width of `cfg`'s control schedule."""
    return CTL_COLS + (2 if cfg.replace_replicas else 0)


def _cycle_runs(cycle: np.ndarray):
    """A phased schedule's cycle as its runs of equal rows, in what is
    a group's own (the round's columns zeroed): (edges [E], runs
    [E, cols]), run j from cycle round ``edges[j]`` on; the last, from
    the cycle's end on, is the steady row, all zeros."""
    own = cycle.astype(np.int32)
    own[:, [CTL_READS, CTL_STALL]] = 0
    first = np.ones(len(own), bool)
    first[1:] = (own[1:] != own[:-1]).any(axis=1)
    edges = np.append(np.flatnonzero(first), len(own))
    return edges, np.concatenate([own[first], np.zeros_like(own[:1])])


# What a scan with a control schedule counts in its carry
# (``MultiRaftEngine.scan_watch``), in this order.
WATCH_NAMES = (
    "joint_instance_rounds",   # instance-rounds in a joint configuration
    "read_open_instance_rounds",  # instance-rounds with a read batch open
    "reads_below_commit",      # batches confirmed with an index below the
    # highest commit a replica of the group held before the batch opened
    "joint_commits_in_stall",  # commit advances in a joint configuration
    # in a round the schedule marks CTL_STALL
    "conf_marks_lost",         # an unapplied change's mark overwritten
)
# What it counts besides for a configuration with ``replace_replicas``
# (``watch_names``), after those.
REPLACE_WATCH_NAMES = (
    "outsider_votes_or_campaigns",  # a campaign begun or a vote granted
    # by a replica whose own configuration does not name it as a voter
    # (before the round and after it)
    "swaps_before_ready",      # a CONF_SWAP taken by a leader whose row
    # for the learner was not REPLICATE with a match at the commit the
    # leader held as the round began
    "conf_restores",           # snapshots that restored a configuration
    "replicas_reset",          # instances the control phase wiped
    "learner_rounds_short_of_replicate",  # instance-rounds a learner
    # spent in PROBE or SNAPSHOT in its leader's row
    "swaps_taken",             # CONF_SWAP entries appended
)


def watch_names(cfg: BatchedConfig):
    return WATCH_NAMES + (
        REPLACE_WATCH_NAMES if cfg.replace_replicas else ())


# A count is two int32 limbs, low 24 bits and the rest: a round adds at
# most N to one, and N rounds x instances passes 2^31 inside a run.
_LIMB = 24


# The fields a controlled scan folds into each instance's history,
# after every round, in this order (the membership masks as bits, slot
# s worth 1 << s): `history_fold` below is the rule, in Python
# integers, for whoever steps a reference through the same rounds.
HISTORY_FIELDS = ("term", "role", "lead", "commit", "last", "read_seq",
                  "read_index", "read_ready", "in_joint", "voter",
                  "voter_out", "learner")
_FNV = 16777619


def history_fold(h: int, values) -> int:
    """One round folded into a history: `values` are HISTORY_FIELDS'
    of the instance after the round (FNV-1a over 32-bit words)."""
    for v in values:
        h = ((h ^ (int(v) & 0xFFFFFFFF)) * _FNV) & 0xFFFFFFFF
    return h


# The closed loop steps a tile of whole groups through all of a call's
# rounds, then the next tile (``scan_tiles``; ``MultiRaftEngine._init``'s
# ``closed_loop``). A tile holds at most TILE_ROWS instance rows, the
# one size the chip has priced (PERF.md section 6, "PR 35": a round's
# intermediates at that size stay in the compiler's fast memory, at
# millions of rows every fusion streams from HBM), and a multiple of
# TILE_ALIGN of them: the TPU's T(1024) / T(8,128) tiling of an [N] /
# [N, W] array, so a tile is a contiguous block and never a relayout.
TILE_ROWS = 196_608
TILE_ALIGN = 1_024


def scan_tiles(cfg: BatchedConfig, nodes: bool = False) -> int:
    """How many tiles `cfg`'s closed loop runs in: the fewest, so the
    largest, that are whole groups, equal, TILE_ALIGN-aligned and no
    larger than TILE_ROWS. 1 — the one scan over all rows — for a shape
    of less than two tiles' rows (at 307,200 rows two tiles gained 5.9%
    a round and cost a quarter more warm set-up, the round traced and
    fetched at a second shape: PERF.md section 6, "PR 35"), one with no
    such divisor, and a configuration with ``fleet_summary`` (its frame
    reduces across rows with fields that do not add). With `nodes`
    (``MultiRaftEngine(nodes=...)``) the rule is held to one node's
    rows, a row a group: every node walks the same tiles of groups."""
    n, unit = ((cfg.num_groups, 1) if nodes
               else (cfg.num_instances, cfg.num_replicas))
    if n < 2 * TILE_ROWS or cfg.fleet_summary:
        return 1
    for tiles in range(-(-n // TILE_ROWS), n // TILE_ALIGN + 1):
        rows, rest = divmod(n, tiles)
        if not (rest or rows % unit or rows % TILE_ALIGN):
            return tiles
    return 1


# The mesh axis of an engine placed over nodes: device s of it holds
# replica slot s of every group.
NODE_AXIS = "node"


class ScanWatch(NamedTuple):
    counts: jnp.ndarray  # [len(WATCH_NAMES), 2] i32 (high, low limb)
    # Per instance: the highest commit any replica of its group held at
    # the end of the round before its open read batch opened.
    read_floor: jnp.ndarray  # [N] i32
    # Per instance: a hash of its state after every round of every
    # controlled scan so far. Two runs agree on it only if they agreed
    # round by round, which the state after the last round cannot say
    # (a commit that ran ahead through an outage has been caught up
    # with by then).
    history: jnp.ndarray  # [N] u32


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _to_placed(x, g: int, r: int, by_node):
    """[G*R, ...] in the logical order as [R*G, ...] in placed order,
    split by node."""
    y = x.reshape((g, r) + x.shape[1:]).swapaxes(0, 1).reshape(x.shape)
    return jax.lax.with_sharding_constraint(y, by_node)


class MultiRaftEngine:
    """Host calls are spans of the round-span recorder (obs/spans.py):
    ``engine.init``, ``engine.step_round`` and ``engine.run_rounds``
    (one a scan, so one a chunk of ``run_rounds_pipelined``), with
    member 0, the call's number as ``round`` and the engine's serial,
    the scan's ``rounds``, its ``tiles`` (``scan_tiles``; 1: one scan
    over all rows) and ``isolated`` (rounds x nodes its fault
    schedule cut off; 0 with none) and, of its control schedule,
    ``reads`` (rounds x instances asked), ``conf_ops`` and ``transfers``
    (rows that offer a change, ask for a hand-over; 0 with none) and,
    for a configuration with ``replace_replicas``, ``retired`` and
    ``wipes`` (rounds x nodes switched off, reset; 0 elsewhere) as
    stats; of a phased schedule those four count a round a batch in
    flight, and ``batches`` (in flight in the call) and ``started``
    (groups that have entered the cycle by its end) say which form it
    was; a scan with a load plane says ``load_from``, its first load
    round. A span ends when the
    program is enqueued: the host's share of a call, not the device's."""

    def __init__(self, cfg: BatchedConfig, start_index: int = 0,
                 spare=None, nodes=None):
        """`spare` (``cfg.replace_replicas``): the slot every group
        leaves empty, or one a group as [G]: ``state.init_state``.

        `nodes`: the devices that are the deployment's nodes, R of
        them. Device s then holds replica slot s of every group (G
        rows, a row a group), the round's exchange is an all-to-all
        between the devices inside the scan (``step.exchange_lanes``;
        ``route()`` does not run), a node cut off, retired or wiped is
        a device's rows, and every device walks the same tiles of
        groups together. An instance keeps its logical id
        ``g * R + s`` (the timeout hash reads it), so a group runs bit
        for bit as it does on one device. ``eng.state``, ``eng.inbox``
        and the accumulators are then arrays over the node mesh in
        placed order (row ``s * G + g``, sharded by node); every method
        takes and hands back per-instance values in the logical order
        (``logical`` reorders a placed array on the host). Without
        `nodes` nothing of this exists and the programs are what they
        were."""
        self._serial = next(_ENGINE_SERIAL)
        self._calls = 0  # spans opened: the id the next one takes
        with self._span("engine.init"):
            self._init(cfg, start_index, spare, nodes)

    def _span(self, name: str, **stats) -> "spans.Span":
        call = self._calls
        self._calls = call + 1
        return spans.span(name, 0, call, engine=self._serial, **stats)

    def _pretrace(self) -> "spans.Span":
        """The span over an abstract pre-trace of one tile's round
        (``jax.eval_shape`` in the two tiled paths): opened while the
        jit that needs it is being traced, so under the
        ``engine.step_round`` or ``engine.run_rounds`` that pays for
        it, whose ``(member, round)`` it takes; it numbers no call."""
        return spans.span("engine.pretrace", engine=self._serial)

    def _init(self, cfg: BatchedConfig, start_index: int, spare,
              nodes) -> None:
        # deliver_shape="auto" becomes "vectorized" here, so self.cfg
        # reads as the compile key does.
        self.cfg = cfg = cfg.validate().resolved()
        # Round programs are expensive to build; cache compilations
        # across processes.
        enable_compile_cache()
        r = cfg.num_replicas
        self._nodes = nodes = None if nodes is None else tuple(nodes)
        placed = nodes is not None
        if placed:
            if len(nodes) != r or len(set(nodes)) != r:
                raise ValueError(
                    f"nodes must be num_replicas = {r} distinct devices, "
                    f"got {len(nodes)}")
            if cfg.fleet_summary:
                raise ValueError(
                    "fleet_summary reduces across all rows of one device: "
                    "not with nodes")
            if cfg.log_runs:
                raise ValueError(
                    "log_runs counts a replica's catch-up against its "
                    "group's commit, a reduce over a group's rows on one "
                    "device: not with nodes")
            if app_head(cfg):
                raise ValueError(
                    f"max_ents_per_msg={cfg.max_ents_per_msg} with "
                    f"max_props_per_round={cfg.max_props_per_round} splits "
                    "the append lane (step.app_head), and emit builds the "
                    "tail on a bit of a node's own rows where the nodes "
                    "exchange it on one they agree on: not with nodes")
            mesh = Mesh(np.asarray(nodes), (NODE_AXIS,))
            # Of a per-instance array in placed order, and of what
            # every node holds whole.
            self._by_node = NamedSharding(mesh, P(NODE_AXIS))
            self._on_all = NamedSharding(mesh, P())
            with spans.span("engine.place", engine=self._serial, nodes=r):
                self._place_state(start_index, spare)
            self._step = None  # the untiled one-device round
        else:
            self.state = init_state(cfg, start_index, spare=spare)
            self.inbox = empty_msgs(
                (cfg.num_instances, cfg.num_replicas, NUM_KINDS),
                cfg.max_ents_per_msg,
                narrow=cfg.narrow_lanes,
            )
            self._step = make_step_round(cfg)

        # The rows one device holds: all, or one node's (a row a group).
        n = cfg.num_groups if placed else cfg.num_instances
        # One scan over all rows, or tile by tile (``scan_tiles``); the
        # eager round likewise, so that a configuration traces the
        # round at one shape. Placed over nodes the loops are the tile
        # loops whatever the number of tiles.
        self._tiles = tiles = scan_tiles(cfg, nodes=placed)
        self.tile_rows = rows = n // tiles
        # A split append lane's head (step.app_head; 0: not split), and
        # the occupancy vector's length with its bit.
        self._head = head = app_head(cfg)
        n_occ = NUM_OCC + (1 if head else 0)

        def row_slots():
            """The slot of each row of a tile: the node's own, or the
            row's place in its group."""
            if placed:
                return jnp.full((rows,), jax.lax.axis_index(NODE_AXIS), I32)
            return jnp.arange(rows, dtype=I32) % cfg.num_replicas

        def tile_step(lo, slots):
            """The round for the rows from `lo` on: the timeout hash
            reads the row's own id, ``g * R + s`` wherever it lives."""
            iids = lo + jnp.arange(rows, dtype=I32)
            if placed:
                iids = iids * r + slots
            return make_step_round(cfg, iids=iids, slots=slots)

        def like(x):  # a tile's share of a per-row array, as a shape
            return jax.ShapeDtypeStruct((rows,) + x.shape[1:], x.dtype)

        def over_nodes(fn, in_specs, out_specs):
            """`fn` as every node runs it on its own rows."""
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)

        def tiled_round(st, lanes, per_row):
            """The eager round tile by tile (`tiled_loop`'s idiom):
            `per_row` is (masks, conf_req, wipe), every leaf [N]."""
            with jax.named_scope("raft_carry"):
                slots = row_slots()

            def one(lo, st, lanes, per_row):
                masks, conf_req, wipe = per_row
                with jax.named_scope("raft_carry"):
                    # (Emit's bit, last, is the scan's to count. A split
                    # append lane's tail means something on the tile's
                    # own bit: settled here, tile by tile.)
                    out = tile_step(lo, slots)(
                        st, lanes, *masks, lane_any=lane_occupancy(lanes),
                        conf_req=conf_req, wipe=wipe)[:-1]
                    return (out[0], settled(out[1])) + out[2:]

            # The shapes of what a tile answers, for the outbox and the
            # frames the loop writes into; and the round traced once
            # outside any loop (see `tiled_loop`).
            with self._pretrace():
                answer = jax.eval_shape(
                    one, 0, *jax.tree.map(like, (st, lanes, per_row)))[1:]
            with jax.named_scope("raft_tiles"):
                whole = jax.tree.map(
                    lambda x: jnp.zeros((n,) + x.shape[1:], x.dtype),
                    answer)

            def tile(i, carry):
                with jax.named_scope("raft_tiles"):
                    lo = i * rows
                    mine = jax.tree.map(
                        lambda x: jax.lax.dynamic_slice_in_dim(x, lo, rows),
                        (carry[0], lanes, per_row))
                out = one(lo, *mine)
                with jax.named_scope("raft_tiles"):
                    return jax.tree.map(
                        lambda x, y: jax.lax.dynamic_update_slice_in_dim(
                            x, y, lo, 0),
                        carry, (out[0], out[1:]))

            st, out = jax.lax.fori_loop(0, tiles, tile, (st, whole))
            with jax.named_scope("raft_carry"):
                outbox = out[0]
                if placed:
                    # Every lane, nothing to agree on: what route()
                    # does after the one-device eager round.
                    outbox = exchange_lanes(outbox, NODE_AXIS)
                return (st, stack_lanes(outbox)) + out[1:]

        def step_round(st, inbox, *masks, conf_req=None, wipe=None):
            # The eager round hands the round program what the scan
            # hands it, lanes and their occupancy, so the two share
            # one trace of it (a cold start traces the round once,
            # not twice). `_step` is read when this is first traced.
            # `conf_req` is given for a configuration with
            # conf_entries alone, `wipe` for one with
            # replace_replicas; None is no input.
            # Handed lanes it answers in lanes; route(), a program
            # of its own here, takes them stacked.
            with jax.named_scope("raft_carry"):
                lanes = split_lanes(inbox, head)
            if tiles > 1 or placed:
                return tiled_round(st, lanes, (masks, conf_req, wipe))
            with jax.named_scope("raft_carry"):
                out = self._step(st, lanes, *masks,
                                 lane_any=lane_occupancy(lanes),
                                 conf_req=conf_req, wipe=wipe)
                return (out[0], stack_lanes(out[1])) + out[2:-1]

        # In tiles the loop's carry is the state: donated, it is updated
        # in place as the scan's is (`step_round` below reassigns state
        # and inbox from what comes back); not donated it would be a
        # second copy, a gigabyte more than the one round holds. One
        # tile keeps the parent's program, which donates nothing.
        if placed:
            one_node_round = step_round

            def step_round(st, inbox, *masks, conf_req=None, wipe=None):
                # What comes back in the outbox's place is the next
                # inbox: the exchange ran with the round.
                return over_nodes(
                    lambda st, inbox, masks, conf_req, wipe: one_node_round(
                        st, inbox, *masks, conf_req=conf_req, wipe=wipe),
                    P(NODE_AXIS), P(NODE_AXIS))(
                        st, inbox, masks, conf_req, wipe)

        self._round = jax.jit(
            step_round, donate_argnums=(0, 1) if tiles > 1 or placed else ())
        n_all = cfg.num_instances
        zeros = self._zeros
        self._zeros_b = zeros((n_all,), bool)
        self._zeros_i = zeros((n_all,), I32)
        # Scan rounds in which each kind lane held a message for any
        # instance (lane_rounds()) and, after the lanes, in which each
        # rare message type did (rare_rounds()): the occupancy vector
        # (step.lane_occupancy) added up through the closed loop.
        # Placed over nodes, beside it the tile-rounds in which each
        # lane crossed the interconnect (lane_exchanges()).
        # And, second of the pair, the tile-rounds in which emit read
        # the ring for the terms it states (emit_ring_rounds(); step._emit):
        # [1], or a count a node, [R].
        self._lanes = (jnp.zeros((n_occ,), I32), jnp.zeros((1,), I32))
        if placed:
            self._lanes = (
                (self._on_nodes(np.zeros((NUM_OCC,), np.int32)),
                 self._on_nodes(np.zeros((NUM_OCC,), np.int32))),
                zeros((r,), I32))
        # What the scans with a control schedule counted (scan_watch()):
        # made by the first of them, carried by every one after.
        self._watch: Optional[ScanWatch] = None
        # Rounds the scans with a phased schedule have run (what its
        # `starts` count in), and that schedule as the device holds it
        # (`_phased_schedule`).
        self.phase_round = 0
        self._phase: Optional[dict] = None
        # Rounds the scans with a load plane have run (what its draws
        # count in), that plane as the device holds it
        # (`_load_schedule`) and what those scans counted
        # (load_counts()): made by the first of them, so that an engine
        # that runs none builds no program more than it did.
        self.load_round = 0
        self._load: Optional[dict] = None
        self._tally = None
        # What the scans of a configuration with log_runs counted of
        # catch-up (catchup_counts()): CATCHUP_NAMES in two
        # limbs, in the carry where a load plane's counts ride (the two
        # do not meet: `_scan`), and the last row of the last fault
        # schedule, for the nodes a call heals in its first round.
        self._catchup = (jnp.zeros((len(CATCHUP_NAMES), 2), I32)
                         if cfg.log_runs else None)
        self._cut_last = np.zeros((r,), bool)
        # In-device telemetry accumulator (cfg.telemetry): per-instance
        # counter totals + OR-folded invariant bitmaps, accumulated
        # inside the closed-loop scan with no per-round host sync.
        if cfg.telemetry:
            from .telemetry import NUM_COUNTERS

            self._tel_counters = zeros((n_all, NUM_COUNTERS), I32)
            self._tel_invariants = zeros((n_all,), I32)
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
                n_all, cfg.num_replicas, cfg.num_groups)
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

        def widen_phased(phase, t, slots, zeros_i):
            """What round `t` of a phased schedule asks of each row:
            (transfer, conf, retired, wipe), the last three None where
            the configuration has no such input. `phase` is (edges [E],
            runs [E, cols], start [rows]): a row's cycle round is
            ``t - start``, its run the last whose edge is at or below
            it (none before the cycle begins; the last run, from the
            cycle's end on, is the steady row)."""
            edges, runs, start = phase
            k = t - start
            past = [k >= edges[j] for j in range(edges.shape[0])]

            def column(col):
                out = zeros_i
                for j, at_or_past in enumerate(past):
                    out = jnp.where(at_or_past, runs[j, col], out)
                return out

            drained = slots == column(CTL_FROM) - 1
            transfer = jnp.where(drained, column(CTL_TO), 0)
            conf = retired = wipe = None
            if cfg.conf_entries:
                conf = jnp.where(drained, 0, column(CTL_CONF))
            if cfg.replace_replicas:
                retired = slots == column(CTL_RETIRE) - 1
                wipe = slots == column(CTL_WIPE) - 1
            return transfer, conf, retired, wipe

        def load_plane(load, lo, slots):
            """A load plane for the rows from `lo` on, made once a
            call or a tile: `load` is (update_thr, read_thr, seed) with
            the thresholds a row each. Hands `draw_load` the
            thresholds, which of them no draw can miss, the rows' hash
            keys (their group's) and the rows that count for their
            group (its first)."""
            upd, rd, seed = load
            group = (lo + jnp.arange(upd.shape[0], dtype=I32)) // r
            return (upd, rd, upd == U32(LOAD_ALWAYS), rd == U32(LOAD_ALWAYS),
                    group.astype(U32) * U32(LOAD_GROUP_MUL), slots == 0, seed)

        def draw_load(plane, t, tally):
            """Round `t` of a load plane: each row's (updates offered
            [rows] i32, read asked [rows] bool), a group's replicas
            alike, and `tally` (LOAD_COUNT_NAMES, two limbs) with the
            round's counted in, a group once."""
            upd, rd, always_u, always_r, key, first, seed = plane
            base = load_base(seed, t)
            n_new = zeros = jnp.zeros(upd.shape, I32)
            for k in range(cfg.max_props_per_round):
                n_new = n_new + (
                    (load_word(base, key, k) < upd) | always_u).astype(I32)
            reads = (load_word(base, key, cfg.max_props_per_round) < rd
                     ) | always_r
            add = jnp.stack([
                jnp.sum(jnp.where(first, n_new, zeros)),
                jnp.sum((first & reads).astype(I32)),
                jnp.sum((first & (reads | (n_new > 0))).astype(I32))])
            return n_new, reads, _add_limbs(tally, add)

        def round_body(step, zeros_b, zeros_i, slots, ticks, props, tiled,
                       phase=None, load=None):
            """The scan's body over the rows its arguments are made
            for: all N, or one tile's (`tiled`). With `phase`
            (`widen_phased`) the control row is a phased schedule's
            three scalars of the round; with `load` (`load_plane`) it
            is the round's number alone, and what each row is offered
            is drawn from it."""

            def body(carry, row):
                # `occ` is the inbox's occupancy (step.lane_occupancy:
                # the K lanes, then the rare types' bits and, of a split
                # append lane, BULK_APP), [NUM_OCC] bool or one more:
                # what deliver's lane conds skip on and route_lanes'
                # are told was there.
                # Every line here stands under a scope of
                # step.DEVICE_SCOPES (the round's own are innermost and
                # win): what a trace then files under no scope, the
                # compiler made (tests/batched/test_scopes.py).
                # (`tally`: the load plane's counts, of that form alone.)
                st, inbox, occ, tel, flt, (lanes, ring, *tally), watch = carry
                cut, ctl = row
                offer = props
                with jax.named_scope("raft_carry"):
                    if not tiled:
                        lanes = lanes + occ
                    iso = zeros_b
                    # jitlint: waive(tracer-branch) -- as above: a scan without xs hands its body None
                    if cut is not None:
                        # Row t widened to [N] where it is used: node s
                        # is slot s of every group.
                        for s in range(cfg.num_replicas):
                            iso = iso | ((slots == s) & cut[s])
                    transfer, reads, conf = zeros_i, zeros_b, None
                    wipe = None
                    # jitlint: waive(tracer-branch) -- as above
                    if ctl is not None and load is not None:
                        # A load plane: each row is offered what its
                        # group draws in this round.
                        with jax.named_scope("raft_load"):
                            offer, reads, counted = draw_load(
                                load, ctl, tally[0])
                            tally = [counted]
                            stall = jnp.zeros((), bool)
                        if cfg.replace_replicas:
                            wipe = zeros_b
                        pre = st
                    # jitlint: waive(tracer-branch) -- as above
                    elif ctl is not None and phase is None:
                        # The row's few scalars widened the same way.
                        drained = slots == ctl[CTL_FROM] - 1
                        transfer = jnp.where(drained, ctl[CTL_TO], 0)
                        reads = jnp.broadcast_to(
                            ctl[CTL_READS] != 0, zeros_b.shape)
                        if cfg.conf_entries:
                            conf = jnp.where(drained, 0, ctl[CTL_CONF])
                        if cfg.replace_replicas:
                            iso = iso | (slots == ctl[CTL_RETIRE] - 1)
                            wipe = slots == ctl[CTL_WIPE] - 1
                        pre = st
                    # jitlint: waive(tracer-branch) -- as above
                    elif ctl is not None:
                        # A phased schedule: each row reads the cycle
                        # at its own group's round of it.
                        with jax.named_scope("raft_phase"):
                            transfer, conf, retired, wipe = widen_phased(
                                phase, ctl[PH_ROUND], slots, zeros_i)
                            reads = jnp.broadcast_to(
                                ctl[PH_READS] != 0, zeros_b.shape)
                            if cfg.replace_replicas:
                                iso = iso | retired
                            stall = ctl[PH_STALL] != 0
                        pre = st
                    out = step(
                        st, inbox, ticks, zeros_b, offer, iso,
                        transfer, reads, lane_any=occ, conf_req=conf,
                        wipe=wipe,
                    )
                    st, outbox = out[:2]
                    ring = ring + out[-1]  # emit's bit, the round's last
                if cfg.log_runs:
                    with jax.named_scope("raft_watch"):
                        tally = [_add_limbs(tally[0], catchup_round(
                            cfg, st, outbox[KIND_APP], iso, slots))]
                # jitlint: waive(tracer-branch) -- as above
                if ctl is not None:
                    with jax.named_scope("raft_watch"):
                        # (The lockstep row's stall is read here, where
                        # it always was: the lowered text follows the
                        # order of the lines.)
                        watch = self._watch_round(
                            watch, pre, st, slots,
                            ctl[CTL_STALL] != 0
                            if phase is None and load is None else stall,
                            wipe)
                with jax.named_scope("raft_carry"):
                    if cfg.telemetry:
                        fr = out[self._tel_pos]
                        tel = (tel[0] + fr.counters,
                               tel[1] | fr.invariants)
                    if cfg.fleet_summary:
                        fv = out[self._fleet_pos]
                        flt = jnp.where(self._fleet_summask, flt + fv, fv)
                    sent = lane_occupancy(outbox)
                # The lanes somebody wrote this round are exchanged,
                # those that held last round's messages wiped, the rest
                # left as they are (step.route_lanes). The exchange
                # permutes slots inside a lane, so the outbox's
                # occupancy (`sent`) is the next inbox's.
                if placed:
                    # Between nodes: what any node wrote, agreed first
                    # (`occ` was, a round ago), so that every node
                    # takes the same branch round the collective; a
                    # node's deliver then runs a lane that only a peer
                    # holds, over no valid slot. `lanes` counts the
                    # lanes that crossed.
                    sent = agree_lanes(sent, NODE_AXIS)
                    inbox = exchange_lanes(
                        outbox, NODE_AXIS, sent, (inbox, occ))
                    with jax.named_scope("raft_carry"):
                        lanes = lanes + sent
                else:
                    inbox = route_lanes(cfg, outbox, sent, (inbox, occ))
                # A tile cannot count the rounds a lane was occupied
                # for ANY instance: it hands each round's own vector
                # out, for the call to put together over its tiles.
                return (st, inbox, sent, tel, flt, (lanes, ring, *tally),
                        watch), (occ if tiled else None)

            return body

        def enter(inbox):
            """Inbox lanes as a scan takes them, and their occupancy.
            A caller's inbox may hold anything in a lane with no
            valid slot (the eager round exchanges emit's unsent
            request fields too), so such a lane is wiped here, once
            a call: inside the scan an empty lane is all zeros."""
            occ = lane_occupancy(inbox)
            # (A split append lane's tail is the public form's already,
            # zeros unless an append states it: split_lanes' of slots.)
            return tuple(
                jax.tree.map(
                    lambda x, _k=k: jnp.where(occ[_k], x, jnp.zeros_like(x)),
                    inbox[k])
                for k in range(NUM_KINDS)), occ

        def tiled_loop(st, inbox, ticks, props, tel, lanes, isolate,
                       rounds, control, watch, phase=None, load=None):
            """`closed_loop` tile by tile. Groups share nothing, so a
            call of `rounds` rounds over all rows is `tiles` calls
            over a block of whole groups each (`rows` adjacent rows:
            N is g-major), and the chip then steps a block small
            enough for its fast memory. What crosses tiles is put
            together here: a lane counts for a round if any tile held
            a message in it, the ScanWatch counts run on from tile to
            tile, everything else is per row and rides the slice. The
            lane skip is the tile's own, and exact: a lane empty in
            this tile and occupied in another comes out as zeros here,
            where one scan over all rows exchanged emit's unsent
            fields under ``valid`` false
            (tests/batched/test_scan_tiles.py).

            Placed over nodes this is what one node runs on its G rows
            (a row a group, so any block of rows is whole groups), the
            nodes walking the same tiles together: a tile's round ends
            in their exchange. `lanes` is then ((rounds occupied, tile-
            rounds crossed), the node's own count of emit's), the
            occupancy every node counts is the agreed one, and the fence
            is the node's own, [1]. Of a
            phased schedule (`phase`: `widen_phased`) a tile takes its
            rows' starts, as it takes every per-row array, and of a
            load plane (`load`: (update_thr, read_thr, seed), a row
            each) its rows' thresholds; the plane's counts run on from
            tile to tile with the others."""
            with jax.named_scope("raft_carry"):
                slots = row_slots()
                zeros_b = jnp.zeros((rows,), bool)
                zeros_i = jnp.zeros((rows,), I32)

            def tile_body(lo, ticks, props, *own):
                """The scan's body for the rows from `lo` on; `own` is
                what the schedule's form keeps a row: a phased
                schedule's starts, a load plane's two thresholds."""
                with jax.named_scope("raft_carry"):
                    step = tile_step(lo, slots)
                plane = None
                if load is not None:  # its structure: None or arrays
                    with jax.named_scope("raft_load"):
                        plane = load_plane(own + load[2:], lo, slots)
                return round_body(
                    step, zeros_b, zeros_i, slots, ticks, props, tiled=True,
                    phase=None if phase is None else phase[:2] + own,
                    load=plane)

            def tile_rounds(lo, st, inbox, tel, watch, ticks, props,
                            counts, *own):
                """The call's rounds on the rows from `lo` on, handed
                in as the tile's slices (`watch` with the whole
                counts, `counts` the whole counts the scan's carry adds
                up: of lanes exchanged between nodes, () on one device,
                of tile-rounds in which emit read the ring and, of a
                load plane, of what it offered); and each round's lane
                occupancy."""
                with jax.named_scope("raft_carry"):
                    inbox, occ = enter(inbox)
                if placed:
                    occ = agree_lanes(occ, NODE_AXIS)
                (st, inbox, _, tel, _, counts, watch), occs = jax.lax.scan(
                    tile_body(lo, ticks, props, *own),
                    (st, inbox, occ, tel, (), counts, watch),
                    (isolate, control), length=rounds)
                return st, inbox, tel, watch, occs, counts

            def tile(i, carry):
                st, inbox, tel, watch, seen, counts = carry
                with jax.named_scope("raft_tiles"):
                    lo = i * rows
                    cut = lambda x: jax.lax.dynamic_slice_in_dim(x, lo, rows)  # noqa: E731
                    # The counts run on from tile to tile: a sum,
                    # whatever the order. (None is an empty pytree, as
                    # in closed_loop.)
                    t_watch = None if watch is None else watch._replace(
                        read_floor=cut(watch.read_floor),
                        history=cut(watch.history))
                    mine = (*jax.tree.map(cut, (st, inbox, tel)), t_watch,
                            cut(ticks), cut(props))
                    start = ()
                    if phase is not None:  # its structure: None or arrays
                        with jax.named_scope("raft_phase"):
                            start = (cut(phase[2]),)
                    if load is not None:  # likewise
                        with jax.named_scope("raft_load"):
                            start = (cut(load[0]), cut(load[1]))
                t_st, t_inbox, t_tel, t_watch, occs, counts = tile_rounds(
                    lo, *mine, counts, *start)
                with jax.named_scope("raft_tiles"):
                    # In place: the carry is the donated state, and no
                    # second copy of it exists.
                    paste = lambda x, y: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
                        x, y, lo, 0)
                    st, inbox, tel = jax.tree.map(
                        paste, (st, inbox, tel), (t_st, t_inbox, t_tel))
                    watch = None if watch is None else ScanWatch(
                        t_watch.counts,
                        paste(watch.read_floor, t_watch.read_floor),
                        paste(watch.history, t_watch.history))
                    return st, inbox, tel, watch, seen | occs, counts

            lanes, ring, *tally = lanes
            crossed = ()
            if placed:
                lanes, crossed = lanes
            counts = (crossed, ring, *tally)
            with jax.named_scope("raft_carry"):
                inbox = split_lanes(inbox, head)
            # Tracing only. As the body of the loops the round takes
            # JAX 12.5 s to trace on the TPU's host, by itself 2.6 s
            # (PERF.md section 6, "PR 35": every warm start would pay
            # the difference); one round traced abstractly here first,
            # the loops' trace finds the round's jaxpr cached. Nothing
            # of this reaches the program.
            # jitlint: waive(tracer-branch) -- None is an empty pytree, as in closed_loop
            t_watch = None if watch is None else ScanWatch(
                watch.counts, like(watch.read_floor), like(watch.history))
            # jitlint: waive(tracer-branch) -- as above: None or a tuple of arrays
            t_start = () if phase is None else (like(phase[2]),)
            # jitlint: waive(tracer-branch) -- as above
            if load is not None:
                t_start = (like(load[0]), like(load[1]))
            with self._pretrace():
                jax.eval_shape(
                    lambda ticks, props, carry, row, *start: tile_body(
                        0, ticks, props, *start)(carry, row),
                    like(ticks), like(props),
                    (*jax.tree.map(like, (st, inbox)),
                     jax.ShapeDtypeStruct((n_occ,), bool),
                     jax.tree.map(like, tel), (), counts, t_watch),
                    jax.tree.map(
                        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                        (isolate, control)),
                    *t_start)
            with jax.named_scope("raft_tiles"):
                seen = jnp.zeros((rounds, n_occ), bool)
            st, inbox, tel, watch, seen, (crossed, ring, *tally) = (
                jax.lax.fori_loop(
                    0, tiles, tile, (st, inbox, tel, watch, seen, counts)))
            # Three blocks for two names, in the order the lines had
            # before they had names: the lowered text follows the order
            # of the lines, and JAX's cache key the text (the names are
            # stripped from it: tests/batched/test_scopes.py pins both
            # tiled texts).
            with jax.named_scope("raft_carry"):
                inbox = stack_lanes(inbox)
            with jax.named_scope("raft_tiles"):
                lanes = lanes + jnp.sum(seen, axis=0, dtype=I32)
            with jax.named_scope("raft_carry"):
                if placed:
                    return (st, inbox, tel, (), ((lanes, crossed), ring),
                            st.commit[:1], watch)
                return (st, inbox, tel, (), (lanes, ring, *tally),
                        st.commit[0], watch)

        def placed_loop(st, inbox, ticks, props, tel, lanes, isolate,
                        rounds, control, watch):
            """`tiled_loop` as every node runs it on its own rows; a
            node keeps its own ScanWatch counts (``scan_watch`` adds
            them up)."""
            def one_node(st, inbox, ticks, props, tel, watch, ring, lanes,
                         isolate, control):
                if watch is not None:  # None is an empty pytree
                    with jax.named_scope("raft_carry"):
                        watch = watch._replace(counts=watch.counts[0])
                st, inbox, tel, _, (lanes, ring), fence, watch = tiled_loop(
                    st, inbox, ticks, props, tel, (lanes, ring), isolate,
                    rounds, control, watch)
                if watch is not None:
                    with jax.named_scope("raft_carry"):
                        watch = watch._replace(counts=watch.counts[None])
                return (st, inbox, tel, fence, watch, ring), lanes

            by_node = P(NODE_AXIS)
            lanes, ring = lanes  # agreed between the nodes; a node's own
            (st, inbox, tel, fence, watch, ring), lanes = over_nodes(
                one_node, (by_node,) * 7 + (P(),) * 3, (by_node, P()))(
                    st, inbox, ticks, props, tel, watch, ring, lanes,
                    isolate, control)
            return st, inbox, tel, (), (lanes, ring), fence, watch

        def closed_loop(st, inbox, ticks, props, tel, flt, lanes, isolate,
                        rounds, control=None, watch=None, phase=None,
                        load=None):
            # `isolate` is None (no fault: the scan is traced as it
            # always was) or the bool [rounds, R] node schedule, one
            # row a round as the scan's xs; `control` is None (the
            # same) or the int32 [rounds, CTL_COLS] control schedule,
            # beside it, and `watch` the ScanWatch that rides the carry
            # with it. `phase` is None (the same again) or a phased
            # schedule's (edges [E], runs [E, cols], starts [G]), and
            # `control` then the rounds' [rounds, 3] (PH_*). `load` is
            # None (the same once more) or a load plane's (update_thr
            # [G], read_thr [G], seed), `control` then the rounds'
            # numbers, [rounds], and `lanes` ends in the plane's counts.
            if placed:
                return placed_loop(st, inbox, ticks, props, tel, lanes,
                                   isolate, rounds, control, watch)
            # jitlint: waive(tracer-branch) -- None or a tuple of arrays: the argument's structure
            if phase is not None:
                # A group's start on each of its rows (N is g-major).
                with jax.named_scope("raft_phase"):
                    phase = phase[:2] + (
                        jnp.repeat(phase[2], cfg.num_replicas),)
            # jitlint: waive(tracer-branch) -- as above
            if load is not None:
                # A group's thresholds on each of its rows.
                with jax.named_scope("raft_load"):
                    load = (jnp.repeat(load[0], cfg.num_replicas),
                            jnp.repeat(load[1], cfg.num_replicas), load[2])
            if tiles > 1:
                return tiled_loop(st, inbox, ticks, props, tel, lanes,
                                  isolate, rounds, control, watch, phase,
                                  load)
            slots = None
            # jitlint: waive(tracer-branch) -- None is an empty pytree: the branch is on the argument's structure at trace time, never on a device value
            if isolate is not None or control is not None or cfg.log_runs:
                with jax.named_scope("raft_carry"):
                    slots = jnp.arange(n, dtype=I32) % cfg.num_replicas
            # jitlint: waive(tracer-branch) -- as above
            if load is not None:
                with jax.named_scope("raft_load"):
                    load = load_plane(load, 0, slots)
            body = round_body(self._step, self._zeros_b, self._zeros_i,
                              slots, ticks, props, tiled=False, phase=phase,
                              load=load)
            # Inbox and outbox ride the scan as K kind lanes, each an
            # array of its own (the round answers lanes with lanes),
            # and the inbox is stacked back once at the exit.
            with jax.named_scope("raft_carry"):
                inbox, occ = enter(split_lanes(inbox, head))
            (st, inbox, _, tel, flt, lanes, watch), _ = jax.lax.scan(
                body, (st, inbox, occ, tel, flt, lanes, watch),
                (isolate, control), length=rounds
            )
            # The scalar fence is a SEPARATE output buffer: pipelined
            # callers block on it to bound queue depth without holding
            # (and thereby breaking) a donated state buffer.
            with jax.named_scope("raft_carry"):
                return (st, stack_lanes(inbox), tel, flt, lanes,
                        st.commit[0], watch)

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
        self._wkey_step = f"round_step/{hash((cfg, False, n_all))}"

    # -- placement over nodes (``nodes=``) -------------------------------------

    def _place_state(self, start_index: int, spare) -> None:
        """State and inbox built where they live: every node makes its
        own rows, slot s of every group, with the logical ids."""
        cfg = self.cfg
        g, r = cfg.num_groups, cfg.num_replicas

        def build(spare):
            row = jnp.arange(g * r, dtype=I32)  # placed: s * G + g
            st = init_state(cfg, start_index, iids=(row % g) * r + row // g,
                            spare=spare)
            return st, empty_msgs((g * r, r, NUM_KINDS),
                                  cfg.max_ents_per_msg,
                                  narrow=cfg.narrow_lanes)

        # The spare is an argument, so one program serves every seed's.
        self.state, self.inbox = jax.jit(
            build, out_shardings=self._by_node)(
                None if spare is None else self._on_nodes(
                    np.asarray(spare, np.int32)))

    def _zeros(self, shape, dtype):
        """Zeros a row an instance, where the state lives."""
        return jnp.zeros(
            shape, dtype,
            device=None if self._nodes is None else self._by_node)

    def _place(self, x):
        """A per-instance array a caller hands in, logical order, as
        the engine holds it; None stays None."""
        if x is None or self._nodes is None:
            return x
        g, r = self.cfg.num_groups, self.cfg.num_replicas
        x = jax.device_put(x, self._on_all)
        return _to_placed(x, g, r, self._by_node)

    def _on_nodes(self, x: np.ndarray):
        """A host array every node needs whole (a schedule, a count),
        on the device, or on each of the nodes: placed here, a call's
        dispatch moves nothing between devices."""
        if self._nodes is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._on_all)

    def _rows(self, instance_ids):
        """Logical instance ids as rows of the engine's arrays."""
        ids = jnp.asarray(instance_ids)
        if self._nodes is None:
            return ids
        g, r = self.cfg.num_groups, self.cfg.num_replicas
        return (ids % r) * g + ids // r

    def logical(self, x) -> np.ndarray:
        """A per-instance array of this engine (a field of
        ``eng.state``, of ``eng.inbox``) on the host in the logical
        order, row ``g * R + s``."""
        x = np.asarray(x)
        if self._nodes is None:
            return x
        g, r = self.cfg.num_groups, self.cfg.num_replicas
        return np.ascontiguousarray(
            x.reshape((r, g) + x.shape[1:]).swapaxes(0, 1)).reshape(x.shape)

    def _watch_round(self, watch: ScanWatch, pre, st, slots,
                     stall, wiped=None) -> ScanWatch:
        """One round of a controlled scan counted into its ScanWatch:
        `pre` and `st` are the state before and after the round, `stall`
        the row's CTL_STALL, `wiped` (``replace_replicas``) the
        instances its control phase reset. Runs inside the scan's body,
        outside the instance vmap, on whole [N] fields."""
        r = self.cfg.num_replicas

        def group_max(x):
            if self._nodes is not None:
                # A group's replicas are the same row of every node.
                with jax.named_scope("raft_agree"):
                    return jax.lax.pmax(x, NODE_AXIS)
            # The maximum over the R adjacent rows of each row's group,
            # as row shifts under the slot mask (route()'s idiom: N is
            # never split).
            out = x
            for k in range(1, r):
                pad = jnp.zeros((k,), x.dtype)
                up = jnp.concatenate([x[k:], pad])      # row n + k
                down = jnp.concatenate([pad, x[:-k]])   # row n - k
                out = jnp.maximum(out, jnp.where(slots + k < r, up, out))
                out = jnp.maximum(out, jnp.where(slots - k >= 0, down, out))
            return out

        # A batch open as the round began is confirmed in it if the
        # round ends with it ready or with the next one open (the
        # control phase reopens in the round whose deliver confirmed).
        open_before = (pre.read_index >= 0) & ~pre.read_ready
        reopened = st.read_seq != pre.read_seq
        confirmed = open_before & (reopened | st.read_ready)
        below = confirmed & (pre.read_index < watch.read_floor)
        floor = jnp.where(reopened, group_max(pre.commit), watch.read_floor)
        advanced = st.commit > pre.commit
        # Conservative: a conflict that truncates the mark and an
        # append that brings another in one round counts too.
        lost = ((pre.conf.index > pre.applied)
                & (st.conf.index != pre.conf.index) & (st.conf.index != 0)
                ) if self.cfg.conf_entries else jnp.zeros_like(advanced)
        events = [
            st.in_joint,
            (st.read_index >= 0) & ~st.read_ready,
            below,
            stall & advanced & (pre.in_joint | st.in_joint),
            lost,
        ]
        add = jnp.stack([jnp.sum(e.astype(I32)) for e in events])
        if self.cfg.replace_replicas:
            add = jnp.concatenate(
                [add, self._replace_events(pre, st, slots, wiped)])
        counts = _add_limbs(watch.counts, add)
        bits = (1 << jnp.arange(r, dtype=I32))[None, :]
        history = watch.history
        for name in HISTORY_FIELDS:
            v = getattr(st, name)
            if v.ndim == 2:
                v = jnp.sum(jnp.where(v, bits, 0), axis=1)
            history = (history ^ v.astype(jnp.uint32)) * jnp.uint32(_FNV)
        return ScanWatch(counts, floor, history)

    def _replace_events(self, pre, st, slots, wiped) -> jnp.ndarray:
        """REPLACE_WATCH_NAMES' events of one round, counted, from the
        state before and after it: nothing here is carried by the
        round."""
        cfg = self.cfg
        peers = jnp.arange(cfg.num_replicas, dtype=I32)[None, :]
        own = peers == slots[:, None]

        def votes_here(s):  # its own configuration names it as a voter
            return jnp.any((s.voter | s.voter_out) & own, axis=1)

        def cand(role):
            return (role == CANDIDATE) | (role == PRECANDIDATE)

        leads = st.role == LEADER
        campaigned = (cand(st.role) & ~cand(pre.role)) | (
            leads & (pre.role != LEADER))
        voted = ((st.vote != 0) & (st.vote != slots + 1)
                 & ((st.vote != pre.vote) | (st.term != pre.term)))
        kind, learner, _ = conf_decode(st.conf.op)
        taken = (leads & (st.conf.index != pre.conf.index)
                 & (st.conf.index == st.conf.pending) & (kind == CONF_SWAP))
        ready = jnp.any(
            (peers == learner[:, None]) & (st.pr_state == REPLICATE)
            & (st.match >= pre.commit[:, None]), axis=1)
        # A restore leaves the log empty at the snapshot's index; no
        # compaction does (a floor stays half a ring behind `last`).
        restored = (st.snap_index > pre.snap_index) & (
            st.snap_index == st.last)
        short = leads[:, None] & st.learner & (st.pr_state != REPLICATE)
        events = [
            ~votes_here(pre) & ~votes_here(st) & (campaigned | voted),
            taken & ~ready, restored, wiped, short, taken,
        ]
        return jnp.stack([jnp.sum(e.astype(I32)) for e in events])

    # -- driving --------------------------------------------------------------

    def step_round(
        self,
        tick: bool = False,
        campaign_mask: Optional[jnp.ndarray] = None,
        propose_n: Optional[jnp.ndarray] = None,
        isolate: Optional[jnp.ndarray] = None,
        transfer_to: Optional[jnp.ndarray] = None,
        read_req: Optional[jnp.ndarray] = None,
        conf_req: Optional[jnp.ndarray] = None,
        wipe: Optional[jnp.ndarray] = None,
    ) -> None:
        """One round: deliver pending messages, optionally tick every
        instance, run host control ops (leader transfer, ReadIndex,
        for a configuration with ``conf_entries`` the configuration
        change on offer, `conf_req`: [N] ``state.conf_code``, and for
        one with ``replace_replicas`` the replica reset, `wipe`: [N]
        bool), append proposals on leaders, route the outbox. `isolate`
        cuts instances off the network for this round."""
        self._eager_round(tick, *map(self._place, (
            campaign_mask, propose_n, isolate, transfer_to, read_req,
            conf_req, wipe)))

    def _eager_round(self, tick=False, campaign_mask=None, propose_n=None,
                     isolate=None, transfer_to=None, read_req=None,
                     conf_req=None, wipe=None) -> None:
        """``step_round`` on per-instance arrays in the engine's own
        row order."""
        ticks = (
            jnp.ones_like(self._zeros_b) if tick else self._zeros_b
        )
        camp = campaign_mask if campaign_mask is not None else self._zeros_b
        props = propose_n if propose_n is not None else self._zeros_i
        iso = isolate if isolate is not None else self._zeros_b
        transfer = transfer_to if transfer_to is not None else self._zeros_i
        reads = read_req if read_req is not None else self._zeros_b
        if self.cfg.conf_entries:
            conf = conf_req if conf_req is not None else self._zeros_i
        elif conf_req is not None:
            raise ValueError(
                "conf_req needs a configuration with conf_entries")
        else:
            conf = None
        if self.cfg.replace_replicas:
            wipe = wipe if wipe is not None else self._zeros_b
        elif wipe is not None:
            raise ValueError(
                "wipe needs a configuration with replace_replicas")
        # Inside the guard the dispatch must be all-device: any implicit
        # transfer (an eager scalar op, a stray host array) is a hard
        # error when ETCD_TPU_TRANSFER_GUARD=disallow (tests, benches).
        with self._span("engine.step_round"), warm_guard(self._wkey_step):
            out = self._round(
                self.state, self.inbox, ticks, camp, props, iso,
                transfer, reads, conf_req=conf, wipe=wipe,
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
            # Between nodes the exchange ran with the round.
            self.inbox = (outbox if self._nodes is not None
                          else route(self.cfg, outbox))

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
        return self._on_nodes(sched), int(sched.sum())

    def _heals(self, isolate, rounds: int) -> int:
        """Nodes a call heals: cut off in one round of the scans'
        schedules and not in the next, the last call's last round and
        this call's first among them (a call without a schedule cuts
        nobody)."""
        sched = (np.zeros((rounds, self.cfg.num_replicas), bool)
                 if isolate is None else np.asarray(isolate, bool))
        if not len(sched):
            return 0
        before = np.concatenate([self._cut_last[None], sched[:-1]])
        self._cut_last = sched[-1].copy()
        return int((before & ~sched).sum())

    def _control_schedule(self, control, rounds: int):
        """(device schedule or None, span stats) of a call's control
        plane."""
        if control is None:
            return None, {"reads": 0, "conf_ops": 0, "transfers": 0,
                          "wipes": 0, "retired": 0}
        ctl = self._control_rows(control, rounds)
        return self._on_nodes(ctl), self._asked(
            ctl, int((ctl[:, CTL_READS] != 0).sum()))

    def _control_rows(self, control, rounds) -> np.ndarray:
        """`control` as int32 [rounds, CTL_COLS] (a phased schedule's
        cycle: `rounds` None, any number of rows), or a refusal; the
        first such call makes the ScanWatch."""
        ctl = np.asarray(control)
        cols = control_cols(self.cfg)
        rows = "cycle_rounds" if rounds is None else "rounds"
        if (ctl.ndim != 2 or ctl.shape[1] != cols or not len(ctl)
                or rounds not in (None, len(ctl))
                or ctl.dtype.kind not in "iu"):
            raise ValueError(
                f"control must be integers [{rows}, CTL_COLS] = "
                f"{(rows if rounds is None else rounds, cols)}, got "
                f"{ctl.dtype} {ctl.shape}")
        if ctl[:, CTL_CONF].any() and not self.cfg.conf_entries:
            raise ValueError(
                "the control schedule offers a configuration change: "
                "that needs a configuration with conf_entries")
        self._ensure_watch()
        return ctl.astype(np.int32)

    def _ensure_watch(self) -> None:
        """The ScanWatch, made by the first scan that carries one."""
        if self._watch is None:
            counts = (len(watch_names(self.cfg)), 2)
            if self._nodes is not None:  # a node counts its own rows
                counts = (len(self._nodes),) + counts
            self._watch = ScanWatch(
                self._zeros(counts, I32),
                self._zeros((self.cfg.num_instances,), I32),
                self._zeros((self.cfg.num_instances,), jnp.uint32))

    def _asked(self, seen: np.ndarray, reads: int) -> dict:
        """The span's stats of the rows a call's instances read (of a
        phased schedule: a round a batch in flight) and the rounds that
        ask for reads."""
        return {
            "reads": reads * self.cfg.num_instances,
            "conf_ops": int((seen[:, CTL_CONF] != 0).sum()),
            "transfers": int(((seen[:, CTL_FROM] != 0)
                              & (seen[:, CTL_TO] != 0)).sum()),
            # rounds x nodes, as `isolated` counts.
            "wipes": int((seen[:, CTL_WIPE:] != 0).sum()),
            "retired": int((seen[:, CTL_RETIRE:CTL_WIPE] != 0).sum()),
        }

    def _phased_schedule(self, control, starts, rounds: int):
        """A phased schedule (the CTL_* comment at the top of this
        module) for the `rounds` rounds from ``phase_round`` on, which
        moves on: (the rounds' [rounds, 3] PH_* rows and the
        schedule's (edges, runs, starts), both on the device; the
        span's stats, with two more: ``batches`` in flight in the call
        and ``started``, the groups that have entered the cycle by its
        end). The schedule's arrays are kept from call to call while
        it is the same."""
        if self._nodes is not None:
            raise ValueError(
                "a phased schedule reads the row's group, which an engine "
                "placed over nodes does not widen yet (ROADMAP R1d): not "
                "with nodes")
        if control is None:
            raise ValueError(
                "starts says when each group enters the cycle that control "
                "holds: it needs control")
        cycle = self._control_rows(control, None)
        period = len(cycle)
        starts = np.asarray(starts)
        if (starts.shape != (self.cfg.num_groups,)
                or starts.dtype.kind not in "iu" or (starts < 0).any()):
            raise ValueError(
                f"starts must be rounds >= 0, integers [num_groups] = "
                f"{(self.cfg.num_groups,)}, got {starts.dtype} "
                f"{starts.shape}")
        kept = self._phase
        if (kept is None or not np.array_equal(kept["cycle"], cycle)
                or not np.array_equal(kept["starts"], starts)):
            batches, sizes = np.unique(starts, return_counts=True)
            self._phase = kept = {
                "cycle": cycle, "starts": starts.copy(),
                "device": tuple(jnp.asarray(x, I32) for x in (
                    *_cycle_runs(cycle), starts)),
                "batches": batches.astype(np.int64),
                "started": np.cumsum(sizes)}
        t = self.phase_round + np.arange(rounds, dtype=np.int64)
        self.phase_round += rounds
        per_round = np.stack(
            [t, cycle[t % period, CTL_READS] != 0,
             cycle[t % period, CTL_STALL] != 0], axis=1).astype(np.int32)
        batches = kept["batches"]
        k = t[:, None] - batches[None, :]
        inside = (k >= 0) & (k < period)
        done = np.searchsorted(batches, t[-1], side="right")
        stats = dict(
            self._asked(cycle[k[inside]], int(per_round[:, PH_READS].sum())),
            batches=int(inside.any(axis=0).sum()),
            started=int(kept["started"][done - 1]) if done else 0)
        return jnp.asarray(per_round), kept["device"], stats

    def _load_schedule(self, load, rounds: int):
        """A load plane (the LOAD_* comment at the top of this module)
        for the `rounds` rounds from ``load_round`` on, which moves on:
        (the rounds' numbers [rounds] and the plane's (update_thr,
        read_thr, seed), both on the device; the span's stats, with
        ``load_from``, the call's first load round). The plane's arrays
        are kept from call to call while it is the same."""
        if self._nodes is not None:
            raise ValueError(
                "a load plane reads the row's group, which an engine "
                "placed over nodes does not widen yet (ROADMAP R1d): not "
                "with nodes")
        try:
            update_thr, read_thr, seed = load
        except (TypeError, ValueError):
            raise ValueError(
                "load must be (update_thr, read_thr, seed)") from None
        thr = [np.asarray(update_thr), np.asarray(read_thr)]
        for name, x in zip(("update_thr", "read_thr"), thr):
            if x.shape != (self.cfg.num_groups,) or x.dtype != np.uint32:
                raise ValueError(
                    f"{name} must be uint32 [num_groups] = "
                    f"{(self.cfg.num_groups,)}, got {x.dtype} {x.shape}")
        if (not isinstance(seed, (int, np.integer))
                or not 0 <= int(seed) <= LOAD_ALWAYS):
            raise ValueError(
                f"the load plane's seed must be an integer in [0, 2**32), "
                f"got {seed!r}")
        kept = self._load
        if (kept is None or kept["seed"] != int(seed)
                or not all(np.array_equal(a, b)
                           for a, b in zip(kept["thr"], thr))):
            self._load = kept = {
                "thr": [x.copy() for x in thr], "seed": int(seed),
                "device": (jnp.asarray(thr[0]), jnp.asarray(thr[1]),
                           jnp.asarray(np.uint32(seed)))}
        self._ensure_watch()
        if self._tally is None:
            self._tally = jnp.zeros((len(LOAD_COUNT_NAMES), 2), I32)
        first = self.load_round
        self.load_round += rounds
        stats = dict(self._control_schedule(None, rounds)[1],
                     load_from=first)
        return (jnp.asarray(first + np.arange(rounds, dtype=np.int32)),
                kept["device"], stats)

    def _scan(self, rounds: int, ticks, props, isolate, control=None,
              starts=None, load=None):
        """One closed-loop scan enqueued; returns its scalar fence."""
        sched, isolated = self._schedule(isolate, rounds)
        healed = self._heals(isolate, rounds)
        phase = plane = None
        if load is not None and self.cfg.log_runs:
            raise ValueError(
                "a load plane's counts and log_runs' ride one place of the "
                "scan's carry: not with log_runs")
        if load is not None:
            ctl, plane, asked = self._load_schedule(load, rounds)
        elif starts is None:
            ctl, asked = self._control_schedule(control, rounds)
        else:
            ctl, phase, asked = self._phased_schedule(control, starts, rounds)
        # `rounds` is a static arg: each new value compiles a new scan
        # program (and so does the first call with a schedule of either
        # kind, or of either form), so warmth (and thus the transfer
        # guard) is per value.
        key = f"closed_loop/{self._serial}/{rounds}" + (
            "" if sched is None else "/isolate") + (
            "" if ctl is None else "/control") + (
            "" if phase is None else f"/phased{len(phase[0])}") + (
            "" if plane is None else "/load")
        with self._span("engine.run_rounds", rounds=rounds,
                        tiles=self._tiles, isolated=isolated, healed=healed,
                        **asked), warm_guard(key):
            watch = None if ctl is None else self._watch
            self.state, self.inbox, tel, flt, lanes, fence, watch = self._closed_loop(
                self.state, self.inbox, ticks, props, self._tel(),
                self._flt(),
                self._lanes + (() if plane is None else (self._tally,))
                + (() if self._catchup is None else (self._catchup,)),
                sched, rounds, ctl, watch,
                *(() if phase is None else (phase,)),
                **({} if plane is None else {"load": plane})
            )
        if plane is not None:
            self._tally = lanes[2]
        if self._catchup is not None:
            self._catchup = lanes[2]
        self._lanes = lanes[:2]
        self._set_tel(tel)
        self._set_flt(flt)
        if ctl is not None:
            self._watch = watch
        return fence

    def run_rounds(self, rounds: int, tick: bool = True,
                   propose_n: Optional[jnp.ndarray] = None,
                   isolate=None, control=None, starts=None,
                   load=None) -> None:
        """Closed-loop simulation of `rounds` rounds, faults and the
        control plane included, without leaving the device (one fused
        lax.scan program).
        `isolate`, bool [rounds, R], cuts node s (slot s of every
        group) off the network in round t where ``isolate[t, s]``: it
        neither receives nor sends, and keeps ticking — what
        ``step_round(isolate=...)`` does to single instances, as the
        scan's per-round input. `control`, int [rounds, CTL_COLS], is
        what the control plane asks in round t (the CTL_* columns at
        the top of this module): the ``transfer_to``, ``read_req`` and
        ``conf_req`` of ``step_round``, a few scalars a round widened
        to the instances on the device as `isolate` is. With neither,
        the scan takes no per-round input.
        With `starts`, int [num_groups], the control schedule is
        *phased*: `control` is one cycle's rows, int [cycle_rounds,
        CTL_COLS], and ``starts[g]`` the round at which group g enters
        the cycle, counted in the rounds this engine's phased scans
        have run (``phase_round``; this call runs rounds
        ``phase_round`` to ``phase_round + rounds - 1`` and moves it
        on). A group reads the cycle at ``t - starts[g]`` for what is
        its own (transfer, change on offer, replica retired, slot
        reset) and the steady row (none of them) before its start
        (``NEVER``: never) and once its cycle has ended; reads and the
        stall mark are a round's, row ``t mod cycle_rounds``. A
        rebalancer that starts a batch of moves every few rounds is a
        `starts` with a batch's groups at each such round. Not with
        ``nodes=``.
        With `load`, ``(update_thr, read_thr, seed)``, every group is
        offered its own updates and reads, drawn on the device round by
        round (the LOAD_* comment at the top of this module): 0 to
        ``max_props_per_round`` updates, one for each draw below
        ``update_thr[g]``, and a read where the read's draw is below
        ``read_thr[g]`` (uint32 [num_groups]; LOAD_ALWAYS: in every
        round), in the rounds ``load_round`` to ``load_round + rounds -
        1`` of this engine's load scans, which moves on. The scan
        carries the ScanWatch as a controlled scan does and counts what
        it offered (``load_counts``). The draws are the call's
        proposals and its control plane: not with `propose_n`,
        `control` or `starts`, and not with ``nodes=``."""
        if load is not None:
            for name, given in (("propose_n", propose_n),
                                ("control", control), ("starts", starts)):
                if given is not None:
                    raise ValueError(
                        f"a load plane draws what each group is offered "
                        f"and asked: not with {name}")
        ticks = jnp.ones_like(self._zeros_b) if tick else self._zeros_b
        propose_n = self._place(propose_n)
        props = propose_n if propose_n is not None else self._zeros_i
        self._scan(rounds, ticks, props, isolate, control, starts, load)

    def run_rounds_pipelined(self, rounds: int, chunk: int = 16,
                             depth: int = 2, tick: bool = True,
                             propose_n: Optional[jnp.ndarray] = None,
                             isolate=None, control=None) -> None:
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
        node schedule over all `rounds` and `control` its control
        schedule; each chunk takes its rows."""
        if rounds <= 0:
            return
        if chunk <= 0:
            # A non-positive chunk would dispatch zero-round scans
            # forever (done never advances) — a silent host hang.
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        ticks = jnp.ones_like(self._zeros_b) if tick else self._zeros_b
        propose_n = self._place(propose_n)
        props = propose_n if propose_n is not None else self._zeros_i
        fences: deque = deque()
        done = 0
        while done < rounds:
            n = min(chunk, rounds - done)
            fences.append(self._scan(
                n, ticks, props,
                None if isolate is None else isolate[done:done + n],
                None if control is None else control[done:done + n]))
            done += n
            while len(fences) > depth:
                # jitlint: waive(sync-in-loop) -- the sync IS the pipelining contract: block on the per-chunk scalar fence to bound queue depth at `depth` without holding a donated buffer
                jax.block_until_ready(fences.popleft())

    def campaign(self, instance_ids) -> None:
        mask = self._zeros_b.at[self._rows(instance_ids)].set(True)
        self._eager_round(campaign_mask=mask)

    def transfer_leader(self, leader_instance: int, target_slot: int) -> None:
        """Ask the leader instance to hand leadership to target_slot
        (ref: raft.go:1339 MsgTransferLeader on the leader)."""
        tr = self._zeros_i.at[self._rows(leader_instance)].set(
            target_slot + 1)
        self._eager_round(transfer_to=tr)

    def read_index(self, instance_ids) -> None:
        """Open a ReadIndex batch on the given leader instances
        (ref: v3_server.go sendReadIndex → MsgReadIndex)."""
        req = self._zeros_b.at[self._rows(instance_ids)].set(True)
        self._eager_round(read_req=req)

    def read_states(self) -> "tuple[np.ndarray, np.ndarray, np.ndarray]":
        """(seq, index, ready) per instance — the ReadState watermarks
        the host read loop waits on (ref: read_only.go advance →
        Ready.ReadStates)."""
        return (
            self.logical(self.state.read_seq),
            self.logical(self.state.read_index),
            self.logical(self.state.read_ready),
        )

    def set_membership(self, group: int, voters, voters_out=(),
                       learners=(), joint: bool = False) -> None:
        """Upload new membership masks for every replica row of `group`
        — the confchange apply point of a configuration without
        ``conf_entries`` (ref: confchange/confchange.go
        EnterJoint/LeaveJoint/Simple; the host Changer computes the
        slot sets, the device only sees masks). With ``conf_entries``
        a change is an entry and each replica applies it itself
        (``step_round(conf_req=...)``, ``run_rounds(control=...)``);
        this upload is then for a test's starting point only."""
        r = self.cfg.num_replicas
        rows = self._rows(jnp.arange(group * r, (group + 1) * r))

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
        return (self.logical(self._tel_counters),
                self.logical(self._tel_invariants))

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
        role = self.logical(self.state.role).reshape(
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
        return self._occupied()[:NUM_KINDS]

    def rare_rounds(self) -> np.ndarray:
        """[2] closed-loop rounds, counted like ``lane_rounds``, in
        which the heartbeat lane held a MsgTimeoutNow and the
        heartbeat-response lane a MsgAppResp for any instance (of the
        outbox, before a fault schedule cuts anybody off): the rounds
        in which deliver took the whole handler of those lanes, campaign
        and MsgAppResp fold included, and not the plain one
        (step._deliver_vectorized). One less their share of
        ``lane_rounds()[KIND_HB]`` / ``[KIND_HB_RESP]`` is how often the
        plain branch did."""
        return self._occupied()[NUM_KINDS:NUM_OCC]

    def bulk_rounds(self) -> int:
        """Closed-loop rounds, counted like ``lane_rounds``, in which
        the append lane held a MsgApp that states more entries than a
        split lane's head holds (step.app_head, ``lane_occupancy``'s
        BULK_APP) for any instance of any tile: the rounds in which
        deliver ran the lane at its whole width and, the round before,
        emit built the entries' tail and route() moved it; in every
        other round of ``lane_rounds()[KIND_APP]`` the lane ran at the
        head's. 0 where the lane is not split."""
        return int(self._occupied()[BULK_APP]) if self._head else 0

    def emit_ring_rounds(self) -> int:
        """Tile-rounds of the closed loop (a node's, over nodes) in
        which emit read the log ring for the terms its messages state,
        because some row of the tile asked below its own-term boundary
        (a vote request, a new leader's first append, a snapshot taken
        below it: step._emit); in every other it stated the sender's
        own term and read the ring for the floor's term alone. Of
        ``rounds * tiles`` (``* R`` over nodes) a call; counted in the
        scan's carry like ``lane_rounds``."""
        return int(np.asarray(self._lanes[1]).sum())

    def _occupied(self) -> np.ndarray:
        lanes = self._lanes[0]
        return np.asarray(lanes if self._nodes is None else lanes[0])

    def lane_exchanges(self) -> np.ndarray:
        """[NUM_KINDS] for an engine placed over nodes: how often the
        closed loop exchanged each lane between them, counted in the
        scan's carry like ``lane_rounds``. One count is one tile's
        round in which the lane crossed: an all-to-all of
        ``eng.tile_rows`` rows of R slots, of which a node keeps its
        own and sends R - 1. Zeros on one device, where nothing
        crosses."""
        if self._nodes is None:
            return np.zeros((NUM_KINDS,), np.int32)
        return np.asarray(self._lanes[0][1])[:NUM_KINDS]

    def scan_watch(self) -> dict:
        """What the scans with a control schedule counted, by
        ``watch_names(cfg)``, since the engine was built (all zero
        before the first such scan). One host gather; no per-round
        sync."""
        names = watch_names(self.cfg)
        if self._watch is None:
            return dict.fromkeys(names, 0)
        c = _limbs_total(self._watch.counts)
        if self._nodes is not None:  # a node counts its own rows
            c = c.sum(axis=0)
        return dict(zip(names, map(int, c)))

    def scan_history(self) -> np.ndarray:
        """[N] uint32: each instance's state after every round of every
        controlled scan, hashed (``history_fold`` over HISTORY_FIELDS,
        from 0). All zero before the first such scan."""
        if self._watch is None:
            return np.zeros((self.cfg.num_instances,), np.uint32)
        return self.logical(self._watch.history)

    def load_counts(self) -> dict:
        """What the scans with a load plane offered since the engine
        was built, by LOAD_COUNT_NAMES, a group and not a row: updates
        offered, group-rounds in which a read was asked, group-rounds
        in which either was. One host gather; no per-round sync."""
        if self._tally is None:
            return dict.fromkeys(LOAD_COUNT_NAMES, 0)
        return dict(zip(LOAD_COUNT_NAMES,
                        map(int, _limbs_total(self._tally))))

    def catchup_counts(self) -> dict:
        """What the scans of a configuration with log_runs counted of
        catch-up since the engine was built, by CATCHUP_NAMES
        (all zero without the field). One host gather; no per-round
        sync."""
        if self._catchup is None:
            return dict.fromkeys(CATCHUP_NAMES, 0)
        return dict(zip(CATCHUP_NAMES,
                        map(int, _limbs_total(self._catchup))))

    def commits(self) -> np.ndarray:
        """Per-instance commit watermarks [G, R] — the host applies
        payloads from its arena up to these."""
        return self.logical(self.state.commit).reshape(
            self.cfg.num_groups, self.cfg.num_replicas
        )

    def terms(self) -> np.ndarray:
        return self.logical(self.state.term).reshape(
            self.cfg.num_groups, self.cfg.num_replicas
        )
