"""The append lane's entries in two halves (ISSUE 51, ``step.app_head``):
where an append's E columns outweigh the lane's nine scalar fields the
lane form holds the first Wn = P + 1 apart from the rest
(``step.BulkLane``), the occupancy vector carries one bit more
(``BULK_APP``: a valid MsgApp that states more than Wn entries) and on
it deliver runs the lane at the head's width or whole, emit builds the
tail or hands the spent one on, and route() moves it, wipes it or
leaves it (``step._deliver_vectorized``, ``_emit``,
``_exchange_written``). The split is exact by construction; these tests
hold it to that: the closed loop against the same loop with the lane in
one piece (the parent's program: ``app_head`` patched to 0 for that
engine alone), round by round, untiled and in two tiles, through a
steady stretch, a reboot's return (probe, reject, appends of E), a
returned ex-leader's suffix truncated in the tail columns, a snapshot
in the lane and elections whose first appends carry P + 1 entries;
against the shadow oracle over a reboot; an injected append of Wn + 1
entries; the counter (``eng.bulk_rounds()``); the static predicate over
the benchmark's configurations; the refusal over nodes.

Round-step programs (``conftest.py``): DEEP64 is the cell
``engine100k-r3-deeplog``'s sizes at 8 groups, which
``tests/benchmark/test_catchup.py`` builds (E=64, K=32 runs), and RING16
``test_differential_wide.make_pair(2, 10, auto_compact=True)``'s values
(E=16, P=4 on a ring of 64: the split on the ring's kernels, and the one
that can be handed a snapshot), keys both; the lane in one piece is
another trace of the same key's round (the round answers in the form it
is handed), no key.
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched import engine as engine_mod
from etcd_tpu.batched import step as step_mod
from etcd_tpu.batched.shadow import ShadowCluster
from etcd_tpu.batched.step import (BULK_APP, KIND_APP, NUM_OCC, T_APP, T_SNAP,
                                   app_head, lane_occupancy, split_lanes)
from etcd_tpu.batched.telemetry import TM_INDEX

from .test_differential import device_state
from .test_scan_faults import inbox_equal

CONFIGS_DIR = os.path.join(os.path.dirname(__file__), "..", "..",
                           "benchmark", "configs")


def sizes(name: str) -> dict:
    with open(os.path.join(CONFIGS_DIR, name + ".json")) as f:
        return json.load(f)["sizes"]


DEEP64 = BatchedConfig(**dict(sizes("engine100k-r3-deeplog"), num_groups=8))
RING16 = BatchedConfig(
    num_groups=2, num_replicas=3, window=64, max_ents_per_msg=16,
    max_props_per_round=4, election_timeout=10, heartbeat_timeout=1,
    max_inflight=1 << 20, auto_compact=True)
CONFIGS = {"deep64": DEEP64, "ring16": RING16}
ROUNDS = 88


def schedule(kind: str, cfg) -> np.ndarray:
    """isolate [ROUNDS, R]. Every group is led from node 0 at first.
    ``reboot``: node 1 is away for 40 rounds and returns 40 x P entries
    behind (DEEP64: 80, two appends deep; RING16: 160 of the 32 kept, so
    by a snapshot, then appends of 16). ``ex-leader``: node 0 is away
    until CheckQuorum has stood it down and the others have elected
    (first appends of P + 1 entries), and returns with the entries it
    appended meanwhile to be truncated. ``flap``: node 2 is away six
    rounds, twice (6 x P entries: one append past the head)."""
    iso = np.zeros((ROUNDS, cfg.num_replicas), bool)
    if kind == "reboot":
        iso[4:44, 1] = True
    elif kind == "ex-leader":
        iso[4:40, 0] = True
    elif kind == "flap":
        iso[4:10, 2] = True
        iso[30:36, 2] = True
    else:
        assert kind == "steady"
    return iso


SCHEDULES = ("steady", "reboot", "ex-leader", "flap")
# Where no append ever states more than the head holds: steady rounds,
# and the ring of 64 under a flap (its healed replica is fed a snapshot
# every second round and four entries between them: ROADMAP D12).
NARROW = {("deep64", "steady"), ("ring16", "steady"), ("ring16", "flap")}


def build(cfg, tiles: int, split: bool) -> MultiRaftEngine:
    """An engine of `cfg`, every group led from node 0, settled. The
    tile constants and, for the lane in one piece, ``app_head`` are
    patched while the engine is built (it reads them then) and are no
    option of the program."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine_mod, "TILE_ALIGN", 1)
        mp.setattr(engine_mod, "TILE_ROWS",
                   cfg.num_instances // tiles if tiles else 1 << 40)
        if not split:
            mp.setattr(engine_mod, "app_head", lambda cfg: 0)
        eng = MultiRaftEngine(cfg)
        assert eng._tiles == max(tiles, 1)
        eng.campaign(np.arange(cfg.num_groups) * cfg.num_replicas)
        for _ in range(12):
            eng.step_round()
        # (The first scan is traced under the patch too.)
        eng.run_rounds(1, propose_n=offered(cfg),
                       isolate=np.zeros((1, cfg.num_replicas), bool))
    assert (eng.leaders() == 0).all()
    return eng


def offered(cfg):
    return jnp.full((cfg.num_instances,), cfg.max_props_per_round, jnp.int32)


def everything(eng: MultiRaftEngine) -> dict:
    got = {"state": [np.asarray(x) for x in jax.tree.leaves(eng.state)],
           "inbox": jax.tree.map(np.asarray, eng.inbox),
           "lanes": eng.lane_rounds(), "rare": eng.rare_rounds(),
           "last": np.asarray(eng.state.last)}
    if eng.cfg.telemetry:
        got["telemetry"] = eng.telemetry()
    return got


def rounds(eng: MultiRaftEngine, iso):
    """A call a round, and what the engine holds after each."""
    props = offered(eng.cfg)
    for t in range(len(iso)):
        eng.run_rounds(1, propose_n=props, isolate=iso[t:t + 1])
        yield everything(eng)


@functools.cache
def in_one_piece(name: str, kind: str):
    """Every round of `kind` with the lane in one piece, untiled: the
    parent's program, once a (configuration, schedule)."""
    cfg = CONFIGS[name]
    eng = build(cfg, 0, split=False)
    assert eng.bulk_rounds() == 0
    first = everything(eng)
    return [first] + list(rounds(eng, schedule(kind, cfg)))


def states_bulk(inbox, head: int) -> bool:
    app = np.asarray(inbox.valid)[:, :, KIND_APP] & (
        np.asarray(inbox.type)[:, :, KIND_APP] == T_APP)
    return bool((app & (np.asarray(inbox.n_ents)[:, :, KIND_APP] > head)).any())


def assert_same(got: dict, want: dict, what) -> None:
    for i, (x, y) in enumerate(zip(got["state"], want["state"])):
        assert x.dtype == y.dtype and (x == y).all(), (what, "state", i)
    inbox_equal(got["inbox"], want["inbox"])
    assert (got["lanes"] == want["lanes"]).all(), (what, "lanes")
    assert (got["rare"] == want["rare"]).all(), (what, "rare")
    for x, y in zip(got.get("telemetry", ()), want.get("telemetry", ())):
        assert (x == y).all(), (what, "telemetry")


# -- (a) the split against the lane in one piece, round by round -------------------


@pytest.mark.parametrize("tiles", [0, 2])
@pytest.mark.parametrize("kind", SCHEDULES)
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_split_lane_equals_the_lane_in_one_piece_every_round(
        name, kind, tiles):
    cfg = CONFIGS[name]
    head = app_head(cfg)
    assert head == cfg.max_props_per_round + 1
    want = in_one_piece(name, kind)
    eng = build(cfg, tiles, split=True)
    assert_same(everything(eng), want[0], (name, kind, "settled"))
    wide = 0
    for t, got in enumerate(rounds(eng, schedule(kind, cfg))):
        # (Round t delivered the inbox the round before left.)
        wide += states_bulk(want[t]["inbox"], head)
        assert_same(got, want[t + 1], (name, kind, tiles, t))
        # A message of the public inbox is what a writer of slots
        # states: zeros past the entries it carries, the tail's among
        # them (nothing of a spent tail shows).
        inbox = got["inbox"]
        e = np.arange(cfg.max_ents_per_msg)
        past = inbox.valid[..., None] & (e >= inbox.n_ents[..., None])
        assert not np.where(past, inbox.ent_terms, 0).any(), (name, kind, t)
    # The counter: the rounds whose inbox held an append past the head,
    # whatever the tiles (a round counts once, in whichever tile).
    assert eng.bulk_rounds() == wide
    assert wide < eng.lane_rounds()[KIND_APP]
    if (name, kind) in NARROW:
        assert wide == 0
    else:
        assert wide > 0, "the schedule sent no append past the head"
    total = eng.telemetry()[0].sum(axis=0) if cfg.telemetry else None
    if kind == "ex-leader" and cfg.telemetry:
        assert total[TM_INDEX["elections_won"]] > 0
    if kind == "reboot" and cfg.telemetry:
        assert total[TM_INDEX["append_rejected"]] > 0
        assert total[TM_INDEX["sent_snapshot"]] == 0  # carried by appends


def test_the_schedules_hold_what_they_are_named_for():
    """Of the reference's own rounds: a snapshot rides the ring's append
    lane in ``reboot`` and ``flap``; the returned ex-leader is handed an
    append whose tail columns lie over entries it holds (the suffix it
    appended while away, of an older term: rewritten from there)."""
    for kind in ("reboot", "flap"):
        assert any(((m["inbox"].type[:, :, KIND_APP] == T_SNAP)
                    & m["inbox"].valid[:, :, KIND_APP]).any()
                   for m in in_one_piece("ring16", kind)), kind
    # (DEEP64's: RING16 runs without CheckQuorum, its cut-off leader
    # fills its ring and comes back by a snapshot.)
    for name in ("deep64",):
        head = app_head(CONFIGS[name])
        seen = in_one_piece(name, "ex-leader")
        over = False
        for before, after in zip(seen, seen[1:]):
            m = before["inbox"]
            app = m.valid[:, :, KIND_APP] & (m.type[:, :, KIND_APP] == T_APP)
            reach = m.index[:, :, KIND_APP] + m.n_ents[:, :, KIND_APP]
            rewrites = (app & (m.n_ents[:, :, KIND_APP] > head)
                        & (m.index[:, :, KIND_APP] + head
                           < before["last"][:, None])
                        & (reach == after["last"][:, None]))
            over = over or bool(rewrites.any())
        assert over, name


# -- (b) against the shadow oracle over a reboot ------------------------------------


def test_a_reboots_return_matches_the_oracle_every_round():
    """DEEP64 through ``reboot`` and ``ex-leader`` back to back beside
    plain RawNodes stepped message by message: state of every replica
    after every round."""
    cfg = DEEP64
    g_n, r = cfg.num_groups, cfg.num_replicas
    eng = MultiRaftEngine(cfg)
    shadows = [
        ShadowCluster(
            r, election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, pre_vote=True, check_quorum=True,
            group=g, deterministic_timeouts=True,
            auto_compact_window=cfg.window, max_ents=cfg.max_ents_per_msg,
            max_props=cfg.max_props_per_round)
        for g in range(g_n)]
    eng.campaign(np.arange(g_n) * r)
    for sh in shadows:
        sh.round(campaigns=[0])
    for _ in range(12):
        eng.step_round()
        for sh in shadows:
            sh.round()
    iso = np.concatenate([schedule("reboot", cfg), schedule("ex-leader", cfg)])
    props = offered(cfg)
    for t in range(len(iso)):
        eng.run_rounds(1, tick=True, propose_n=props, isolate=iso[t:t + 1])
        cut = tuple(np.nonzero(iso[t])[0].tolist())
        for sh in shadows:
            sh.round(tick=True, offer=cfg.max_props_per_round, isolate=cut)
        want = [s for sh in shadows for s in sh.snapshot_state()]
        assert device_state(eng, cfg) == want, f"round {t}"
    assert eng.bulk_rounds() > 0
    counters, invariants = eng.telemetry()
    assert not invariants.any()
    assert counters.sum(axis=0)[TM_INDEX["sent_snapshot"]] == 0


# -- (c) an append of Wn + 1 entries in a steady batch ------------------------------


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_one_append_past_the_head_takes_the_whole_branch(name):
    cfg = CONFIGS[name]
    head = app_head(cfg)
    split, whole = build(cfg, 0, split=True), build(cfg, 0, split=False)
    inbox = jax.tree.map(np.array, split.inbox)
    at = np.argwhere(inbox.valid[:, :, KIND_APP]
                     & (inbox.type[:, :, KIND_APP] == T_APP))
    assert len(at) and not states_bulk(inbox, head)
    n, s = at[0]
    inbox.n_ents[n, s, KIND_APP] = head + 1
    inbox.ent_terms[n, s, KIND_APP, :head + 1] = inbox.term[n, s, KIND_APP]
    for eng in (split, whole):
        eng.inbox = jax.tree.map(jnp.asarray, inbox)
    lanes = split_lanes(split.inbox, head)
    occ = np.asarray(lane_occupancy(lanes))
    assert occ.shape == (NUM_OCC + 1,) and occ[BULK_APP]
    assert np.asarray(lane_occupancy(split_lanes(split.inbox))).shape == (
        NUM_OCC,)
    before = split.bulk_rounds()
    iso = np.zeros((1, cfg.num_replicas), bool)
    for eng in (split, whole):
        eng.run_rounds(1, propose_n=offered(cfg), isolate=iso)
    assert split.bulk_rounds() == before + 1
    assert whole.bulk_rounds() == 0
    assert_same(everything(split), everything(whole), name)
    # And the row took it: one entry more than its group's other
    # follower holds.
    last = np.asarray(split.state.last)
    g = n // cfg.num_replicas
    peers = [g * cfg.num_replicas + k for k in range(1, cfg.num_replicas)
             if g * cfg.num_replicas + k != n]
    assert last[n] > last[peers[0]]


# -- (d) the counter over a period, in tiles -----------------------------------------


@pytest.mark.parametrize("tiles", [0, 2, 4])
def test_bulk_rounds_are_the_returns_rounds_whatever_the_tiles(tiles):
    """One call of ROUNDS rounds of ``reboot`` on DEEP64: zero as the
    engine stands settled, then the return's rounds, the same count
    untiled and in 2 and 4 tiles (a round in which any tile ran the
    lane whole counts once)."""
    cfg, head = DEEP64, app_head(DEEP64)
    want = in_one_piece("deep64", "reboot")
    wide = sum(states_bulk(w["inbox"], head) for w in want[:-1])
    assert 0 < wide < 16
    eng = build(cfg, tiles, split=True)
    assert eng.bulk_rounds() == 0
    eng.run_rounds(ROUNDS, propose_n=offered(cfg),
                   isolate=schedule("reboot", cfg))
    assert eng.bulk_rounds() == wide
    got = everything(eng)
    for i, (x, y) in enumerate(zip(got["state"], want[-1]["state"])):
        assert (x == y).all(), ("state", i)
    inbox_equal(got["inbox"], want[-1]["inbox"])


# -- (e) the predicate and the refusal -----------------------------------------------


def test_the_lane_is_split_for_the_deep_log_alone():
    names = sorted(f[:-5] for f in os.listdir(CONFIGS_DIR)
                   if f.endswith(".json"))
    widths = lambda n: DEEP64._replace(**{  # noqa: E731
        k: sizes(n)[k] for k in ("max_ents_per_msg", "max_props_per_round")})
    split = {n: app_head(widths(n)) for n in names}
    assert split.pop("engine100k-r3-deeplog") == 3
    # (The parked served cell: E=8, P=4, three columns against nine.)
    assert split.pop("served1k-r3") == 0
    assert len(split) == 8 and not any(split.values()), split
    # Static, of E and P alone.
    for e, p, head in ((64, 2, 3), (4, 2, 0), (8, 4, 0), (16, 4, 5),
                       (16, 2, 3), (12, 2, 0), (13, 2, 3)):
        cfg = DEEP64._replace(max_ents_per_msg=e, max_props_per_round=p)
        assert app_head(cfg) == head, (e, p)


def test_over_nodes_the_split_is_refused_with_its_reason():
    with pytest.raises(ValueError, match="splits the append lane"):
        MultiRaftEngine(RING16, nodes=jax.devices()[:3])


def test_a_vector_without_the_bit_means_a_lane_in_one_piece():
    """A caller that hands the round its own occupancy vector says by
    its length which form it means: with the eight bits of lanes in one
    piece, slots are stepped in one piece (the same state and outbox as
    with no vector), and lanes in two halves are refused, not read with
    a bit that is not there."""
    cfg = RING16
    eng = build(cfg, 0, split=True)
    n = cfg.num_instances
    zb = jnp.zeros((n,), bool)
    args = (jnp.ones((n,), bool), zb, offered(cfg), zb)
    step = step_mod.make_step_round(cfg)
    short = lane_occupancy(split_lanes(eng.inbox))
    assert short.shape == (NUM_OCC,)
    want = step(eng.state, eng.inbox, *args)
    got = step(eng.state, eng.inbox, *args, lane_any=short)
    for x, y in zip(jax.tree.leaves(got[:2]), jax.tree.leaves(want[:2]),
                    strict=True):
        assert (np.asarray(x) == np.asarray(y)).all()
    with pytest.raises(ValueError, match="BULK_APP"):
        step(eng.state, split_lanes(eng.inbox, app_head(cfg)), *args,
             lane_any=short)
