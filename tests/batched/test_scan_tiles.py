"""The closed-loop scan in tiles of whole groups (ISSUE 35): a call
steps one block of rows through all its rounds, then the next
(``engine.scan_tiles``, ``MultiRaftEngine._init``'s ``tiled_loop``).
Raft groups share nothing, so the tiled scan must equal the one scan
over all rows bit for bit in everything but the payload of inbox slots
whose ``valid`` is false; and a shape that fits one tile must keep the
parent's program to the letter.

Round-step programs (``conftest.py``, ISSUE 35 audit): the five live
configurations at the CPU tests' 8 groups, every one a key already
(``test_scan_faults.CELL`` and ``R5``, ``test_scan_reconf.RC3``,
``test_scan_replace.RP4`` and ``engine64k-r3`` at 8 groups as
``tests/benchmark`` and ``test_scan_replace._lowered`` build it): a
tile steps the configuration's own round over fewer rows, no program
of its own.
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
from etcd_tpu.batched.engine import scan_tiles
from etcd_tpu.batched.step import MsgSlots

from . import test_scan_reconf as reconf
from . import test_scan_replace as replace
from .test_scan_faults import inbox_equal

CONFIGS = ("engine64k-r3", "engine10k-r5", "engine100k-r3", "engine1m-r3",
           "engine512k-r3of4")
ROUNDS, CALL = 128, 16
SPARE = 2  # the slot `engine512k-r3of4`'s groups leave empty at first


@functools.cache
def sizes(name: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "..", "..", "benchmark",
                           "configs", name + ".json")) as f:
        return json.load(f)["sizes"]


def schedules(cfg):
    """(isolate, control) over ROUNDS rounds: a node cut off and healed
    twice and, where the configuration applies changes itself, its
    cell's own cycle: the drain through a joint configuration
    (``engine1m-r3``) or the replacement with its wipe
    (``engine512k-r3of4``)."""
    r = cfg.num_replicas
    if cfg.replace_replicas:
        rows = [replace.replace_row(t, SPARE) for t in range(ROUNDS)]
        return replace.isolate_rows(rows), replace.control_rows(rows)
    if cfg.conf_entries:
        rows = [reconf.drain_row(t, 1, r) for t in range(ROUNDS)]
        return reconf.isolate_rows(rows, r), reconf.control_rows(rows)
    iso = np.zeros((ROUNDS, r), bool)
    iso[3:9, 1] = True
    iso[12:40, 0] = True
    iso[70:100, r - 1] = True
    return iso, None


def run(name: str, tiles: int, monkeypatch) -> MultiRaftEngine:
    """`name`'s configuration at 8 groups, its leaders drawn from a
    seed, settled, then ROUNDS rounds under `schedules`: three calls of
    ``run_rounds`` and the rest through ``run_rounds_pipelined``. The
    tile constants are this test's to patch and no option of the
    program; `tiles` 0 puts the tiled path out of reach."""
    cfg = BatchedConfig(**dict(sizes(name), num_groups=8))
    n, r, g_n = cfg.num_instances, cfg.num_replicas, cfg.num_groups
    monkeypatch.setattr(engine_mod, "TILE_ALIGN", 1)
    monkeypatch.setattr(engine_mod, "TILE_ROWS", n // tiles if tiles else 1 << 40)
    spare = {"spare": SPARE} if cfg.replace_replicas else {}
    eng = MultiRaftEngine(cfg, **spare)
    assert eng._tiles == max(tiles, 1)
    live = np.asarray([s for s in range(r) if not spare or s != SPARE])
    lead = live[np.random.default_rng(35).integers(0, len(live), g_n)]
    eng.campaign(np.arange(g_n) * r + lead)
    for _ in range(16):
        eng.step_round()
    assert (eng.leaders() == lead).all()
    iso, ctl = schedules(eng.cfg)
    props = jnp.full((n,), 2, jnp.int32)
    cut = lambda x, lo, hi: None if x is None else x[lo:hi]  # noqa: E731
    for lo in range(0, 3 * CALL, CALL):
        eng.run_rounds(CALL, propose_n=props, isolate=cut(iso, lo, lo + CALL),
                       control=cut(ctl, lo, lo + CALL))
    eng.run_rounds_pipelined(
        ROUNDS - 3 * CALL, chunk=CALL, propose_n=props,
        isolate=cut(iso, 3 * CALL, ROUNDS), control=cut(ctl, 3 * CALL, ROUNDS))
    return eng


def observed(eng: MultiRaftEngine) -> dict:
    """Everything the scan leaves behind, on the host."""
    got = {
        "state": [np.asarray(x) for x in jax.tree.leaves(eng.state)],
        "inbox": jax.tree.map(np.asarray, eng.inbox),
        "lane_rounds": eng.lane_rounds(),
        "commits": eng.commits(),
        "watch": eng.scan_watch(),
        "history": eng.scan_history(),
    }
    if eng.cfg.telemetry:
        got["telemetry"] = eng.telemetry()
    return got


@functools.cache
def untiled(name: str) -> dict:
    """The one scan over all rows, once a configuration."""
    with pytest.MonkeyPatch.context() as mp:
        return observed(run(name, 0, mp))


@pytest.mark.parametrize("tiles", [1, 2, 4])
@pytest.mark.parametrize("name", CONFIGS)
def test_the_tiled_scan_equals_the_one_scan_over_all_rows(
        name, tiles, monkeypatch):
    want = untiled(name)
    eng = run(name, tiles, monkeypatch)
    got = observed(eng)
    assert len(got["state"]) == len(want["state"])
    for i, (x, y) in enumerate(zip(got["state"], want["state"])):
        assert x.dtype == y.dtype and (x == y).all(), (
            type(eng.state)._fields, i)
    # `valid` equal and every field equal wherever a slot is valid; a
    # lane nobody of the batch wrote all zeros in both.
    inbox_equal(got["inbox"], want["inbox"])
    for key in ("lane_rounds", "commits", "history"):
        assert (got[key] == want[key]).all(), key
    assert got["watch"] == want["watch"]
    if eng.cfg.telemetry:
        for x, y in zip(got["telemetry"], want["telemetry"]):
            assert (x == y).all()
        assert not got["telemetry"][1].any(), "an invariant bit is set"
    # What was decided for the slots whose `valid` is false: the lane
    # skip is the tile's own, so a lane nobody of a TILE wrote holds
    # what ``empty_msgs`` holds on that tile's rows, every field zero
    # (the one scan exchanged emit's unsent request fields there if
    # some other block of rows had written the lane).
    rows = eng.cfg.num_instances // tiles
    for lo in range(0, eng.cfg.num_instances, rows):
        block = jax.tree.map(lambda x: x[lo:lo + rows], got["inbox"])
        empty = ~block.valid.any(axis=(0, 1))
        for f in MsgSlots._fields:
            assert not getattr(block, f)[:, :, empty].any(), (lo, f)
    # The schedule did something, and differently in different groups.
    assert (got["commits"].max(axis=1) > 0).all()
    assert got["lane_rounds"].any()
    if eng.cfg.conf_entries:
        assert got["watch"]["joint_instance_rounds"] > 0
        assert len(set(got["history"].tolist())) > eng.cfg.num_replicas
    if eng.cfg.replace_replicas:
        assert got["watch"]["replicas_reset"] == eng.cfg.num_groups


def test_a_lane_empty_in_one_tile_alone_comes_back_as_zeros_there(monkeypatch):
    """The one visible difference. Half the groups elect a leader and
    append, the other half (the second tile) never hear of one: the one
    scan over all rows exchanges the append lane for every row and
    leaves emit's unsent ``type`` in the idle rows' slots, ``valid``
    false; the tiled scan skips the lane in the idle tile and hands
    its rows back as ``empty_msgs`` has them."""
    cfg = BatchedConfig(**dict(sizes("engine100k-r3"), num_groups=8))
    n, r = cfg.num_instances, cfg.num_replicas
    monkeypatch.setattr(engine_mod, "TILE_ALIGN", 1)
    inboxes = []
    for tile_rows in (1 << 40, n // 2):
        monkeypatch.setattr(engine_mod, "TILE_ROWS", tile_rows)
        eng = MultiRaftEngine(cfg)
        eng.campaign(np.arange(cfg.num_groups // 2) * r + 1)
        eng.run_rounds(5, tick=False, propose_n=jnp.full((n,), 2, jnp.int32))
        assert (eng.leaders() == [1] * 4 + [-1] * 4).all()
        inboxes.append(jax.tree.map(np.asarray, eng.inbox))
    whole, tiled = inboxes
    inbox_equal(tiled, whole)
    assert whole.valid[:n // 2].any() and not whole.valid[n // 2:].any()
    assert whole.type[n // 2:].any(), "the one scan left nothing to see"
    for f in MsgSlots._fields:
        assert not getattr(tiled, f)[n // 2:].any(), f


@pytest.mark.parametrize("name", ["engine100k-r3", "engine512k-r3of4"])
def test_the_eager_round_in_tiles_equals_the_one_round(name, monkeypatch):
    """``step_round`` runs tile by tile too where the scan does (a
    configuration traces the round at one shape): every input it takes,
    per instance, drawn from a seed."""
    cfg = BatchedConfig(**dict(sizes(name), num_groups=8))
    n, r, g_n = cfg.num_instances, cfg.num_replicas, cfg.num_groups
    monkeypatch.setattr(engine_mod, "TILE_ALIGN", 1)
    spare = {"spare": SPARE} if cfg.replace_replicas else {}
    engines = []
    for tile_rows in (1 << 40, n // 4):
        monkeypatch.setattr(engine_mod, "TILE_ROWS", tile_rows)
        eng = MultiRaftEngine(cfg, **spare)
        rng = np.random.default_rng(3535)
        eng.campaign(np.arange(g_n) * r + rng.integers(0, 2, g_n))
        for t in range(24):
            more = {}
            if cfg.conf_entries:
                more["conf_req"] = jnp.asarray(np.where(
                    rng.random(n) < 0.1, replace.conf_code(
                        replace.CONF_ADD_LEARNER, SPARE), 0).astype(np.int32))
            if cfg.replace_replicas:
                more["wipe"] = jnp.asarray((rng.random(n) < 0.02) & (t == 20))
            eng.step_round(
                tick=True,
                propose_n=jnp.asarray(rng.integers(0, 3, n).astype(np.int32)),
                isolate=jnp.asarray(rng.random(n) < 0.1),
                transfer_to=jnp.asarray(np.where(
                    rng.random(n) < 0.05, rng.integers(1, r + 1, n), 0
                ).astype(np.int32)),
                read_req=jnp.asarray(rng.random(n) < 0.5), **more)
        engines.append(eng)
    whole, tiled = engines
    assert (whole._tiles, tiled._tiles) == (1, 4)
    for x, y in zip(jax.tree.leaves(tiled.state), jax.tree.leaves(whole.state)):
        assert x.dtype == y.dtype and (np.asarray(x) == np.asarray(y)).all()
    # The eager round exchanges every lane, emit's unsent fields too:
    # the same messages is `valid` and the fields where `valid`.
    valid = np.asarray(whole.inbox.valid)
    assert valid.any() and (valid == np.asarray(tiled.inbox.valid)).all()
    for f in MsgSlots._fields:
        a, b = (np.asarray(getattr(e.inbox, f)) for e in engines)
        at = valid.reshape(valid.shape + (1,) * (a.ndim - 3))
        assert a.dtype == b.dtype and (
            np.where(at, a, 0) == np.where(at, b, 0)).all(), f
    for x, y in zip(tiled.telemetry(), whole.telemetry()):
        assert (x == y).all()
    assert (whole.commits().max(axis=1) > 0).any()


def test_the_span_says_how_many_tiles_a_call_ran(monkeypatch):
    from etcd_tpu.obs import spans

    eng = run("engine100k-r3", 2, monkeypatch)
    mine = [s for s in spans.snapshot()
            if s.name == "engine.run_rounds"
            and s.stats.get("engine") == eng._serial]
    assert [s.stats["tiles"] for s in mine] == [2] * (ROUNDS // CALL)


# -- the rule ------------------------------------------------------------------

# Nothing under two tiles' rows (393,216) is tiled: `engine100k-r3`
# (307,200 rows) keeps the one scan, as its own A/B on the chip decided.
CELL_TILES = {"engine64k-r3": 1, "engine10k-r5": 1, "engine100k-r3": 1,
              "engine1m-r3": 16, "engine512k-r3of4": 16}


@pytest.mark.parametrize("name", CONFIGS)
def test_the_tile_count_of_a_live_cell(name):
    cfg = BatchedConfig(**sizes(name))
    tiles = scan_tiles(cfg)
    assert tiles == CELL_TILES[name]
    rows = cfg.num_instances // tiles
    assert rows * tiles == cfg.num_instances
    assert rows % cfg.num_replicas == 0, "a tile is whole groups"
    if tiles > 1:
        assert rows % engine_mod.TILE_ALIGN == 0
        assert rows <= engine_mod.TILE_ROWS


@pytest.mark.parametrize("what,fields,tiles", [
    ("no-aligned-divisor", dict(num_groups=1_000_003), 1),
    ("odd-multiple-of-the-alignment", dict(num_groups=3 * 65_536 + 1024), 193),
    ("fleet-summary", dict(num_groups=1_048_576, fleet_summary=True), 1),
    ("one-tile", dict(num_groups=65_536), 1),
    ("under-two-tiles", dict(num_groups=2 * 65_536 - 1024), 1),
    ("two-tiles", dict(num_groups=2 * 65_536), 2),
])
def test_the_tile_count_follows_from_the_shape(what, fields, tiles):
    cfg = BatchedConfig(**dict(sizes("engine64k-r3"), **fields))
    assert scan_tiles(cfg) == tiles
    assert cfg.num_instances % tiles == 0


# -- one tile is the parent's program --------------------------------------------


@pytest.mark.parametrize(
    "name", ["engine64k-r3", "engine10k-r5", "engine100k-r3"])
def test_under_the_constant_the_loop_is_the_untiled_text(name, monkeypatch):
    """With the rule in place a shape that fits one tile lowers to the
    text it lowers to with the tiled path out of reach (and that text
    is the parent's: ``test_scan_replace`` pins its digest)."""
    cfg, one, loop = replace._lowered(name)
    assert scan_tiles(cfg) == 1
    monkeypatch.setattr(engine_mod, "TILE_ROWS", 1 << 40)
    assert replace._lowered(name) == (cfg, one, loop)
    assert "dynamic_update_slice" not in loop
