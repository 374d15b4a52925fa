"""The closed loop placed a node a device (ISSUE 40):
``MultiRaftEngine(cfg, nodes=devices)`` holds slot s of every group on
device s, exchanges a round's messages between the devices with one
all-to-all a kind lane inside the scan (``step.exchange_lanes``) and
keeps every instance's logical id ``g * R + s``, so a group has to run
bit for bit as it does on one device: in state, log, masks, read state,
inbox, ``scan_history``, ``scan_watch``, telemetry and ``lane_rounds``,
and as ``batched/shadow.py``'s plain ``RawNode``s do.

Every node-placed run here happens in a child process with a time
limit (four of the eight devices ``tests/conftest.py`` forces): a
collective left inside a branch that only some nodes take never
returns, and a child that hangs fails its tests and does not hang the
suite. The child writes what it read back, in the logical order, to an
``.npz``; this process runs the same schedule on one device and on the
oracle and compares. Three runs: a whole replacement period of
``benchmark/traffic/replace-readindex.json``'s cycle, a node's rows in
two tiles (a node is retired in it and another cut off, so a lane is
written by some nodes only for many rounds); an election only node 1
campaigns in, then appends only node 1 sends (a lane a single node
alone occupies, from the scan's first round); and a node retired from
the first round to the last under timers.

Round-step programs (``conftest.py``): ``test_scan_replace.py``'s RP4
alone, a key already; the node-placed loops are other traces of it.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CASES = ("period", "lone_lane", "retired")
E0 = 2  # the slot every group leaves empty at first
LIMIT_S = 900  # a child's: its imports, two compiles and 144 rounds


# -- a case's schedule and one engine's run of it ------------------------------------


def build(case: str, placed: bool):
    """(engine, leader slots) of a case, settled: built on one device
    or over four, a node's rows in two tiles where `case` is the
    period."""
    import jax

    from etcd_tpu.batched import MultiRaftEngine
    from etcd_tpu.batched import engine as engine_mod

    from .test_scan_replace import RP4

    cfg = RP4
    g_n, r = cfg.num_groups, cfg.num_replicas
    if case == "period":
        engine_mod.TILE_ALIGN = 1
        engine_mod.TILE_ROWS = (g_n if placed else cfg.num_instances) // 2
    eng = MultiRaftEngine(
        cfg, spare=E0, **({"nodes": jax.devices()[:r]} if placed else {}))
    assert eng._tiles == (2 if case == "period" else 1)
    if case == "lone_lane":
        slots = np.full(g_n, 1)
    else:
        seated = np.asarray([s for s in range(r) if s != E0])
        slots = seated[np.random.default_rng(40).integers(0, r - 1, g_n)]
    eng.campaign(np.arange(g_n) * r + slots)
    if case != "lone_lane":
        for _ in range(8):
            eng.step_round()
    return eng, slots


def schedule(case: str):
    """[(rounds, tick, isolate, control)] of a case's scans."""
    from etcd_tpu.batched.engine import CTL_READS, CTL_RETIRE, control_cols

    from .test_scan_replace import (PERIOD, RP4, control_rows, isolate_rows,
                                    replace_row)

    if case == "period":
        rows = [replace_row(t, E0) for t in range(PERIOD)]
        ctl, iso = control_rows(rows), isolate_rows(rows)
        return [(64, True, iso[lo:lo + 64], ctl[lo:lo + 64])
                for lo in (0, 64)]
    ctl = np.zeros((16, control_cols(RP4)), np.int32)
    ctl[:, CTL_READS] = 1
    if case == "retired":
        ctl[:, CTL_RETIRE] = 3 + 1  # node 3, from the first round
    # The lone lane: the timers off, so nobody but node 1 ever
    # campaigns and nobody but its leaders sends an append.
    return [(16, case == "retired", np.zeros((16, 4), bool), ctl)]


def run(case: str, placed: bool) -> dict:
    """A case on one engine: what it hands back, every array in the
    logical order, after each scan (``<call>/<name>``)."""
    import jax.numpy as jnp

    from etcd_tpu.batched.step import MsgSlots

    eng, slots = build(case, placed)
    cfg = eng.cfg
    props = jnp.full((cfg.num_instances,), 2, jnp.int32)
    out = {"slots": slots}
    for i, (rounds, tick, iso, ctl) in enumerate(schedule(case)):
        eng.run_rounds(rounds, tick=tick, propose_n=props, isolate=iso,
                       control=ctl)
        st = eng.state
        got = {f: eng.logical(getattr(st, f)) for f in st._fields
               if f != "conf"}
        got.update({"conf." + f: eng.logical(getattr(st.conf, f))
                    for f in st.conf._fields})
        got.update({"inbox." + f: eng.logical(getattr(eng.inbox, f))
                    for f in MsgSlots._fields})
        seq, index, ready = eng.read_states()
        counters, invariants = eng.telemetry()
        watch = eng.scan_watch()
        got.update({
            "read_states": np.stack([seq, index, ready.astype(np.int32)]),
            "leaders": eng.leaders(), "commits": eng.commits(),
            "terms": eng.terms(), "scan_history": eng.scan_history(),
            "scan_watch": np.asarray([watch[k] for k in sorted(watch)]),
            "telemetry.counters": counters,
            "telemetry.invariants": invariants,
            "lane_rounds": eng.lane_rounds(),
            "lane_exchanges": eng.lane_exchanges(),
        })
        out.update({f"{i}/{k}": np.asarray(v) for k, v in got.items()})
    out["calls"] = np.asarray(len(schedule(case)))
    return out


# -- the three runs, each twice -------------------------------------------------------


@pytest.fixture(scope="module", params=CASES)
def pair(request, tmp_path_factory):
    """(case, the child's node-placed run, this process's run on one
    device)."""
    case = request.param
    path = str(tmp_path_factory.mktemp("nodes") / f"{case}.npz")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    try:
        child = subprocess.run(
            [sys.executable, "-m", "tests.batched.test_scan_nodes", case,
             path], cwd=REPO, env=env, capture_output=True, text=True,
            timeout=LIMIT_S)
    except subprocess.TimeoutExpired:
        pytest.fail(
            f"the node-placed run of {case!r} did not end in {LIMIT_S} s: "
            "a collective inside a branch that only some nodes take?")
    assert child.returncode == 0, child.stderr[-3000:]
    from etcd_tpu.batched import engine as engine_mod

    tiles = engine_mod.TILE_ROWS, engine_mod.TILE_ALIGN
    try:
        one = run(case, placed=False)
    finally:
        engine_mod.TILE_ROWS, engine_mod.TILE_ALIGN = tiles
    with np.load(path) as placed:
        return case, dict(placed), one


def _state_fields():
    from etcd_tpu.batched.state import BatchedState, ConfLanes

    return BatchedState._fields + tuple(
        "conf." + f for f in ConfLanes._fields)


WHOLE = ("read_states", "leaders", "commits", "terms", "scan_history",
         "scan_watch", "telemetry.counters", "telemetry.invariants",
         "lane_rounds")


@pytest.mark.parametrize("field", _state_fields() + WHOLE)
def test_placed_over_nodes_equals_one_device(pair, field):
    """Every field of the state, every lane of the configuration, and
    everything the engine hands back, after each scan."""
    case, placed, one = pair
    for call in range(int(one["calls"])):
        a, b = placed[f"{call}/{field}"], one[f"{call}/{field}"]
        assert a.dtype == b.dtype and a.shape == b.shape, (case, call)
        where = np.argwhere(a != b)[:3].tolist()
        assert not where, (case, call, field, where)


def test_the_inbox_holds_the_same_messages(pair):
    """``valid`` equal and every field equal where valid; a lane with no
    valid slot is zeros between nodes as it is in tiles."""
    from etcd_tpu.batched.step import MsgSlots

    case, placed, one = pair
    for call in range(int(one["calls"])):
        valid = placed[f"{call}/inbox.valid"]
        assert (valid == one[f"{call}/inbox.valid"]).all(), (case, call)
        empty = ~valid.any(axis=(0, 1))
        for f in MsgSlots._fields:
            a, b = placed[f"{call}/inbox.{f}"], one[f"{call}/inbox.{f}"]
            assert a.dtype == b.dtype, f
            at = valid.reshape(valid.shape + (1,) * (a.ndim - 3))
            assert (np.where(at, a, 0) == np.where(at, b, 0)).all(), (
                case, call, f)
            assert not a[:, :, empty].any(), (case, call, f)


def test_lanes_that_crossed_are_counted(pair):
    """``lane_exchanges``: zeros on one device; between nodes a lane
    crosses in a tile's round where some node wrote it, so in every
    round ``lane_rounds`` counts it occupied and, the call's last round
    apart (whose messages wait in the inbox), in no other: tiles times
    the rounds occupied, give or take a round a tile a call."""
    case, placed, one = pair
    tiles = 2 if case == "period" else 1
    last = int(one["calls"]) - 1
    assert not one[f"{last}/lane_exchanges"].any()
    crossed = placed[f"{last}/lane_exchanges"].astype(np.int64)
    occupied = placed[f"{last}/lane_rounds"].astype(np.int64)
    assert crossed.shape == (6,) and crossed.sum() > 0
    assert (abs(crossed - tiles * occupied) <= tiles * (last + 1)).all(), (
        crossed, occupied)
    if case == "lone_lane":
        # Votes by node 1 alone, then appends and heartbeats by it
        # alone: those lanes crossed all the same.
        assert crossed[1] >= 8 and crossed[2] >= 4 and crossed[0] >= 1


# -- against the oracle -----------------------------------------------------------------


def test_placed_over_nodes_equals_the_oracle(pair):
    """``batched/shadow.py`` stepped through the same rounds: state,
    membership, read state and log of every replica of every group
    after the last scan."""
    from etcd_tpu.batched.engine import (CTL_CONF, CTL_FROM, CTL_RETIRE,
                                         CTL_TO, CTL_WIPE)

    from .test_scan_replace import RP4, _first, make_shadows

    case, placed, _one = pair
    cfg = RP4
    g_n, r, w = cfg.num_groups, cfg.num_replicas, cfg.window
    shadows = make_shadows(cfg, E0)
    slots = placed["slots"]
    for g, sh in enumerate(shadows):
        sh.round(campaigns=[int(slots[g])])
        if case != "lone_lane":
            for _ in range(8):
                sh.round()
    plan = schedule(case)
    for rounds, tick, iso, ctl in plan:
        for t in range(rounds):
            node = lambda c: (None if ctl[t, c] == 0  # noqa: E731
                              else int(ctl[t, c]) - 1)
            away = [int(s) for s in np.nonzero(iso[t])[0]]
            if node(CTL_RETIRE) is not None:
                away.append(node(CTL_RETIRE))
            for sh in shadows:
                sh.round(tick=tick, offer=2, isolate=away, reads=True,
                         conf=int(ctl[t, CTL_CONF]), drained=node(CTL_FROM),
                         transfer_to=node(CTL_TO), wipe=node(CTL_WIPE))
    get = lambda f: placed[f"{len(plan) - 1}/{f}"]  # noqa: E731
    n = cfg.num_instances
    got = [tuple(int(get(f)[i]) for f in (
        "term", "role", "lead", "commit", "last")) for i in range(n)]
    want = [s for sh in shadows for s in sh.snapshot_state()]
    assert got == want, (case, "state", _first(got, want))
    pick = lambda a, i: tuple(np.nonzero(a[i])[0].tolist())  # noqa: E731
    joint = get("in_joint")
    got = [(pick(get("voter"), i),
            pick(get("voter_out"), i) if joint[i] else (),
            pick(get("learner"), i), pick(get("conf.learner_next"), i))
           for i in range(n)]
    want = [m for sh in shadows for m in sh.membership()]
    assert got == want, (case, "membership", _first(got, want))
    seq, index, ready = get("read_states")
    got = list(zip(seq.tolist(), index.tolist(), ready.astype(bool).tolist()))
    want = [x for sh in shadows for x in sh.read_state()]
    assert got == want, (case, "reads", _first(got, want))
    ring, floor, last = get("log_term"), get("snap_index"), get("last")
    for g, sh in enumerate(shadows):
        for s in range(r):
            i = g * r + s
            log = [(k, int(ring[i, k % w]))
                   for k in range(int(floor[i]) + 1, int(last[i]) + 1)]
            assert log == sh.log_terms(s), (case, "log", g, s)


# -- the placement itself -----------------------------------------------------------------


def test_rows_and_the_logical_order():
    """Placed order is node after node; ``logical`` and the ids the
    engine hands the round agree on ``g * R + s``."""
    import jax

    from etcd_tpu.batched import MultiRaftEngine

    from .test_scan_replace import RP4

    eng = MultiRaftEngine(RP4, spare=E0, nodes=jax.devices()[:4])
    g_n, r = RP4.num_groups, RP4.num_replicas
    ids = np.arange(g_n * r)
    rows = np.asarray(eng._rows(ids))
    assert sorted(rows.tolist()) == ids.tolist()
    assert (rows == (ids % r) * g_n + ids // r).all()
    placed = np.asarray(eng._place(jax.numpy.asarray(ids, np.int32)))
    assert (placed[rows] == ids).all()
    assert (eng.logical(placed) == ids).all()
    # The timeout a replica drew at reset count 0 is its logical id's.
    et = RP4.election_timeout
    assert (eng.logical(eng.state.randomized_timeout)
            == et + ((ids + 1) * 7919) % et).all()
    # A node holds its slot of every group.
    shards = {s.device: s.data for s in eng.state.term.addressable_shards}
    assert len(shards) == r and all(
        v.shape == (g_n,) for v in shards.values())


@pytest.mark.parametrize("nodes,cfg,match", [
    (3, {}, "num_replicas"), (4, {"fleet_summary": True}, "fleet_summary")])
def test_what_cannot_be_placed_is_refused(nodes, cfg, match):
    import jax

    from etcd_tpu.batched import MultiRaftEngine

    from .test_scan_replace import RP4

    with pytest.raises(ValueError, match=match):
        MultiRaftEngine(RP4._replace(**cfg), spare=E0,
                        nodes=jax.devices()[:nodes])


def test_tiles_of_a_nodes_rows():
    """``scan_tiles(cfg, nodes=True)`` holds the rule to one node's
    rows, a row a group: the cell's 1,048,576 rows a chip run in 8
    tiles of 131,072, and half that in 4."""
    from etcd_tpu.batched import BatchedConfig
    from etcd_tpu.batched.engine import TILE_ALIGN, TILE_ROWS, scan_tiles

    with open(os.path.join(REPO, "benchmark", "configs",
                           "engine1m-r3of4-x4.json")) as f:
        sizes = json.load(f)["sizes"]
    cfg = BatchedConfig(**sizes)
    assert (cfg.num_groups, cfg.num_replicas) == (1_048_576, 4)
    assert scan_tiles(cfg, nodes=True) == 8
    assert cfg.num_groups // 8 == 131_072 <= TILE_ROWS
    assert 131_072 % TILE_ALIGN == 0
    assert scan_tiles(cfg._replace(num_groups=524_288), nodes=True) == 4
    # On one device the same shape would run in tiles of whole groups.
    assert (cfg.num_instances // scan_tiles(cfg)) % cfg.num_replicas == 0


if __name__ == "__main__":  # the child: python -m tests.batched.test_scan_nodes
    np.savez(sys.argv[2], **run(sys.argv[1], placed=True))
