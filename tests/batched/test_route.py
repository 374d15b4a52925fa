"""route(): the sender/target exchange on the layout the round carries.

``step.route()`` computes the routed inbox with the instance axis N
whole (row shifts and selects under ``n % R == t``). The spelling it
replaced — ``reshape(G, R, ...) -> swapaxes -> reshape`` — is the
oracle here and lives nowhere else: the inbox must equal it bit for
bit for every R, both carry dtypes and both ``lanes_minor`` layouts,
the lowering must never give the group or the replica an axis of its
own, and the engine's eager path must stay two programs. Handed the
outbox's lane occupancy (ISSUE 31), route() exchanges only the lanes
somebody wrote: those equal the oracle bit for bit, the others come
out as ``empty_msgs``, and the closed loop, which routes that way,
equals single rounds that exchange every lane.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke  # tests/conftest.py puts the repo root on sys.path
from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.step import (
    KIND_APP,
    KIND_APP_RESP,
    KIND_HB,
    KIND_HB_RESP,
    KIND_VOTE,
    NARROW_MSG_DTYPES,
    NUM_KINDS,
    MsgSlots,
    route,
)

from .test_differential_wide import make_pair
from .test_scan_faults import CELL, _fields_equal, inbox_equal

E = 4
REPLICAS = (1, 2, 3, 5, 7)


def cfg_of(groups: int, replicas: int, **kw) -> BatchedConfig:
    return BatchedConfig(
        num_groups=groups, num_replicas=replicas, window=32,
        max_ents_per_msg=E, max_props_per_round=2,
        election_timeout=1 << 20, heartbeat_timeout=4, auto_compact=True,
        **kw)


def transposing_oracle(groups: int, replicas: int, x: np.ndarray):
    """The three lines route() was until PR 25."""
    y = x.reshape((groups, replicas) + x.shape[1:])
    y = np.swapaxes(y, 1, 2)
    return y.reshape((groups * replicas,) + x.shape[1:])


def random_outbox(rng, groups: int, replicas: int, narrow: bool) -> MsgSlots:
    shape = (groups * replicas, replicas, NUM_KINDS)

    def field(name):
        if name in ("valid", "reject"):
            return rng.random(shape) < 0.5
        if name == "ent_terms":
            return rng.integers(-2**31, 2**31, shape + (E,), dtype=np.int64
                                ).astype(np.int32)
        dt = NARROW_MSG_DTYPES.get(name, jnp.int32) if narrow else jnp.int32
        info = np.iinfo(np.dtype(dt))
        return rng.integers(info.min, info.max, shape, dtype=np.int64,
                            endpoint=True).astype(dt)

    return MsgSlots(**{f: jnp.asarray(field(f)) for f in MsgSlots._fields})


@pytest.mark.parametrize("narrow", [False, True], ids=["wide", "narrow"])
@pytest.mark.parametrize("replicas", REPLICAS)
def test_inbox_equals_transposing_oracle(replicas, narrow):
    groups = 5
    out = random_outbox(np.random.default_rng(1000 * replicas + narrow),
                        groups, replicas, narrow)
    inbox = route(cfg_of(groups, replicas, narrow_lanes=narrow), out)
    for f in MsgSlots._fields:
        got, sent = np.asarray(getattr(inbox, f)), np.asarray(getattr(out, f))
        assert got.dtype == sent.dtype, f
        assert got.shape == sent.shape, f
        assert (got == transposing_oracle(groups, replicas, sent)).all(), f


OCCUPANCY = {
    "none": (),
    "app": (KIND_APP, KIND_APP_RESP),
    "app-hb": (KIND_APP, KIND_HB, KIND_APP_RESP, KIND_HB_RESP),
    "all": tuple(range(NUM_KINDS)),
}


def only_lanes(out: MsgSlots, lanes) -> MsgSlots:
    """`out` with nothing valid outside `lanes`; the payload fields
    there stay, as emit leaves term, type and commit in the request
    slots it does not send."""
    keep = np.isin(np.arange(NUM_KINDS), lanes)
    return out._replace(valid=out.valid & jnp.asarray(keep))


def lane_any_of(out: MsgSlots):
    return jnp.any(out.valid, axis=(0, 1))


@pytest.mark.parametrize("occupied", list(OCCUPANCY))
@pytest.mark.parametrize("narrow", [False, True], ids=["wide", "narrow"])
@pytest.mark.parametrize("replicas", [3, 5])
def test_only_occupied_lanes_are_exchanged(replicas, narrow, occupied):
    groups = 5
    lanes = OCCUPANCY[occupied]
    out = only_lanes(
        random_outbox(np.random.default_rng(31 * replicas + narrow),
                      groups, replicas, narrow), lanes)
    cfg = cfg_of(groups, replicas, narrow_lanes=narrow)
    lane_any = lane_any_of(out)
    assert np.asarray(lane_any).nonzero()[0].tolist() == sorted(lanes)
    skipping, whole = route(cfg, out, lane_any), route(cfg, out)
    for f in MsgSlots._fields:
        sent = np.asarray(getattr(out, f))
        want = transposing_oracle(groups, replicas, sent)
        got = np.asarray(getattr(skipping, f))
        assert got.dtype == sent.dtype and got.shape == sent.shape, f
        # No occupancy given: the whole inbox is the oracle's, as ever.
        assert (np.asarray(getattr(whole, f)) == want).all(), f
        for k in range(NUM_KINDS):
            if k in lanes:
                assert (got[:, :, k] == want[:, :, k]).all(), (f, k)
            else:
                assert not got[:, :, k].any(), (f, k)
    assert (np.asarray(skipping.valid)
            == transposing_oracle(groups, replicas,
                                  np.asarray(out.valid))).all()


@pytest.mark.parametrize("replicas", [3, 5])
def test_a_lane_emptied_leaves_no_message_behind(replicas):
    """Routed into last round's inbox: a lane occupied then and empty
    now is wiped, `valid` with it (a slot left behind would be
    delivered twice); one empty then and now is left as it was; one
    occupied now is the oracle's whatever it held."""
    groups = 5
    cfg = cfg_of(groups, replicas)
    rng = np.random.default_rng(replicas)
    first = only_lanes(random_outbox(rng, groups, replicas, False),
                       OCCUPANCY["app-hb"])
    second = only_lanes(random_outbox(rng, groups, replicas, False),
                        (KIND_VOTE,) + OCCUPANCY["app"])
    was = lane_any_of(first)
    inbox = route(cfg, first, was)
    assert np.asarray(inbox.valid)[:, :, KIND_HB].any()
    inbox = route(cfg, second, lane_any_of(second), (inbox, was))
    for f in MsgSlots._fields:
        got = np.asarray(getattr(inbox, f))
        want = transposing_oracle(groups, replicas,
                                  np.asarray(getattr(second, f)))
        for k in range(NUM_KINDS):
            if k in (KIND_VOTE,) + OCCUPANCY["app"]:
                assert (got[:, :, k] == want[:, :, k]).all(), (f, k)
            else:
                assert not got[:, :, k].any(), (f, k)


@pytest.mark.parametrize("replicas", REPLICAS)
def test_wraparound_rows_never_leak(replicas):
    """Every slot of the outbox carries its own row's group (and, in
    ent_terms, row and column): an inbox row may hold nothing from
    another group, the first and the last group included, where the
    shifted planes run off the ends of N."""
    groups = 3
    n = groups * replicas
    shape = (n, replicas, NUM_KINDS)
    row = np.arange(n, dtype=np.int32)[:, None, None]
    col = np.arange(replicas, dtype=np.int32)[None, :, None]
    gid = np.broadcast_to(row // replicas + 1, shape)
    fields = {f: jnp.asarray(gid) for f in MsgSlots._fields}
    fields["valid"] = fields["reject"] = jnp.ones(shape, bool)
    ent = np.broadcast_to((row * replicas + col)[..., None], shape + (E,))
    fields["ent_terms"] = jnp.asarray(ent)
    inbox = route(cfg_of(groups, replicas), MsgSlots(**fields))
    assert np.asarray(inbox.valid).all() and np.asarray(inbox.reject).all()
    for f in ("type", "term", "ctx"):
        assert (np.asarray(getattr(inbox, f)) == gid).all(), f
    # inbox[g*R + t, s] is what row g*R + s addressed to column t.
    want = (row // replicas * replicas + col) * replicas + row % replicas
    assert (np.asarray(inbox.ent_terms) == want[..., None]).all()


def test_lowering_never_splits_the_instance_axis():
    """G=8, R=3: under raft_route no tensor has G or R as an axis of
    its own (no [8,3,3,6]), and nothing transposes."""
    groups, replicas = 8, 3
    cfg = cfg_of(groups, replicas)
    out = random_outbox(np.random.default_rng(7), groups, replicas, False)
    lowered = jax.jit(lambda o: route(cfg, o)).lower(out)
    text = lowered.as_text(debug_info=True)
    assert "raft_route" in text
    assert "transpose" not in text
    shapes = set(re.findall(r"tensor<((?:\d+x)+)", text))
    assert shapes, text[:400]
    n = groups * replicas
    for s in shapes:
        dims = [int(d) for d in s.rstrip("x").split("x")]
        # Every array keeps N (padded for the shifts, or whole) first.
        assert dims[0] in (n, n + 2 * (replicas - 1)), s
    compiled = lowered.compile().as_text()
    assert not re.search(r"\btranspose\(", compiled)
    assert f"[{groups},{replicas},{replicas},{NUM_KINDS}" not in compiled


def _eqns(jaxpr, routed=False, branch=False):
    """(equation, under raft_route, inside one of its branches) for
    every equation of `jaxpr` and of what it calls. A sub-jaxpr's name
    stacks start afresh, so the scope is handed down."""
    for e in jaxpr.eqns:
        here = routed or "raft_route" in str(e.source_info.name_stack)
        yield e, here, branch
        for p in e.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(
                        sub, here,
                        branch or (here and e.primitive.name == "cond"))


def test_the_scan_routes_lane_by_lane_with_no_restack():
    """The closed loop holds one conditional a kind lane under
    raft_route; there too no array gives the group or the replica an
    axis of its own; and outside the branches nothing concatenates or
    transposes a whole [N, R, K] field: a branch returns its lane and
    the lanes ride the scan as they are. (Per-lane results re-stacked
    relaid the whole carried inbox out on the chip, K into the
    sublanes: PERF.md section 6, PR 31.)"""
    cfg = cfg_of(4, 3, lanes_minor=True)  # test_scan_equals_...'s
    eng = MultiRaftEngine(cfg)
    n, r = cfg.num_instances, cfg.num_replicas
    traced = eng._closed_loop.trace(
        eng.state, eng.inbox, eng._zeros_b, eng._zeros_i, eng._tel(),
        eng._flt(), eng._lanes, None, 16)
    routed = [(e, branch) for e, here, branch in _eqns(traced.jaxpr.jaxpr)
              if here]
    conds = [e for e, branch in routed
             if e.primitive.name == "cond" and not branch]
    assert len(conds) == NUM_KINDS
    assert all(len(e.params["branches"]) == 3 for e in conds)
    whole = {(n, r, NUM_KINDS), (n, r, NUM_KINDS, E)}
    for e, branch in routed:
        shapes = [v.aval.shape for v in e.outvars if hasattr(v.aval, "shape")]
        for shape in shapes:
            if len(shape) >= 2:
                # Every array keeps N (padded for the shifts, or whole).
                assert shape[0] in (n, n + 2 * (r - 1)), (e.primitive, shape)
        if not branch:
            assert not (e.primitive.name in ("concatenate", "transpose")
                        and whole & set(shapes)), e


def test_first_step_round_compiles_round_and_route_only():
    """The campaign round of a fresh engine is two programs: were
    route() to run eagerly again, op by op, each op would be one more
    (six reshapes made 370 s of a cold start at G=65536)."""
    # A config no other test builds, so both programs compile here.
    eng = MultiRaftEngine(cfg_of(3, 3, lanes_minor=True, narrow_lanes=True))
    meter = chip_smoke.CompileMeter()
    eng.step_round()
    jax.block_until_ready(eng.inbox)
    names = [name for name, _secs in meter.programs]
    assert sorted(names) == ["jit(route)", "jit(step_round)"], names


def append_schedule(lanes_minor):
    """Steady appends, nobody cut: the vote lanes never hold a thing."""
    cfg = cfg_of(4, 3, lanes_minor=lanes_minor)
    return cfg, 2, np.zeros((16, 3), bool)


def election_schedule(lanes_minor):
    """Timer elections live, node 0 cut off from round 2 to 29 and
    healed: every lane is busy in some rounds and idle in others.
    Configurations other modules build (CELL is test_scan_faults' and
    tests/benchmark's; the n-major one test_differential_wide's)."""
    cfg = CELL if lanes_minor else make_pair(
        groups=2, election_timeout=10, auto_compact=True)[0]
    sched = np.zeros((48, 3), bool)
    sched[2:30, 0] = True
    return cfg, cfg.max_props_per_round, sched


@pytest.mark.parametrize("schedule", [append_schedule, election_schedule],
                         ids=["append", "elections"])
@pytest.mark.parametrize("lanes_minor", [False, True],
                         ids=["n-major", "n-minor"])
def test_scan_equals_single_rounds(lanes_minor, schedule):
    """run_rounds (route() traced into the scan, handed each round's
    lane occupancy) equals as many step_rounds (route() as its own
    program, every lane exchanged): every state field, the messages in
    flight, and the lane counter against the occupancy counted here,
    round by round."""
    cfg, n_props, sched = schedule(lanes_minor)
    rounds, r = sched.shape[0], cfg.num_replicas
    a, b = MultiRaftEngine(cfg), MultiRaftEngine(cfg)
    leaders = [g * r + g % r for g in range(cfg.num_groups)]
    props = jnp.full((cfg.num_instances,), n_props, jnp.int32)
    for eng in (a, b):
        eng.campaign(leaders)
        for _ in range(4):
            eng.step_round()
    term0 = np.asarray(a.state.term).copy()
    before = a.lane_rounds()
    for lo in range(0, rounds, 16):
        a.run_rounds(16, tick=True, propose_n=props,
                     isolate=sched[lo:lo + 16] if sched.any() else None)
    slots = np.arange(cfg.num_instances) % r
    occupied = np.zeros((NUM_KINDS,), np.int64)
    for t in range(rounds):
        occupied += np.asarray(b.inbox.valid).any(axis=(0, 1))
        b.step_round(tick=True, propose_n=props,
                     isolate=jnp.asarray(sched[t][slots]))
    assert (np.asarray(a.state.commit) > 0).all()
    _fields_equal(a.state, b.state, "state")
    inbox_equal(a.inbox, b.inbox)
    assert (a.lane_rounds() - before == occupied).all(), (
        a.lane_rounds() - before, occupied)
    if sched.any():
        assert (np.asarray(a.state.term) > term0).any(), "no election ran"
        assert 0 < occupied[KIND_VOTE] < rounds
    else:
        assert occupied[KIND_VOTE] == 0 and occupied[KIND_APP] == rounds
        assert 0 < occupied[KIND_HB] < rounds
