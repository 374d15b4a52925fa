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
equals single rounds that exchange every lane. The round hands its
outbox on in the form it was handed its inbox (ISSUE 33): six kind
lanes for lanes, entries in the append lane alone, and the scan's body
never holds an [N, R, K] array; slots for slots, the same bits. A lane
carries the fields its messages use and no other (ISSUE 48,
``step.LANE_FIELDS``): the outboxes made here hold zero everywhere
else, as every writer's do (``test_lane_fields.py`` holds that).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke  # tests/conftest.py puts the repo root on sys.path
from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.engine import CTL_COLS
from etcd_tpu.batched.step import (
    KIND_APP,
    KIND_APP_RESP,
    KIND_HB,
    KIND_HB_RESP,
    KIND_VOTE,
    LANE_FIELDS,
    NARROW_MSG_DTYPES,
    NUM_KINDS,
    T_APP,
    T_SNAP,
    MsgSlots,
    make_step_round,
    route,
    split_lanes,
    stack_lanes,
)

from .test_deliver_shapes import make_engine as differential_engine
from .test_differential_wide import make_pair
from .test_scan_faults import (CELL, R3_MAJOR, R5, _fields_equal,
                               inbox_equal)
from .test_scan_reconf import RC3, RC5

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
    """Random bits in every field of every lane that some message type
    of the lane states, and zero in the rest, as a round makes it: no
    writer puts anything else in a field outside `LANE_FIELDS` (entries
    travel in the append lane alone, ISSUE 33; `reject` in two response
    lanes, `commit` in the two leader lanes, ...: ISSUE 48), which is
    why the lane form carries none of them and `route()` by lane hands
    them back as zeros."""
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

    def written(name):
        x = field(name)
        live = np.array([name in fs for fs in LANE_FIELDS])
        return np.where(live[:, None] if name == "ent_terms" else live,
                        x, np.zeros_like(x))

    return MsgSlots(**{f: jnp.asarray(written(f)) for f in MsgSlots._fields})


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
    """`out` (`random_outbox`'s: zero in every field a lane does not
    carry) with nothing valid outside `lanes`: the payload fields there
    stay, as emit leaves term, type and commit in the request slots it
    does not send."""
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


# -- the outbox leaves the round as kind lanes (ISSUE 33) --------------------------


def _trace_scan(cfg, rounds=64):
    """The closed loop of `cfg` traced (nothing compiles), with a
    control schedule where the configuration has a control plane of its
    own to trace."""
    eng = MultiRaftEngine(cfg)
    ctl = watch = None
    if cfg.conf_entries:
        ctl, _ = eng._control_schedule(
            np.zeros((rounds, CTL_COLS), np.int32), rounds)
        watch = eng._watch
    return eng._closed_loop.trace(
        eng.state, eng.inbox, eng._zeros_b, eng._zeros_i, eng._tel(),
        eng._flt(), eng._lanes, None, rounds, ctl, watch)


# Values other tests build already (no round-step key of this file's
# own): R=3 and R=5, both layouts, the counter plane and the
# configuration lanes on and off.
SCANNED = {
    "r3-major": R3_MAJOR,
    "r3-minor": cfg_of(4, 3, lanes_minor=True),
    "r3-minor-telemetry": CELL,
    "r5-minor": R5,
    "r3-minor-telemetry-conf": RC3,
    "r5-major-conf": RC5,
}


@pytest.mark.parametrize("name", list(SCANNED))
def test_the_scans_body_holds_no_packed_outbox(name):
    """Inside the 64-round scan no value has the shape of a packed
    field, [N, R, K] or [N, R, K, E], in either order of its axes
    (under `lanes_minor` the vmap's own arrays run [R, K, N]): emit
    makes lanes, route_lanes takes them, and the inbox is stacked once,
    after the scan. Exactly one [N, R, E] array is exchanged a round,
    the append lane's `ent_terms`, and of [N, R] planes the 35 that
    `LANE_FIELDS` names; a lane carries no other field (ISSUE 48)."""
    cfg = SCANNED[name].validate().resolved()
    n, r, e = cfg.num_instances, cfg.num_replicas, cfg.max_ents_per_msg
    closed = _trace_scan(cfg).jaxpr.jaxpr
    scans = [q for q in closed.eqns if q.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 64
    body = scans[0].params["jaxpr"].jaxpr
    packed = {tuple(sorted((n, r, NUM_KINDS))),
              tuple(sorted((n, r, NUM_KINDS, e)))}
    exchanged = []
    seen = 0
    for q, routed, _branch in _eqns(body):
        for v in list(q.invars) + list(q.outvars):
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None:
                seen += 1
                assert tuple(sorted(shape)) not in packed, (q.primitive, shape)
        if routed and q.params.get("name") == "exchange":
            exchanged.append(q.invars[0].aval.shape)
    assert seen > 1000
    planes = sum(len(fs) for fs in LANE_FIELDS) - 1
    assert planes == 35
    assert sorted(exchanged) == sorted(
        [(n, r)] * planes + [(n, r, e)]), exchanged
    # The stacked inbox is there all the same, outside the scan.
    outer = [v.aval.shape for q in closed.eqns for v in q.outvars
             if hasattr(v.aval, "shape")]
    assert (n, r, NUM_KINDS, e) in outer


def _bits_equal(a, b, what):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, what
        assert (x == y).all(), what


# (configuration, lane_skip): R=3 and R=5, the narrow carry on and
# off, the lane skip on and off. The last is this file's one new
# round-step key (conftest.py, ISSUE 33 audit); the others are keys
# already (test_scan_faults' CELL and R5, this file's own narrow
# configuration, test_deliver_shapes' lane_skip twin).
BOTH_FORMS = {
    "r3-wide-skip": (lambda: CELL, True),
    "r5-wide-skip": (lambda: R5, True),
    "r3-narrow-skip": (
        lambda: cfg_of(3, 3, lanes_minor=True, narrow_lanes=True), True),
    "r3-wide-noskip": (lambda: differential_engine().cfg, False),
    "r5-narrow-noskip": (
        lambda: cfg_of(4, 5, lanes_minor=True, narrow_lanes=True), False),
}


@pytest.mark.parametrize("name", list(BOTH_FORMS))
def test_lanes_in_lanes_out_equals_slots_in_slots_out(name):
    """The round handed its inbox as six lanes answers with six lanes,
    and their `stack_lanes` is, field for field and bit for bit, the
    outbox of the same round handed [N, R, K] slots; the state and the
    counter frame are the same too. Driven through a campaign (a vote
    round), steady appends, a node cut off until the ring has passed it
    by and healed (snapshot rounds, where the ring compacts). And
    `split_lanes` then `stack_lanes` is the identity on every inbox
    and outbox on the way."""
    make_cfg, lane_skip = BOTH_FORMS[name]
    cfg = make_cfg().validate().resolved()
    n, r = cfg.num_instances, cfg.num_replicas
    step = make_step_round(cfg, lane_skip=lane_skip)
    eng = MultiRaftEngine(cfg)  # for a fresh state and inbox
    st, inbox = eng.state, eng.inbox
    slots = np.arange(n) % r
    lead = np.zeros((n,), bool)
    lead[np.arange(cfg.num_groups) * r + np.arange(cfg.num_groups) % r] = True
    rounds = 8 + 3 * cfg.window
    saw = dict(vote=0, append=0, snapshot=0, entries=0, cut=0)
    for t in range(rounds):
        cut = 8 <= t < 8 + 2 * cfg.window
        iso = jnp.asarray((slots == 0) & cut)
        args = (jnp.full((n,), t >= 4), jnp.asarray(lead & (t == 0)),
                jnp.full((n,), cfg.max_props_per_round if t >= 4 else 0,
                         jnp.int32), iso)
        slots_out = step(st, inbox, *args)
        lanes_out = step(st, split_lanes(inbox), *args)
        assert isinstance(slots_out[1], MsgSlots)
        assert isinstance(lanes_out[1], tuple) and all(
            isinstance(m, MsgSlots) for m in lanes_out[1])
        for k, m in enumerate(lanes_out[1]):
            assert [f for f, x in zip(MsgSlots._fields, m)
                    if x is not None] == list(LANE_FIELDS[k]), k
        assert lanes_out[1][KIND_APP].ent_terms.shape == (
            n, r, cfg.max_ents_per_msg)
        _bits_equal(slots_out[0], lanes_out[0], ("state", t))
        _bits_equal(slots_out[2:], lanes_out[2:], ("frames", t))
        outbox = stack_lanes(lanes_out[1])
        _bits_equal(outbox, slots_out[1], ("outbox", t))
        st = slots_out[0]
        inbox = route(cfg, outbox)
        for what in (inbox, outbox):
            _bits_equal(stack_lanes(split_lanes(what)), what,
                        ("split then stack", t))
        v, ty = np.asarray(outbox.valid), np.asarray(outbox.type)
        saw["vote"] += v[:, :, KIND_VOTE].any()
        saw["append"] += (v & (ty == T_APP))[:, :, KIND_APP].any()
        saw["snapshot"] += (v & (ty == T_SNAP))[:, :, KIND_APP].any()
        saw["entries"] += np.asarray(outbox.ent_terms).any()
        saw["cut"] += bool(np.asarray(iso).any()) and v.any()
    assert (np.asarray(st.commit).reshape(-1, r).max(axis=1) > 0).all()
    assert saw["vote"] and saw["append"] and saw["entries"] and saw["cut"]
    if cfg.auto_compact:
        assert saw["snapshot"], saw


def test_split_then_stack_is_the_identity_on_a_hosted_inbox():
    """What `BatchedRawNode` stages for a round (a campaign, appends
    with entries, their responses, heartbeats, over three members)
    carries entries in the append lane alone, so the round's
    `split_lanes` loses nothing of it; and the hosted round, handed
    slots, hands slots back."""
    from etcd_tpu.batched.rawnode import BatchedRawNode

    g = 4
    # test_deliver_shapes' hosted configuration: the same round-step key.
    cfg = BatchedConfig(
        num_groups=g, num_replicas=3, window=16, max_ents_per_msg=4,
        max_props_per_round=2, election_timeout=1 << 20,
        heartbeat_timeout=1, narrow_lanes=True, deliver_shape="vectorized")
    rns = {
        mid: BatchedRawNode(cfg, groups=np.arange(g, dtype=np.int32),
                            slots=np.full(g, mid - 1, np.int32))
        for mid in (1, 2, 3)}
    staged = []
    for rn in rns.values():
        def spy(build=rn._build_inbox):
            staged.append(build())
            return staged[-1]
        rn._build_inbox = spy

    def pump(rounds):
        for _ in range(rounds):
            for rn in rns.values():
                rn.tick()  # heartbeat_timeout 1: a leader beats
                rd = rn.advance_round()
                blk = rd.msg_block
                if blk is not None and len(blk):
                    for to, sub in sorted(blk.split_by_target().items()):
                        rns[to].step_block(sub)
                for row, m in rd.messages:
                    rns[m.to].step(row, m)
                rn.advance()

    rns[1].campaign(list(range(g)))
    pump(4)
    for k in range(3):
        for row in range(g):
            rns[1].propose(row, b"entry-%d-%d" % (k, row))
        pump(3)
    assert (np.asarray(rns[1].state.commit) >= 4).all()
    occupied = np.zeros((NUM_KINDS,), bool)
    with_entries = 0
    for inbox in staged:
        assert isinstance(inbox, MsgSlots)
        _bits_equal(stack_lanes(split_lanes(inbox)), inbox, "staged inbox")
        occupied |= np.asarray(inbox.valid).any(axis=(0, 1))
        with_entries += bool(np.asarray(inbox.ent_terms).any())
    assert occupied.all(), occupied
    assert with_entries
