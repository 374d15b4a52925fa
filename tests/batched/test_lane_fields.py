"""A lane carries the fields its messages use (ISSUE 48).

``step.LANE_FIELDS`` names, a kind lane, the fields some message type of
the lane states; the lane form (``split_lanes``, the scan's carry, what
``step_round`` takes and returns when handed lanes) holds None for every
other field, so ``route_lanes``, ``exchange_lanes``, the wipe, the tile
loops' slices and the carry move 35 ``[N, R]`` planes and the entries
where they moved 60. Held here: (a) the table is what the writers write,
over the scenarios the differential tests run, with the drop undone so
that the writers' own zeros are what is read; (b) nothing rides a field
outside it: garbage there changes no state and no outbox; (c) slots
split and stacked are the slots, for every inbox those scenarios saw;
(d) the traced closed loops exchange exactly the table; (e) the bytes a
slot of each lane weighs.

Round-step programs (``conftest.py``): every engine here is built on a
key that is one already (the differential pair, CELL through it, RC3,
RP4, the five live configurations at 8 groups); (a) clears
``step._step_round_jit``'s cache to trace those rounds with the drop
undone, the same key strings, counted once (as ``test_own_term.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import MultiRaftEngine
from etcd_tpu.batched import step as step_mod
from etcd_tpu.batched.engine import CTL_FROM, CTL_READS, CTL_TO
from etcd_tpu.batched.step import (BULK_APP, KIND_APP, KIND_HB_RESP,
                                   LANE_FIELDS, NUM_KINDS, T_APP, T_APP_RESP,
                                   BulkLane, MsgSlots, app_head,
                                   lane_occupancy, lane_slot_bytes,
                                   make_step_round, route, split_lanes,
                                   stack_lanes)

from . import test_differential as differential
from . import test_rare_lanes as rare
from . import test_scan_reconf as reconf
from . import test_scan_replace as replace
from . import test_scopes as scopes

SCALARS = tuple(f for f in MsgSlots._fields if f != "ent_terms")
DEAD = tuple(tuple(f for f in SCALARS if f not in LANE_FIELDS[k])
             for k in range(NUM_KINDS))

# The differential tests' own scenarios, run again as they stand (each
# steps the oracle beside the engine and asserts equality every round).
SCENARIOS = {
    "steady appends": lambda: (
        differential.test_election_and_replication_lockstep("r3")),
    "a cut-off and heal": lambda: (
        differential.test_partition_divergence_and_heal_lockstep("r3")),
    "a joint change with ReadIndex": lambda: (
        reconf.test_drain_cycle_matches_the_oracle_every_round(
            reconf.RC3, 1, 6)),
    "a replacement with a snapshot": lambda: (
        replace.test_replacement_cycle_matches_the_oracle_every_round(
            replace.RP4, 0)),
}


def test_the_table_is_a_table_of_message_fields():
    assert len(LANE_FIELDS) == NUM_KINDS
    for k, fields in enumerate(LANE_FIELDS):
        # In MsgSlots' order, each once; every lane says whether a slot
        # holds a message, of which type and of which term.
        assert list(fields) == [f for f in MsgSlots._fields if f in fields]
        assert {"valid", "type", "term"} <= set(fields), k
        assert ("ent_terms" in fields) == (k == KIND_APP)
    assert sum(map(len, LANE_FIELDS)) == 36
    assert sum(map(len, DEAD)) == 25
    # Every field is carried by some lane: stack_lanes takes a dead
    # field's shape and dtype from a lane that carries it.
    assert set().union(*LANE_FIELDS) == set(MsgSlots._fields)


# -- (a), (c): what the writers write --------------------------------------------------


@pytest.fixture(scope="module")
def written():
    """{scenario: [inbox in slot form, as numpy, after every eager round
    and every scan]} with the drop undone: `_carried` patched to keep
    every scalar field, so a lane holds what emit and deliver's response
    builders stated, the zeros of their ``empty_msgs`` among it, and not
    what `stack_lanes` would put there. (``ent_terms`` keeps the table:
    outside KIND_APP the writers build it with no columns, so there is
    nothing a message could state in it.) The exchange permutes slots
    inside a lane and field, so an inbox field is all zero exactly when
    the outbox's was."""
    seen = {}
    patch = pytest.MonkeyPatch()
    step_mod._step_round_jit.cache_clear()
    try:
        patch.setattr(
            step_mod, "_carried", lambda k, m: m._replace(
                ent_terms=m.ent_terms if k == KIND_APP else None))
        for name in ("_eager_round", "_scan"):
            def recording(eng, *a, _fn=getattr(MultiRaftEngine, name), **kw):
                out = _fn(eng, *a, **kw)
                seen[scenario].append(jax.tree.map(np.asarray, eng.inbox))
                return out
            patch.setattr(MultiRaftEngine, name, recording)
        for scenario, run in SCENARIOS.items():
            seen[scenario] = []
            run()
    finally:
        patch.undo()
        step_mod._step_round_jit.cache_clear()
    return seen


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_no_writer_states_a_field_outside_the_table(written, scenario):
    inboxes = written[scenario]
    assert len(inboxes) >= 16
    assert any(m.valid.any() for m in inboxes)
    for t, m in enumerate(inboxes):
        for k in range(NUM_KINDS):
            for f in DEAD[k]:
                assert not getattr(m, f)[:, :, k].any(), (scenario, t, k, f)
            if k != KIND_APP:
                assert not m.ent_terms[:, :, k].any(), (scenario, t, k)


def test_every_field_of_the_table_is_stated_in_some_round(written):
    """A field that no scenario ever sees non-zero would come off the
    table (or the scenarios lack the message that states it)."""
    stated = {(k, f): [name for name, inboxes in written.items()
                       if any(getattr(m, f)[:, :, k].any() for m in inboxes)]
              for k in range(NUM_KINDS) for f in LANE_FIELDS[k]}
    assert all(stated.values()), [kf for kf, s in stated.items() if not s]
    # KIND_APP's two borrowed fields are the configuration's: an
    # append's mark with conf_entries, a snapshot's masks with
    # replace_replicas; without either flag they are zeros the table
    # carries by choice (one table for every configuration).
    for f in ("reject_hint", "ctx"):
        assert set(stated[KIND_APP, f]) <= {
            "a joint change with ReadIndex", "a replacement with a snapshot"}


def test_split_then_stack_is_the_identity_on_what_the_writers_write(written):
    """(c), on every inbox of every scenario, with the real functions
    (the fixture's patch is undone): the slot form loses nothing."""
    total = 0
    for scenario, inboxes in written.items():
        for t, m in enumerate(inboxes[::4]):
            m = MsgSlots(*map(jnp.asarray, m))
            back = stack_lanes(split_lanes(m))
            for f, x, y in zip(MsgSlots._fields, back, m):
                assert x.dtype == y.dtype and x.shape == y.shape, f
                assert (np.asarray(x) == np.asarray(y)).all(), (scenario, t, f)
            total += 1
    assert total >= 64
    lanes = split_lanes(m)
    for k in range(NUM_KINDS):
        assert [f for f, x in zip(MsgSlots._fields, lanes[k])
                if x is not None] == list(LANE_FIELDS[k])


@pytest.mark.parametrize("scenario", ["steady appends", "a cut-off and heal"])
def test_the_writers_state_no_entry_past_those_a_message_carries(
        written, scenario):
    """The head/tail table (ISSUE 51) against the writers: the
    differential configuration (E=16, P=4) splits its append lane at
    ``app_head`` = 5, and in every inbox its scenarios saw (the round
    ran split: the oracle stood beside it) a valid append's
    ``ent_terms`` are zero from column ``n_ents`` on, so the tail holds
    something only where some MsgApp states more than the head holds,
    which is the occupancy vector's BULK_APP; split so and stacked, the
    slots are the slots."""
    cfg = differential.make_pair()[0]
    head = app_head(cfg)
    assert head == 5 and cfg.max_ents_per_msg == 16
    tails = 0
    for t, m in enumerate(written[scenario]):
        app = m.valid[:, :, KIND_APP]
        n_ents = np.where(app & (m.type[:, :, KIND_APP] == T_APP),
                          m.n_ents[:, :, KIND_APP], 0)
        past = np.arange(cfg.max_ents_per_msg) >= n_ents[..., None]
        ents = np.where(app[..., None], m.ent_terms[:, :, KIND_APP], 0)
        assert not np.where(past, ents, 0).any(), (scenario, t)
        m = MsgSlots(*map(jnp.asarray, m))
        lanes = split_lanes(m, head)
        lane = lanes[KIND_APP]
        assert isinstance(lane, BulkLane)
        assert lane.ent_terms.shape == app.shape + (head,)
        assert lane.ent_tail.shape == app.shape + (16 - head,)
        assert all(isinstance(lanes[k], MsgSlots)
                   for k in range(NUM_KINDS) if k != KIND_APP)
        bulk = bool(lane_occupancy(lanes)[BULK_APP])
        assert bulk == bool((n_ents > head).any())
        stated = np.where(app[..., None], np.asarray(lane.ent_tail), 0).any()
        assert stated == bulk, (scenario, t)
        tails += bulk
        for f, x, y in zip(MsgSlots._fields, stack_lanes(lanes), m):
            assert x.dtype == y.dtype and x.shape == y.shape, f
            at = m.valid if f != "ent_terms" else m.valid[..., None]
            assert (np.where(at, x, 0) == np.where(at, y, 0)).all(), (t, f)
    # (Both scenarios' appends stay within the head, the heal's short
    # catch-up included: tests/batched/test_bulk_lane.py has the tails.)
    assert tails == 0


# -- (b): nothing rides a field outside the table --------------------------------------


def garbage_outside_the_table(rng, inbox: MsgSlots) -> MsgSlots:
    """`inbox` with random bits in every field of every lane that the
    lane does not carry, in valid slots and empty ones alike."""
    def dirty(f, x):
        x = np.asarray(x)
        dead = np.array([f not in LANE_FIELDS[k] for k in range(NUM_KINDS)])
        if x.dtype == bool:
            noise = rng.random(x.shape) < 0.5
        else:
            noise = rng.integers(1, 1 << 20, x.shape).astype(x.dtype)
        at = dead[:, None] if f == "ent_terms" else dead
        return jnp.asarray(np.where(at, noise, x))

    return MsgSlots(*(dirty(f, x) for f, x in zip(MsgSlots._fields, inbox)))


@pytest.mark.parametrize("kind", ["stale-leader", "hand-over"])
def test_garbage_outside_the_table_changes_nothing(kind):
    """A slot-form inbox with garbage in the fields its lanes do not
    carry steps to the same state, outbox and frames as the one with
    zeros there, in every round of a stretch that holds votes, rejects,
    heartbeats with a read context and, after the heal, the
    stale-leader nudge: a MsgAppResp that states its term alone, in the
    heartbeat-response lane, whose fold reads `index` and `reject`.

    The parent would not have held this: it carried all ten fields and a
    handler read what was there (that nudge's `reject` and `index`, a
    heartbeat's `log_term` through the gather), always the zeros emit
    wrote. The new answer is the right one because it is upstream's:
    raftpb's MsgVote states term, index, logTerm and context; MsgApp
    term, logTerm, index, entries and commit; MsgSnap its snapshot;
    MsgHeartbeat term, commit and context; MsgTimeoutNow its term;
    MsgVoteResp term and reject; MsgAppResp term, index, reject,
    rejectHint and logTerm; MsgHeartbeatResp term and context. A field
    outside these lists is not on the wire, so nothing a peer can send
    is dropped."""
    cfg = replace.RP4
    lead = 1
    eng = rare.settled(cfg, lead)
    iso, ctl = rare.schedule(kind, cfg, lead)
    n, r = cfg.num_instances, cfg.num_replicas
    slots = np.arange(n) % r
    step = make_step_round(cfg)
    st, inbox = eng.state, eng.inbox
    rng = np.random.default_rng(48)
    ones, zeros = jnp.ones((n,), bool), jnp.zeros((n,), bool)
    props = jnp.full((n,), 2, jnp.int32)
    nudges = occupied = 0
    for t in range(rare.ROUNDS):
        drained = slots == ctl[t, CTL_FROM] - 1
        kwargs = dict(
            transfer_to=jnp.asarray(
                np.where(drained, ctl[t, CTL_TO], 0).astype(np.int32)),
            read_req=ones if ctl[t, CTL_READS] else zeros)
        args = (ones, zeros, props, jnp.asarray(iso[t][slots]))
        clean = step(st, inbox, *args, **kwargs)
        dirty = step(st, garbage_outside_the_table(rng, inbox), *args,
                     **kwargs)
        for i, (x, y) in enumerate(zip(jax.tree.leaves(clean),
                                       jax.tree.leaves(dirty), strict=True)):
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and (x == y).all(), (kind, t, i)
        v, ty = np.asarray(inbox.valid), np.asarray(inbox.type)
        nudges += int((v & (ty == T_APP_RESP))[:, :, KIND_HB_RESP].sum())
        occupied += v.any(axis=(0, 1))
        st, inbox = clean[0], route(cfg, clean[1])
    assert (np.asarray(st.commit) > 0).any()
    assert occupied[[KIND_APP, KIND_HB_RESP]].all()
    if kind == "stale-leader":
        assert nudges > 0 and occupied.all(), (nudges, occupied)


# -- (d): the traced loops exchange the table ------------------------------------------


def _leaves(jaxpr, outer=""):
    """(equation, name stack) of every equation, walked through every
    body (`test_scopes.scoped`'s walk)."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn, stack
        for body in scopes._bodies(eqn):
            yield from _leaves(body, stack)


def _lane_switches(jaxpr, scope):
    """The three-way switches under `scope`, outermost only, in program
    order: one a kind lane."""
    found = []

    def walk(jaxpr, outer, inside):
        for eqn in jaxpr.eqns:
            stack = f"{outer}/{eqn.source_info.name_stack}"
            is_switch = (eqn.primitive.name == "cond" and scope in stack
                         and len(eqn.params["branches"]) == 3)
            if is_switch and not inside:
                found.append(eqn)
            for body in scopes._bodies(eqn):
                walk(body, stack, inside or is_switch)

    walk(jaxpr, "", False)
    return found


@pytest.mark.parametrize("name", scopes.CONFIGS)
def test_a_lanes_switch_takes_the_fields_the_lane_carries(name, monkeypatch):
    """Each live configuration's closed loop: under ``raft_route`` one
    switch a lane, whose operands are the branch index and the lane's
    fields twice, the spent inbox's and the outbox's, and whose results
    are the lane's fields; 35 planes and the entries exchanged."""
    eng = scopes.engine_of(name, monkeypatch)
    jaxpr = scopes.loop_jaxpr(eng)
    switches = _lane_switches(jaxpr, "raft_route")
    assert len(switches) == NUM_KINDS
    for k, eqn in enumerate(switches):
        assert len(eqn.invars) == 1 + 2 * len(LANE_FIELDS[k]), (name, k)
        assert len(eqn.outvars) == len(LANE_FIELDS[k]), (name, k)
    exchanged = [eqn for eqn, stack in _leaves(jaxpr)
                 if "raft_route" in stack
                 and eqn.params.get("name") == "exchange"]
    assert len(exchanged) == 36


def test_between_nodes_a_tile_round_is_thirty_six_collectives(monkeypatch):
    """The node-placed loop: one all-to-all a lane and field carried,
    every one inside its lane's switch (61 before ISSUE 48)."""
    eng = scopes.engine_of(scopes.CONFIGS[4], monkeypatch, placed=True)
    assert eng._nodes is not None
    jaxpr = scopes.loop_jaxpr(eng)
    switches = _lane_switches(jaxpr, "raft_ici")
    assert len(switches) == NUM_KINDS
    for k, eqn in enumerate(switches):
        assert len(eqn.invars) == 1 + 2 * len(LANE_FIELDS[k]), k
        inside = [e for body in scopes._bodies(eqn) for e, _ in _leaves(body)
                  if e.primitive.name == "all_to_all"]
        assert len(inside) == len(LANE_FIELDS[k]), k
    total = [eqn for eqn, _ in _leaves(jaxpr)
             if eqn.primitive.name == "all_to_all"]
    assert len(total) == sum(map(len, LANE_FIELDS)) == 36


# -- (e): the bytes of a slot ------------------------------------------------------------


def test_lane_slot_bytes():
    assert lane_slot_bytes(4).tolist() == [21, 49, 17, 10, 22, 13]
    assert lane_slot_bytes(8).tolist() == [21, 65, 17, 10, 22, 13]
    # Of the parent's 34 bytes a slot (50 in the append lane): 132 of
    # 220 stay, and in a steady round of the 1M cell, where the append
    # and the heartbeat pairs run, 101 of 152.
    assert lane_slot_bytes(4).sum() == 132
    assert lane_slot_bytes(4)[[1, 2, 4, 5]].sum() == 101


def test_lane_slot_bytes_of_a_split_lane_are_head_and_tail():
    """The deep-log cell's append slot (E=64, head 3): 45 bytes in every
    round the lane runs and 244 more in the rounds ``bulk_rounds()``
    counts; together the 289 of the lane in one piece."""
    whole, split = lane_slot_bytes(64), lane_slot_bytes(64, 3)
    assert whole.tolist() == [21, 289, 17, 10, 22, 13]
    assert split.tolist() == [21, 45, 17, 10, 22, 13, 244]
    assert split[KIND_APP] + split[-1] == whole[KIND_APP]
    assert lane_slot_bytes(4, 0).tolist() == lane_slot_bytes(4).tolist()
    assert lane_slot_bytes(16, 5).tolist() == [21, 53, 17, 10, 22, 13, 44]
