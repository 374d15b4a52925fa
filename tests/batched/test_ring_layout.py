"""The ring write compiled for a described TPU, no chip: what layout the
compiler gives the [N, W] log ring when the write sits alone in a lane's
`lax.cond`, as `_append_own` does in the HB and VOTE_RESP lanes.

A branch computation made of elementwise ops alone gets the default
layout, minor dimension last: the ring then stands ring-minor (W = 32 of
128 lanes), the branch's fusions cost ten times theirs and the ring is
copied at both edges of the cond, every round, taken or not (PERF.md
section 6, PR 39: +22% on the compiled 64k round). A reduce over K with
a ring-shaped output gets N minor and everything follows it, which is
why `kernels.ring_write_masked` keeps one.

The topology is described inside a fixture (one process at a time may
load the TPU's library, and every xdist worker imports every test file)
and the compiles run in this process, with JAX's persistent cache off:
a program compiled for a device that is not attached cannot be read
back."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from etcd_tpu.batched.kernels import ring_write, term_at

I32 = jnp.int32
N, W, P = 1024, 32, 2


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def persistent_cache_off():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture()
def no_persistent_cache():
    with persistent_cache_off():
        yield


def select_chain(log_term, start_index, terms, count):
    """The write as K static selects and no reduce: what ISSUE 39 asked
    for, and the same bits."""
    w, k = log_term.shape[-1], terms.shape[-1]
    rel = jnp.mod(jnp.arange(w, dtype=I32) - start_index, w)
    out = log_term
    for j in range(k):
        out = jnp.where((rel == j) & (j < count), terms[j], out)
    return out


def compiled_loop(write, sharding):
    """64 rounds of: read the ring outside the cond (`term_at`, as the
    round does), and every third round append one entry of the own term
    inside it, instance axis minor as `lanes_minor` carries it."""

    def append(ring, last, term):
        new = write(ring, last + 1, jnp.full((P,), 1, I32) * term,
                    jnp.asarray(1, I32))
        return new, last + 1

    def read(ring, last):
        zero = jnp.zeros((), I32)
        return term_at(ring, zero, zero, last, last)

    def body(i, carry):
        ring, last, term, acc = carry
        minor = jnp.moveaxis(ring, 0, -1)
        acc = acc + jax.vmap(read, in_axes=-1, out_axes=-1)(minor, last)

        def taken(ring, last):
            new, last = jax.vmap(append, in_axes=-1, out_axes=-1)(
                jnp.moveaxis(ring, 0, -1), last, term)
            return jnp.moveaxis(new, -1, 0), last

        ring, last = jax.lax.cond(
            i % 3 == 0, taken, lambda ring, last: (ring, last), ring, last)
        return ring, last, term, acc

    def loop(ring, last, term):
        return jax.lax.fori_loop(
            0, 64, body, (ring, last, term, jnp.zeros_like(last)))

    vec = jax.ShapeDtypeStruct((N,), I32, sharding=sharding)
    ring = jax.ShapeDtypeStruct((N, W), I32, sharding=sharding)
    return jax.jit(loop).lower(ring, vec, vec).compile().as_text()


def rings(text):
    """(N minor, ring minor): how often the ring's shape stands in each
    layout in a compiled text."""
    return (len(re.findall(rf"\[{N},{W}\]\{{0,1", text)),
            len(re.findall(rf"\[{N},{W}\]\{{1,0", text)))


def test_in_a_cond_the_ring_write_keeps_the_ring_n_minor(
        one_chip, no_persistent_cache):
    n_minor, ring_minor = rings(compiled_loop(ring_write, one_chip))
    assert n_minor > 0 and ring_minor == 0


def test_in_a_cond_a_write_of_selects_alone_lays_the_ring_out_ring_minor(
        one_chip, no_persistent_cache):
    """The control. If this fails the compiler has stopped doing it,
    and the reduce in `ring_write_masked` may go: compile the five
    closed loops for a described v5e first (the `verify` skill)."""
    n_minor, ring_minor = rings(compiled_loop(select_chain, one_chip))
    assert ring_minor > n_minor


# -- a lane cond that holds no ring-shaped value (ISSUE 43) -------------------------


def compiled_lane_cond(ring_through: bool, sharding):
    """64 rounds of: append an entry outside any cond (the ring's write,
    every round, as propose makes it) and, every third round, a lane's
    branch that moves a few per-row words and holds nothing of ring
    shape: the plain heartbeat branches of `step._deliver_vectorized`.
    The ring goes round the cond (it is no operand of it) or, with
    `ring_through`, through it, where the branch reads one entry's term
    from it (`term_at`) and hands the ring back as it came: the
    MsgAppResp lane's branch with the reject hints' counts taken out,
    `_maybe_commit`'s read all that is left."""

    def handler(ring, last, commit, term):
        zero = jnp.zeros((), I32)
        if ring is None:
            ok = commit < last
        else:
            ok = term_at(ring, zero, zero, last, commit + 1) == term
        return jnp.where(ok, commit + 1, commit)

    def body(i, carry):
        ring, last, commit, term = carry
        new, last = jax.vmap(
            lambda r, l, t: (ring_write(r, l + 1, jnp.full((P,), 1, I32) * t,
                                        jnp.asarray(1, I32)), l + 1),
            in_axes=-1, out_axes=-1)(jnp.moveaxis(ring, 0, -1), last, term)
        ring = jnp.moveaxis(new, -1, 0)
        lane = i % 3 == 0
        if ring_through:
            def taken(ring, last, commit):
                return ring, jax.vmap(handler, in_axes=-1, out_axes=-1)(
                    jnp.moveaxis(ring, 0, -1), last, commit, term)

            ring, commit = jax.lax.cond(
                lane, taken, lambda ring, last, commit: (ring, commit),
                ring, last, commit)
        else:
            commit = jax.lax.cond(
                lane,
                lambda last, commit: jax.vmap(
                    lambda l, c, t: handler(None, l, c, t))(
                        last, commit, term),
                lambda last, commit: commit, last, commit)
        return ring, last, commit, term

    def loop(ring, last, term):
        return jax.lax.fori_loop(
            0, 64, body, (ring, last, jnp.zeros_like(last), term))

    vec = jax.ShapeDtypeStruct((N,), I32, sharding=sharding)
    ring = jax.ShapeDtypeStruct((N, W), I32, sharding=sharding)
    return jax.jit(loop).lower(ring, vec, vec).compile().as_text()


def test_a_lane_cond_with_the_ring_led_round_it_keeps_the_ring_n_minor(
        one_chip, no_persistent_cache):
    n_minor, ring_minor = rings(compiled_lane_cond(False, one_chip))
    assert n_minor > 0 and ring_minor == 0


def test_a_lane_cond_that_only_reads_the_ring_lays_it_out_ring_minor(
        one_chip, no_persistent_cache):
    """The control, and what refused the same split of the MsgAppResp
    lane's `reject` (PERF.md section 6, "PR 43"): a branch whose only
    ring-shaped work is one `term_at` takes the ring ring-minor, and it
    is copied at the cond's edges taken or not. If this fails the
    compiler has stopped doing it, and that split may be asked for."""
    n_minor, ring_minor = rings(compiled_lane_cond(True, one_chip))
    assert ring_minor > 0


# -- a cond one branch of which only reads the ring (ISSUE 45) ----------------------


def compiled_emit_cond(sharding):
    """64 rounds of: append an entry outside any cond, as above, and
    state the terms of E entries a row, read from the ring through
    `term_at` where a bit of the batch says so (every third round) and
    the row's own term in every other: emit's two branches
    (`step._emit`). The ring is an operand of the cond that one branch
    reads and neither returns; the other holds nothing of ring shape."""
    e = 4

    def from_ring(ring, last, term):
        zero = jnp.zeros((), I32)
        return term_at(ring, zero, zero, last, last - jnp.arange(e, dtype=I32))

    def own(ring, last, term):
        return jnp.broadcast_to(term, (e,))

    def row(ring, last, term, bit):
        return jax.lax.cond(bit, from_ring, own, ring, last, term)

    def body(i, carry):
        ring, last, term, acc = carry
        new, last = jax.vmap(
            lambda r, l, t: (ring_write(r, l + 1, jnp.full((P,), 1, I32) * t,
                                        jnp.asarray(1, I32)), l + 1),
            in_axes=-1, out_axes=-1)(jnp.moveaxis(ring, 0, -1), last, term)
        ring = jnp.moveaxis(new, -1, 0)
        terms = jax.vmap(row, in_axes=(-1, -1, -1, None), out_axes=-1)(
            new, last, term, i % 3 == 0)
        return ring, last, term, acc + jnp.sum(terms, axis=0)

    def loop(ring, last, term):
        return jax.lax.fori_loop(
            0, 64, body, (ring, last, term, jnp.zeros_like(last)))

    vec = jax.ShapeDtypeStruct((N,), I32, sharding=sharding)
    ring = jax.ShapeDtypeStruct((N, W), I32, sharding=sharding)
    return jax.jit(loop).lower(ring, vec, vec).compile().as_text()


def test_a_cond_whose_one_branch_only_reads_the_ring_keeps_it_n_minor(
        one_chip, no_persistent_cache):
    """What lets emit read the ring in one branch of a cond (PERF.md
    section 6, "PR 45"): handed in and not handed back, the ring stays
    N-minor through the branch's `term_at`s, where the same read in a
    branch that returns the ring (the case above) flips it."""
    text = compiled_emit_cond(one_chip)
    n_minor, ring_minor = rings(text)
    assert " conditional(" in text
    assert n_minor > 0 and ring_minor == 0


# -- tick's campaign in a compiled closed loop (ISSUE 49) ---------------------------

# The smallest batch at which the P-column spelling on tick's path shows
# the sink: 768 rows (at the tests' 8 groups the compiler lays a 24-row
# ring out ring-minor everywhere and sinks nothing). It showed at every
# size compiled from 256 to 65,536 groups, so the control below stays.
SINK_GROUPS = 256


@pytest.fixture(scope="module")
def tick_loops(one_chip):
    """The compiled 64-round closed loop of `engine64k-r3`'s
    configuration at SINK_GROUPS groups, as shipped ("one": tick's
    campaign writes one ring column) and in the spelling before ISSUE
    49 ("p"): (configuration, text, the text and the loop as
    `tools/loop_cost.py` reads them) of each."""
    import importlib.util
    import os

    from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
    from etcd_tpu.batched import step as step_mod

    from .test_scopes import loop_args, sizes
    from .test_tick_campaign import p_column_spelling

    spec = importlib.util.spec_from_file_location(
        "loop_cost", os.path.join(os.path.dirname(__file__), "..", "..",
                                  "tools", "loop_cost.py"))
    loop_cost = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loop_cost)

    shipped = step_mod._campaign
    out = {}
    try:
        with persistent_cache_off():
            for name, campaign in (("one", shipped),
                                   ("p", p_column_spelling(shipped))):
                step_mod._campaign = campaign
                step_mod._step_round_jit.cache_clear()
                eng = MultiRaftEngine(BatchedConfig(
                    **dict(sizes("engine64k-r3"), num_groups=SINK_GROUPS)))
                args = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=one_chip)
                    if hasattr(x, "shape") else x, loop_args(eng))
                text = eng._closed_loop.lower(*args).compile().as_text()
                out[name] = (eng.cfg, text, loop_cost._Text(text),
                             loop_cost.read(text))
    finally:
        step_mod._campaign = shipped
        step_mod._step_round_jit.cache_clear()
    return out


def _p_columns(cfg):
    """The shape of a P-column ring write's outer compare, as the
    compiled text prints it."""
    return f"[{cfg.num_instances},{cfg.window},{cfg.max_props_per_round}]"


def _last_deliver_cond(cfg, t, loop):
    """(the cond, the lines of its not-taken branch, of its taken one):
    the last conditional under `raft_deliver` that returns the ring."""
    ring = f"s32[{cfg.num_instances},{cfg.window}]"
    for cond in reversed(loop.conds):
        if "raft_deliver" not in cond.op_name:
            continue
        line = next(x for x in t.computations[loop.body] if re.match(
            rf"\s*(?:ROOT )?%{re.escape(cond.name)} = ", x))
        if ring in line.split(" conditional(")[0]:
            not_taken, taken = t._branches(line)
            return cond, t.computations[not_taken], t.computations[taken]
    raise AssertionError("no deliver cond returns the ring")


def _columns(cfg, text, scope):
    """How many values of `[N, W, P]` shape stand under a scope in the
    whole text (a fusion's body is part of it)."""
    return sum(_p_columns(cfg) in line.split(" = ", 1)[-1].split("(")[0]
               and re.search(rf'op_name="[^"]*\({scope}\)', line) is not None
               for line in text.splitlines())


def test_the_last_deliver_cond_holds_nothing_of_ticks(tick_loops):
    """What the one-column write is for: no ring-times-P value of
    tick's exists, so conditional code motion has nothing to sink into
    the cond the ring leaves deliver through; its not-taken branch
    hands its operands on and prices 0 cycles."""
    cfg, text, t, loop = tick_loops["one"]
    cond, not_taken, taken = _last_deliver_cond(cfg, t, loop)
    assert cond.branches[0] == 0, cond
    assert not [x for x in not_taken + taken if "raft_tick" in x]
    assert _columns(cfg, text, "raft_tick") == 0
    assert loop.ring_n_minor > 0 and loop.ring_ring_minor == 0


def test_a_campaign_under_a_lane_cond_still_writes_p_columns(tick_loops):
    """The fork is tick's alone: `_become_leader` under the VOTE_RESP
    lane's cond and the transfer campaign under the HB lane's keep the
    P-column write, whose reduce is what lays the ring out N-minor
    inside a branch (the first case of this file; ROADMAP S12 (a): one
    column on every campaign path compiled all eight loops with the
    ring ring-minor)."""
    cfg, text, t, loop = tick_loops["one"]
    assert _columns(cfg, text, "raft_deliver") > 0
    assert loop.ring_ring_minor == 0
    assert len([c for c in loop.conds if "raft_deliver" in c.op_name]) == 8


def test_the_p_column_spelling_on_ticks_path_is_sunk_into_that_cond(
        tick_loops):
    """The control. With P columns on tick's path the `[N, W, P]`
    broadcast of the write stands in the not-taken branch of the last
    deliver cond, materialised in every round whichever branch runs.
    If this fails the compiler has stopped sinking it (or sinks it
    elsewhere: read every cond with `tools/loop_cost.py`), and the
    case above no longer shows what `cols=1` buys."""
    cfg, text, t, loop = tick_loops["p"]
    cond, not_taken, taken = _last_deliver_cond(cfg, t, loop)
    sunk = [x for x in not_taken
            if "raft_tick" in x and _p_columns(cfg) in x]
    assert sunk and cond.branches[0] > 0, cond
    assert _columns(cfg, text, "raft_tick") > 0
    assert loop.ring_ring_minor == 0


# -- the append lane in two halves, in a compiled closed loop (ISSUE 51) --------------

# The deep-log cell's configuration (E = 64, K = 32 runs, a head of 3
# columns) at SINK_GROUPS groups: the shapes a steady round must not
# hold are then [768, 64, 32] (the run table's passes over E entries),
# [768, 3, 64] (an append's entries a sender in one piece) and, but for
# the hand-through, [768, 3, 61] (their tail).


@pytest.fixture(scope="module")
def deep_loop(one_chip):
    """(configuration, text, the text and the loop as
    `tools/loop_cost.py` reads them) of the compiled 64-round closed
    loop of `engine100k-r3-deeplog`'s configuration at SINK_GROUPS
    groups, under a fault schedule as its cell hands it one."""
    import importlib.util
    import os

    import numpy as np

    from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

    from .test_scopes import ROUNDS, sizes

    spec = importlib.util.spec_from_file_location(
        "loop_cost", os.path.join(os.path.dirname(__file__), "..", "..",
                                  "tools", "loop_cost.py"))
    loop_cost = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loop_cost)
    with persistent_cache_off():
        eng = MultiRaftEngine(BatchedConfig(**dict(
            sizes("engine100k-r3-deeplog"), num_groups=SINK_GROUPS)))
        cfg = eng.cfg
        sched, _ = eng._schedule(
            np.zeros((ROUNDS, cfg.num_replicas), bool), ROUNDS)
        args = (eng.state, eng.inbox, eng._zeros_b, eng._zeros_i, eng._tel(),
                eng._flt(), eng._lanes + (eng._catchup,), sched, ROUNDS,
                None, None)
        args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip)
            if hasattr(x, "shape") else x, args)
        text = eng._closed_loop.lower(*args).compile().as_text()
    return cfg, text, loop_cost._Text(text), loop_cost.read(text)


def _reached(t, name, through_conds=True):
    """The lines of computation `name` and of everything it calls
    (fusions, calls, and with `through_conds` every branch of its
    conditionals)."""
    out, todo, seen = [], [name], set()
    while todo:
        at = todo.pop()
        if at in seen or at not in t.computations:
            continue
        seen.add(at)
        for line in t.computations[at]:
            out.append(line)
            branches = t._branches(line)
            if branches is not None:
                if through_conds:
                    todo.extend(branches)
                continue
            todo.extend(re.findall(r"(?:calls|to_apply)=%([^ ,)]+)", line))
    return out


def _cond_line(t, loop, cond):
    return next(x for x in t.computations[loop.body] if re.match(
        rf"\s*(?:ROOT )?%{re.escape(cond.name)} = ", x))


def _wide(cfg):
    """The shapes of an append at its whole width, as the text prints
    them: the run table's [N, E, K] passes, the entries [N, R, E] and
    [N, E], and the tail's [N, R, E - Wn] and [N, E - Wn]."""
    from etcd_tpu.batched.step import app_head

    n, r, e = cfg.num_instances, cfg.num_replicas, cfg.max_ents_per_msg
    tail = e - app_head(cfg)
    assert tail == 61
    return {"passes": f"[{n},{e},{cfg.log_runs}]",
            "entries": (f"[{n},{r},{e}]", f"[{n},{e}]"),
            "tail": (f"[{n},{r},{tail}]", f"[{n},{tail}]")}


def _split_conds(cfg, t, loop):
    """(deliver's append switch, emit's tail cond, route's tail
    switch) of the compiled loop."""
    tail = _wide(cfg)["tail"][0]
    result = lambda c: _cond_line(t, loop, c).split(" conditional(")[0]  # noqa: E731
    deliver = [c for c in loop.conds
               if "raft_deliver" in c.op_name and len(c.branches) == 3]
    emit = [c for c in loop.conds if "raft_emit" in c.op_name
            and len(c.branches) == 2 and tail in result(c)]
    route = [c for c in loop.conds if "raft_route" in c.op_name
             and len(c.branches) == 3 and tail in result(c)]
    assert len(deliver) == len(emit) == len(route) == 1
    return deliver[0], emit[0], route[0]


def test_the_split_loop_holds_no_instance_major_plane(deep_loop):
    """Rule 6 of ROADMAP's queue S: at E >= 32 a reduce over the
    senders of the [R, E] entries laid the append lane's whole cond out
    instance-major (`_gather_msg(chain=)` picks by selects); the
    three-way switch and the two halves must not bring it back. And the
    run table stands N-minor wherever it stands, through the switch
    included."""
    cfg, text, t, loop = deep_loop
    n = cfg.num_instances
    assert text.count(f"s32[{n},{cfg.num_replicas}]{{1,0") == 0
    table = f"[{n},2,{cfg.log_runs}]"
    layouts = set(re.findall(re.escape(table) + r"\{([0-9,]+)", text))
    assert layouts and all(x.startswith("0,") for x in layouts), layouts
    assert len(loop.conds) == 17
    switch, _, _ = _split_conds(cfg, t, loop)
    line = _cond_line(t, loop, switch)
    assert table + "{0," in line.split(" conditional(")[0]
    for branch in t._branches(line)[1:]:
        here = "\n".join(_reached(t, branch))
        assert table + "{0," in here
        assert not re.search(re.escape(table) + r"\{[12]", here)


def test_a_steady_round_holds_nothing_of_an_appends_whole_width(deep_loop):
    """What a round of steady appends runs (the loop's body outside its
    conditionals, the append switch's head arm, emit's not-bulk branch,
    route's untouched branch of the tail) holds no value of the whole
    width: no [N, 64, 32] pass of the run table, no [N, 3, 64] or
    [N, 64] entries, and of the tail's shape only the hand-through: a
    parameter in, the same out, priced 0 cycles, no copy."""
    cfg, text, t, loop = deep_loop
    wide = _wide(cfg)
    switch, emit, route = _split_conds(cfg, t, loop)
    skipped, head, whole = t._branches(_cond_line(t, loop, switch))
    not_bulk, bulk = t._branches(_cond_line(t, loop, emit))
    untouched, wiped, exchanged = t._branches(_cond_line(t, loop, route))
    steady = {
        "the body": _reached(t, loop.body, through_conds=False),
        "the head arm": _reached(t, head),
        "emit's not-bulk branch": _reached(t, not_bulk),
        "route's untouched branch": _reached(t, untouched)}
    shape_of = lambda line: line.split(" = ", 1)[-1].split("(")[0]  # noqa: E731
    for where, lines in steady.items():
        for line in lines:
            made = shape_of(line)
            assert wide["passes"] not in made, (where, line[:200])
            assert not any(x in made for x in wide["entries"]), (
                where, line[:200])
            if any(x in made for x in wide["tail"]):
                op = re.match(r"^\s*(?:ROOT )?%[^ ]+ = .*?\s([a-z][a-z0-9-]*)\(",
                              line).group(1)
                assert op in ("parameter", "get-tuple-element", "tuple",
                              "conditional", "bitcast"), (where, line[:200])
    assert emit.branches[0] == 0 and emit.branches[1] > 0, emit
    assert route.branches[0] == 0 and min(route.branches[1:]) > 0, route
    # The control: the whole arm and emit's bulk branch are where the
    # width lives.
    assert any(wide["passes"] in shape_of(x) for x in _reached(t, whole))
    assert any(wide["tail"][0] in shape_of(x) for x in _reached(t, bulk))
    # And the head arm is the cheaper one (by a fifth at the cell's
    # size, 4.6 M estimated cycles for 25.6 M; at 768 rows the fixed
    # part of a fusion leads).
    assert switch.branches[0] < switch.branches[1] < switch.branches[2] / 1.3
