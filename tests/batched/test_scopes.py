"""Every op the closed loop writes has a name (ISSUE 38).

``step.DEVICE_SCOPES`` is the one registry of the device programs'
``jax.named_scope``s: the round's nine and the closed-loop engine's
seven (three of any engine, two of one placed over nodes, ISSUE 40: the
exchange over the interconnect and what the nodes agree on first; one
of a scan with a phased control schedule, ISSUE 42: each row's own
round of the cycle, held to its scope in ``test_scan_phased.py``; one
of a scan with a load plane, ISSUE 47: each group's draws of the round,
held to its scope in ``test_scan_load.py``). A
profiler trace files a device op under the innermost
``raft_*`` name of its ``tf_op`` (``benchmark/reduce/trace.py``) and
under ``unscoped`` where there is none; these tests hold every equation
of the traced closed loop, of each live configuration's flag set, to a
registered scope, so that ``unscoped`` in a trace is what the compiler
made (the carry's copies, a loop's own condition) and a line added to
the scan without a name fails here, on the CPU, at 8 groups.

Round-step programs (``conftest.py``): the five live configurations at
the CPU tests' 8 groups, every one a key already (``test_scan_tiles``
builds the same five); nothing here compiles, the loops are traced to
jaxprs only. The node-placed loop is the replacement configuration's
over four of the forced devices: the same key, another trace of it.
"""

import contextlib
import hashlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched import engine as engine_mod
from etcd_tpu.batched import step as step_mod
from etcd_tpu.batched import termlog as termlog_mod
from etcd_tpu.batched.engine import control_cols

from .test_scan_tiles import CONFIGS, SPARE, sizes

SCOPES = tuple(scope for _layer, _name, scope in step_mod.DEVICE_SCOPES)
ENGINE = tuple(scope for layer, _name, scope in step_mod.DEVICE_SCOPES
               if layer == "closed-loop engine")
# ``benchmark/reduce/trace.py``'s SCOPE_RE, copied: the program's tests
# import nothing of the benchmark.
SCOPE_RE = re.compile(r"(?:^|[/(])(raft_[a-z_]+)(?=[/):]|$)")
ROUNDS = 4
# Tiles of each configuration's closed loop here (the tile constants
# are patched as test_scan_tiles does): the two large cells run in
# tiles on the chip, the three small ones in one scan.
TILES = dict(zip(CONFIGS, (1, 1, 1, 2, 2)))


def _bodies(eqn):
    """The jaxprs an equation encloses: a scan's or a while's bodies,
    a cond's branches, a jitted callee."""
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def _counts_the_loop(eqn, jaxpr) -> bool:
    """``fori_loop``'s own ``i + 1`` on its body's counter: the loop's
    equation itself, written by no line of the program."""
    return (eqn.primitive.name == "add"
            and not str(eqn.source_info.name_stack)
            and any(isinstance(v, jex_core.Literal) and v.val == 1
                    for v in eqn.invars)
            and any(v in jaxpr.invars for v in eqn.invars
                    if not isinstance(v, jex_core.Literal)))


def scoped(jaxpr, outer: str = ""):
    """(scope -> equations, [(primitive, name stack) under none]) of a
    jaxpr, walked through every body: an equation's name stack is its
    own after those of the loop, branch and call equations round it,
    as lowering composes an op's ``tf_op``; the innermost registered
    name wins; an equation that encloses others is read through them."""
    by_scope, bare = {}, []
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        bodies = list(_bodies(eqn))
        for body in bodies:
            got, more = scoped(body, stack)
            bare += more
            for k, v in got.items():
                by_scope[k] = by_scope.get(k, 0) + v
        if bodies or _counts_the_loop(eqn, jaxpr):
            continue
        hits = [h for h in SCOPE_RE.findall(stack) if h in SCOPES]
        if hits:
            by_scope[hits[-1]] = by_scope.get(hits[-1], 0) + 1
        else:
            bare.append((eqn.primitive.name, stack))
    return by_scope, bare


def engine_of(name: str, monkeypatch, placed: bool = False
              ) -> MultiRaftEngine:
    """`placed`: over as many of the forced devices as the
    configuration has replicas, a node's rows in the same tiles."""
    cfg = BatchedConfig(**dict(sizes(name), num_groups=8))
    rows = cfg.num_groups if placed else cfg.num_instances
    monkeypatch.setattr(engine_mod, "TILE_ALIGN", 1)
    monkeypatch.setattr(
        engine_mod, "TILE_ROWS",
        rows // TILES[name] if TILES[name] > 1 else 1 << 40)
    eng = MultiRaftEngine(
        cfg, **({"spare": SPARE} if cfg.replace_replicas else {}),
        **({"nodes": jax.devices()[:cfg.num_replicas]} if placed else {}))
    assert eng._tiles == TILES[name]
    return eng


def loop_args(eng: MultiRaftEngine) -> tuple:
    """The closed loop's arguments with the schedules its cell hands
    it: none (the two append cells), the fault schedule (the election
    cell), both (the two cells with a control plane)."""
    cfg = eng.cfg
    sched = ctl = watch = None
    if cfg.telemetry:
        sched, _ = eng._schedule(
            np.zeros((ROUNDS, cfg.num_replicas), bool), ROUNDS)
    if cfg.conf_entries:
        ctl, _ = eng._control_schedule(
            np.zeros((ROUNDS, control_cols(cfg)), np.int32), ROUNDS)
        watch = eng._watch
    return (eng.state, eng.inbox, eng._zeros_b, eng._zeros_i, eng._tel(),
            eng._flt(), eng._lanes, sched, ROUNDS, ctl, watch)


def round_args(eng: MultiRaftEngine) -> tuple:
    """(args, kwargs) of the eager round (``engine.step_round``'s
    program)."""
    cfg, zb, zi = eng.cfg, eng._zeros_b, eng._zeros_i
    return ((eng.state, eng.inbox, zb, zb, zi, zb, zi, zb),
            dict(conf_req=zi if cfg.conf_entries else None,
                 wipe=zb if cfg.replace_replicas else None))


def loop_jaxpr(eng: MultiRaftEngine):
    return jax.make_jaxpr(eng._closed_loop, static_argnums=(8,))(
        *loop_args(eng)).jaxpr


def round_jaxpr(eng: MultiRaftEngine):
    args, kwargs = round_args(eng)
    return jax.make_jaxpr(eng._round)(*args, **kwargs).jaxpr


def expected(eng: MultiRaftEngine, loop: bool) -> set:
    """The scopes a configuration's program holds, from its flags."""
    cfg = eng.cfg
    want = {"raft_deliver", "raft_tick", "raft_control", "raft_propose",
            "raft_emit", "raft_lease", "raft_carry"}
    if eng._nodes is not None:
        # The exchange runs with the eager round too; only the scan
        # has an occupancy, and a ScanWatch, to agree on. Nothing of
        # route() runs.
        want |= {"raft_ici", "raft_tiles"} | (
            {"raft_agree"} if loop else set())
    elif loop:
        want.add("raft_route")
    if cfg.telemetry:
        want.add("raft_telemetry")
    if eng._tiles > 1:
        want.add("raft_tiles")
    if loop and cfg.conf_entries:  # the cells with a control schedule
        want.add("raft_watch")
    return want


# -- the registry --------------------------------------------------------------------


def test_the_registry_is_what_the_program_names():
    assert len(set(SCOPES)) == len(SCOPES) == 17
    assert all(SCOPE_RE.fullmatch(s) for s in SCOPES)
    assert {layer for layer, _n, _s in step_mod.DEVICE_SCOPES} == {
        "round program", "closed-loop engine"}
    assert ENGINE == ("raft_tiles", "raft_watch", "raft_carry", "raft_ici",
                      "raft_agree", "raft_phase", "raft_load")
    assert all(scope == "raft_" + name
               for _layer, name, scope in step_mod.DEVICE_SCOPES)
    # Every named_scope the two modules open is registered, and every
    # registered one is opened by one of them.
    opened = set()
    for mod in (step_mod, engine_mod, termlog_mod):
        with open(mod.__file__) as f:
            opened |= set(re.findall(r'named_scope\("([^"]+)"\)', f.read()))
    assert opened == set(SCOPES)
    assert not hasattr(step_mod, "ROUND_PHASE_SCOPES")


# -- every equation under a registered scope ---------------------------------------


@pytest.mark.parametrize("name", CONFIGS)
def test_every_equation_of_the_closed_loop_has_a_registered_scope(
        name, monkeypatch):
    eng = engine_of(name, monkeypatch)
    by_scope, bare = scoped(loop_jaxpr(eng))
    assert not bare, bare[:10]
    assert set(by_scope) == expected(eng, loop=True)
    # The watch, the tiles and the carry are the engine layer's own
    # work, a fraction of the round's.
    own = sum(by_scope.get(s, 0) for s in ENGINE)
    assert 0 < own < sum(by_scope.values()) / 4


@pytest.mark.parametrize("name", CONFIGS)
def test_every_equation_of_the_eager_round_has_a_registered_scope(
        name, monkeypatch):
    eng = engine_of(name, monkeypatch)
    by_scope, bare = scoped(round_jaxpr(eng))
    assert not bare, bare[:10]
    assert set(by_scope) == expected(eng, loop=False)


# -- placed over nodes (ISSUE 40) ------------------------------------------------------


def primitives_by_scope(jaxpr, outer: str = "") -> dict:
    """{scope: {primitive}} of the leaf equations, as `scoped` files
    them."""
    out: dict = {}
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        bodies = list(_bodies(eqn))
        for body in bodies:
            for k, v in primitives_by_scope(body, stack).items():
                out.setdefault(k, set()).update(v)
        if not bodies:
            hits = [h for h in SCOPE_RE.findall(stack) if h in SCOPES]
            out.setdefault(hits[-1] if hits else "", set()).add(
                eqn.primitive.name)
    return out


@pytest.mark.parametrize("loop", (True, False), ids=("closed-loop", "eager"))
def test_placed_over_nodes_every_equation_has_a_registered_scope(
        loop, monkeypatch):
    """The replacement configuration over four nodes, a node's rows in
    two tiles: every equation under a registered scope, none under
    ``raft_route`` (``route()`` does not run: a trace of this program
    has no such scope, which is what the benchmark's readers of it
    count on), every collective under one of the two new names."""
    eng = engine_of(CONFIGS[4], monkeypatch, placed=True)
    assert eng._nodes is not None and eng.cfg.replace_replicas
    jaxpr = loop_jaxpr(eng) if loop else round_jaxpr(eng)
    by_scope, bare = scoped(jaxpr)
    assert not bare, bare[:10]
    assert set(by_scope) == expected(eng, loop)
    assert "raft_route" not in by_scope
    prims = primitives_by_scope(jaxpr)
    collectives = {"all_to_all", "psum", "psum_invariant", "pmax",
                   "all_gather", "ppermute", "pmin"}
    assert "all_to_all" in prims["raft_ici"]
    if loop:
        assert prims["raft_agree"] & {"psum", "psum_invariant"}
        assert "pmax" in prims["raft_agree"]
    for scope, got in prims.items():
        if scope not in ("raft_ici", "raft_agree"):
            assert not got & collectives, (scope, got & collectives)


# -- and the test has teeth ----------------------------------------------------------


@pytest.mark.parametrize("scope", ENGINE[:5])
def test_a_scope_left_out_leaves_its_lines_bare(scope, monkeypatch):
    """Each of the engine scopes taken away in turn (its ``with`` a
    no-op): the lines it enclosed stand under no name, or under the
    wrong one, and the rule above fails. (``raft_phase`` is taken away
    where a phased schedule is traced, ``test_scan_phased.py``, and
    ``raft_load`` where a load plane is, ``test_scan_load.py``.) The
    two of the node-placed
    loop on that loop: without its name the scan's exchange stands
    under none, and the watch's reduction over a group is filed with
    the watch, where a trace would hide the interconnect's time."""
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda s: contextlib.nullcontext() if s == scope else real(s))
    placed = scope in ("raft_ici", "raft_agree")
    eng = engine_of("engine512k-r3of4", monkeypatch, placed=placed)
    jaxpr = loop_jaxpr(eng)
    by_scope, bare = scoped(jaxpr)
    assert scope not in by_scope
    if placed:
        prims = primitives_by_scope(jaxpr)
        assert {"raft_ici": "all_to_all" in prims.get("", ()),
                "raft_agree": "pmax" in prims["raft_watch"]}[scope]
        return
    assert bare
    kinds = {prim for prim, _stack in bare}
    assert {"raft_tiles": {"dynamic_slice", "dynamic_update_slice"} <= kinds,
            "raft_watch": "reduce_sum" in kinds,
            "raft_carry": "add" in kinds}[scope]


@pytest.mark.parametrize("name", (CONFIGS[0], CONFIGS[3]))
def test_a_line_moved_out_of_its_scope_fails_the_rule(name, monkeypatch):
    """A patched body: one more line of the scan's body, written where
    ``route_lanes`` is called, outside every ``with``."""
    real = engine_mod.route_lanes

    def route_lanes(cfg, outbox, sent, prev):
        return real(cfg, outbox, sent | jnp.zeros_like(sent), prev)

    monkeypatch.setattr(engine_mod, "route_lanes", route_lanes)
    eng = engine_of(name, monkeypatch)
    by_scope, bare = scoped(loop_jaxpr(eng))
    assert sorted(prim for prim, _stack in bare) == [
        "broadcast_in_dim", "or"]
    assert set(by_scope) == expected(eng, loop=True)


# -- names are metadata: the programs are the parent's ---------------------------

# sha256 of the lowered text (the closed loop as its cell calls it, with
# both schedules; the eager round) of the two configurations that run in
# tiles, at 8 groups in two tiles, taken with lowered_texts() below. Pinned
# by PR 38 on the text of 904936a (PR 36), which 2cee456 (PR 38) kept;
# re-pinned by PR 39 on its own text, because it rewrote
# `kernels.ring_write_masked`, which every append site of the round
# calls, and by PR 41 on its own, because it rewrote
# `kernels.quorum_committed` (an elementwise order statistic for a sort
# and a one-hot pick), which every `_maybe_commit` of the round calls
# (the untiled pins in `test_scan_replace.py` moved with both), and by PR
# 43 on its own, because it split deliver's HB and HB_RESP lane conds in
# two (the untiled pins moved with it), and by PR 45 on its own, because
# the state gained `own_from`, `_maybe_commit` and `_control` read it and
# no ring, and emit reads the ring in one branch of a cond on a bit the
# round reduces between its two vmaps (the untiled pins moved with it), and by
# PR 48 on its own, because the lanes the tile loops slice, step and
# paste carry the fields of `step.LANE_FIELDS` alone (the untiled pins
# moved with it), and by PR 49 on its own, because `_tick`'s campaign
# writes its one entry through one ring column (the untiled pins moved
# with it): names
# are still not part of either text. The lowered
# text holds no name of a scope, and JAX's persistent cache keys a
# program with its names stripped: equal text here is a cache hit on the
# chip (a run of this change against a cache the parent filled reads
# `compile.cache_misses` 0), where a `with` that re-orders two lines is
# a miss of the scan: 146-200 s of a large cell's cold set-up. A loop
# that changes on purpose re-pins these (ETCD_TPU_PRINT_ROUND_DIGESTS=1
# prints them); `test_scan_replace.py` pins the untiled texts.
PARENT_TILED_TEXT = {
    "engine1m-r3": (
        "5d8c903986bf1e5f8097785c30424edce86557c0a64ef2e9c270458d3ba74387",
        "461f6b28d1b31299125ae8c1544e0743ed32c690eed4618618416282a3368ec2"),
    "engine512k-r3of4": (
        "75ebe68e355ad7f78767df7b6b2980ffa1ce5bf4094b21b9cd3fd1014ca9e64c",
        "ff61bd28101a9822dcd7a8607eac35b93144e4ab0ade0882c038cc0d5bccb7bd"),
}


def lowered_texts(eng: MultiRaftEngine):
    """(closed loop, eager round) lowered as `loop_jaxpr` and
    `round_jaxpr` trace them: nothing compiles."""
    args, kwargs = round_args(eng)
    return (eng._closed_loop.lower(*loop_args(eng)).as_text(),
            eng._round.lower(*args, **kwargs).as_text())


@pytest.mark.parametrize("name", sorted(PARENT_TILED_TEXT))
def test_in_tiles_the_lowered_text_is_the_parents(name, monkeypatch):
    texts = lowered_texts(engine_of(name, monkeypatch))
    assert not any(scope in t for t in texts for scope in SCOPES)
    got = tuple(hashlib.sha256(t.encode()).hexdigest() for t in texts)
    if os.environ.get("ETCD_TPU_PRINT_ROUND_DIGESTS"):
        print(name, got)
    assert got == PARENT_TILED_TEXT[name], (
        "the lowered closed loop or eager round of a tiled configuration "
        "is not the text it was at the commit that pinned it (PR 49)")


# -- no sort in any live program (ISSUE 41) ----------------------------------------


@pytest.mark.parametrize(
    "name, placed", [(name, False) for name in CONFIGS] + [(CONFIGS[4], True)],
    ids=list(CONFIGS) + [CONFIGS[4] + "-placed"])
def test_no_live_program_holds_a_sort(name, placed, monkeypatch):
    """The quorum index was the round's only `sort` (two a
    `_maybe_commit`), the one op of the round the TPU compiler never
    fuses. `kernels.quorum_committed` is an elementwise order statistic
    since PR 41; a sort that comes back, there or anywhere in a round,
    shows here in the lowered closed loop and eager round of every live
    configuration, the node-placed one included, before it shows as
    `*/sort` in a cell's traced line."""
    for text in lowered_texts(engine_of(name, monkeypatch, placed=placed)):
        assert "stablehlo.reduce" in text  # the text is the program's
        assert text.count("stablehlo.sort") == 0
