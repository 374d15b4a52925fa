"""route(): the sender/target exchange on the layout the round carries.

``step.route()`` computes the routed inbox with the instance axis N
whole (row shifts and selects under ``n % R == t``). The spelling it
replaced — ``reshape(G, R, ...) -> swapaxes -> reshape`` — is the
oracle here and lives nowhere else: the inbox must equal it bit for
bit for every R, both carry dtypes and both ``lanes_minor`` layouts,
the lowering must never give the group or the replica an axis of its
own, and the engine's eager path must stay two programs.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke  # tests/conftest.py puts the repo root on sys.path
from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.step import (
    NARROW_MSG_DTYPES,
    NUM_KINDS,
    MsgSlots,
    route,
)

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


@pytest.mark.parametrize("lanes_minor", [False, True],
                         ids=["n-major", "n-minor"])
def test_scan_equals_single_rounds(lanes_minor):
    """16 rounds of run_rounds (route() traced into the scan) equal
    16 x step_round (route() as its own program), field for field."""
    groups = 4
    cfg = cfg_of(groups, 3, lanes_minor=lanes_minor)
    a, b = MultiRaftEngine(cfg), MultiRaftEngine(cfg)
    leaders = [g * 3 + g % 3 for g in range(groups)]
    props = jnp.zeros((cfg.num_instances,), jnp.int32).at[
        jnp.asarray(leaders)].set(2)
    for eng in (a, b):
        eng.campaign(leaders)
    a.run_rounds(16, tick=True, propose_n=props)
    for _ in range(16):
        b.step_round(tick=True, propose_n=props)
    assert (np.asarray(a.state.commit) > 0).all()
    for name, ta, tb in (("state", a.state, b.state),
                         ("inbox", a.inbox, b.inbox)):
        for f in type(ta)._fields:
            va, vb = np.asarray(getattr(ta, f)), np.asarray(getattr(tb, f))
            assert va.dtype == vb.dtype, (name, f)
            assert (va == vb).all(), (name, f)
