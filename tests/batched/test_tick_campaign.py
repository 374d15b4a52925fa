"""Tick's campaign writes the one entry it appends through one ring
column (ISSUE 49): ``_tick`` hands ``cols=1`` down ``_campaign`` ->
``_become_leader`` -> ``_append_own``, every other caller keeps the
``max_props_per_round`` columns. The same bits, held here three ways:

(i) the kernel: ``ring_write(log, i, terms[:1], 1)`` against the
P-column write with count 1, start indexes across the wrap;
(ii) the round: a single-voter group (one voter, two learners: the only
group whose campaign wins inside tick) becomes leader in the round its
campaign fires, its empty entry at ``last + 1`` and ``own_from`` on it,
the state the reference oracle's and, bit for bit in every field and
every message, the state of the P-column spelling (``cols`` dropped on
the way down), with and without ``pre_vote`` (the two paths through
``_campaign``), the campaign asked for and fired by the timer;
(iii) the fork: the write under ``raft_tick`` is one column wide and
every write a lane's ``lax.cond`` holds is P wide, read from the traced
closed loop.

Round-step programs (``conftest.py``): ``test_scan_faults``' ``CELL``
(pre_vote) and ``R3_MAJOR`` (none), keys already; the P-column spelling
re-traces a key's round and adds none.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import MultiRaftEngine
from etcd_tpu.batched import step as step_mod
from etcd_tpu.batched.kernels import ring_write
from etcd_tpu.batched.shadow import ShadowCluster
from etcd_tpu.batched.state import FOLLOWER, LEADER, BatchedState
from etcd_tpu.batched.step import MsgSlots

from .test_differential import device_log, device_state
from .test_scan_faults import CELL, R3_MAJOR
from .test_scopes import (SCOPE_RE, SCOPES, TILES, _bodies, engine_of,
                          loop_jaxpr)

# -- (i) the kernel ----------------------------------------------------------------


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("w", [16, 32])
def test_one_column_is_the_p_column_write_of_one_entry(w, p):
    g = np.random.default_rng(4900 + w * 10 + p)
    n = 4 * w + 64
    ring = g.integers(-(1 << 31), (1 << 31) - 1, size=(n, w),
                      dtype=np.int64).astype(np.int32)
    start = g.integers(0, 1 << 30, size=n, dtype=np.int32)
    start[:4 * w] = np.arange(4 * w)  # every slot, the wrap four times
    terms = g.integers(-(1 << 31), (1 << 31) - 1, size=(n, p),
                       dtype=np.int64).astype(np.int32)
    one = jnp.ones((n,), jnp.int32)
    wide = jax.jit(jax.vmap(ring_write))(ring, start, terms, one)
    narrow = jax.jit(jax.vmap(ring_write))(ring, start, terms[:, :1], one)
    np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))
    # And it is the write: the entry at its slot, the rest as they were.
    want = ring.copy()
    want[np.arange(n), start % w] = terms[:, 0]
    np.testing.assert_array_equal(np.asarray(narrow), want)


# -- (ii) the round ----------------------------------------------------------------

CONFIGS = {"pre_vote": CELL, "no_pre_vote": R3_MAJOR}
ROUNDS = 24  # CELL's timers fire in round 10 to 19


def _engine(cfg):
    """Every group one voter (slot g mod R) and two learners."""
    eng = MultiRaftEngine(cfg)
    for g in range(cfg.num_groups):
        v = g % cfg.num_replicas
        eng.set_membership(
            g, voters=[v],
            learners=[s for s in range(cfg.num_replicas) if s != v])
    return eng


def _oracles(cfg):
    r = cfg.num_replicas
    return [
        ShadowCluster(
            r, election_timeout=cfg.election_timeout,
            heartbeat_timeout=cfg.heartbeat_timeout,
            max_inflight=cfg.max_inflight, pre_vote=cfg.pre_vote,
            check_quorum=cfg.check_quorum,
            learners=[s for s in range(r) if s != g % r], group=g,
            deterministic_timeouts=True, auto_compact_window=cfg.window,
            max_ents=cfg.max_ents_per_msg, max_props=cfg.max_props_per_round)
        for g in range(cfg.num_groups)]


def _asked(cfg, how, t):
    """The voters asked to campaign in round `t`: all in round 2
    (`hup`), or nobody (`timer`: the election timeout fires)."""
    r = cfg.num_replicas
    if how == "hup" and t == 2:
        return [g * r + g % r for g in range(cfg.num_groups)]
    return []


def _run(cfg, how, after_round=lambda eng, t: None):
    eng = _engine(cfg.validate().resolved())
    n = cfg.num_instances
    props = jnp.full((n,), 1, jnp.int32)  # a leader appends, nobody else
    trail = []
    for t in range(ROUNDS):
        camp = np.zeros(n, bool)
        camp[_asked(cfg, how, t)] = True
        eng.step_round(tick=True, campaign_mask=jnp.asarray(camp),
                       propose_n=props)
        after_round(eng, t)
        trail.append((jax.tree.map(np.asarray, eng.state),
                      jax.tree.map(np.asarray, eng.inbox)))
    return trail


def _cases():
    return [pytest.param(name, how, id=f"{name}-{how}")
            for name in CONFIGS for how in ("hup", "timer")
            # R3_MAJOR's election timeout is 1 << 20: no timer fires.
            if not (name == "no_pre_vote" and how == "timer")]


@pytest.mark.parametrize("name,how", _cases())
def test_a_single_voter_wins_in_the_round_its_campaign_fires(name, how):
    """Against the reference oracle after every round, and the winning
    round itself read: leader at once, the empty entry at last + 1 in
    the new term, own_from on it, committed by the one voter."""
    cfg = CONFIGS[name].validate().resolved()
    r = cfg.num_replicas
    oracles = _oracles(cfg)
    before = {}
    won = {}

    def check(eng, t):
        st = eng.state
        for g, sh in enumerate(oracles):
            sh.round(campaigns=[i % r for i in _asked(cfg, how, t)
                                if i // r == g],
                     offer=1, tick=True)
        dev = device_state(eng, cfg)
        for g, sh in enumerate(oracles):
            host = sh.snapshot_state()
            for s in range(r):
                i = g * r + s
                assert dev[i] == host[s], (t, g, s, dev[i], host[s])
                assert device_log(eng, cfg, i) == sh.log_terms(s), (t, g, s)
        role, last = np.asarray(st.role), np.asarray(st.last)
        term, own = np.asarray(st.term), np.asarray(st.own_from)
        commit = np.asarray(st.commit)
        for g in range(cfg.num_groups):
            i = g * r + g % r
            if role[i] == LEADER and g not in won:
                role0, last0, term0 = before[g]
                assert role0 == FOLLOWER
                won[g] = t
                # The empty entry, then the round's one proposal.
                assert own[i] == last0 + 1 and last[i] == last0 + 2
                assert term[i] == term0 + 1
                ring = np.asarray(st.log_term[i])
                assert ring[own[i] % cfg.window] == term[i]
                assert commit[i] == last[i]
            before[g] = (role[i], last[i], term[i])

    _run(cfg, how, check)
    assert sorted(won) == list(range(cfg.num_groups)), won
    if how == "hup":
        assert set(won.values()) == {2}
    else:
        lo, hi = cfg.election_timeout, 2 * cfg.election_timeout
        assert all(lo - 1 <= t < hi for t in won.values()), won
        assert len(set(won.values())) > 1  # the hash spreads them


def p_column_spelling(campaign):
    """`step._campaign` as it was before ISSUE 49: `cols` dropped on
    the way down, so tick's campaign writes max_props_per_round columns
    like every other."""
    def wide(*a, cols=0, **k):
        return campaign(*a, **k)
    return wide


@pytest.fixture()
def p_columns(monkeypatch):
    """Switches the round between the two spellings. It is cached by
    configuration: cleared at every switch, and after."""
    narrow = step_mod._campaign

    def swap(on):
        monkeypatch.setattr(step_mod, "_campaign",
                            p_column_spelling(narrow) if on else narrow)
        step_mod._step_round_jit.cache_clear()

    yield swap
    swap(False)


@pytest.mark.parametrize("name,how", _cases())
def test_the_one_column_round_is_the_p_column_round_bit_for_bit(
        name, how, p_columns):
    cfg = CONFIGS[name]
    p_columns(True)
    wide = _run(cfg, how)
    p_columns(False)
    narrow = _run(cfg, how)
    for t, ((st_w, in_w), (st_n, in_n)) in enumerate(zip(wide, narrow)):
        for f in BatchedState._fields:
            for a, b in zip(jax.tree.leaves(getattr(st_w, f)),
                            jax.tree.leaves(getattr(st_n, f))):
                assert a.dtype == b.dtype and (a == b).all(), (t, f)
        for f in MsgSlots._fields:
            a, b = getattr(in_w, f), getattr(in_n, f)
            assert a.dtype == b.dtype and (a == b).all(), (t, f)
    leads = (narrow[-1][0].role == LEADER).reshape(cfg.num_groups, -1)
    assert (leads.sum(axis=1) == 1).all()


# -- (iii) the fork ----------------------------------------------------------------


def _ring_writes(jaxpr, under_cond=False, outer=""):
    """(innermost raft_ scope, under a cond, operand shape) of every
    `reduce_sum` of a jaxpr, walked through every body; an equation's
    name stack is its own after those of the equations round it
    (`test_scopes.scoped`)."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        bodies = list(_bodies(eqn))
        for body in bodies:
            yield from _ring_writes(
                body, under_cond or eqn.primitive.name == "cond", stack)
        if not bodies and eqn.primitive.name == "reduce_sum":
            scopes = [h for h in SCOPE_RE.findall(stack) if h in SCOPES]
            yield (scopes[-1] if scopes else "", under_cond,
                   eqn.invars[0].aval.shape)


@pytest.mark.parametrize("name", sorted(TILES))
def test_one_column_under_tick_and_p_columns_under_every_lane_cond(
        name, monkeypatch):
    """The closed loop of each live configuration (8 groups), traced:
    tick's write is the one under no cond and the only narrow one."""
    eng = engine_of(name, monkeypatch)
    cfg = eng.cfg
    w, p = cfg.window, cfg.max_props_per_round
    assert p > 1

    def cols(shape):
        """Columns of a [.., W, K] ring write with the batch axis first
        or last; None for a reduce of another shape."""
        if len(shape) != 3:
            return None
        rest = shape[1:] if shape[1] == w else shape[:2]
        return rest[1] if rest[0] == w else None

    writes = [(scope, cond, cols(shape))
              for scope, cond, shape in _ring_writes(loop_jaxpr(eng))
              if cols(shape) is not None]
    # Tick's write is the one under no cond, and the only narrow one.
    assert [(c, k) for s, c, k in writes if s == "raft_tick"] == [
        (False, 1)], writes
    assert [s for s, c, k in writes if k == 1] == ["raft_tick"], writes
    # The HB lane's transfer campaign and the VOTE_RESP lane's tally
    # (with pre_vote its pre-campaign's _become_leader too) write P
    # columns; the APP lane's entries are E wide.
    assert p != cfg.max_ents_per_msg
    in_conds = [k for s, c, k in writes
                if c and s == "raft_deliver" and k == p]
    assert len(in_conds) >= 2, writes
