"""The leader's own-term boundary as state (ISSUE 45):
``BatchedState.own_from`` is the index of the entry ``_become_leader``
appends, 0 on every row that is not a leader, and it stands in for the
log ring wherever a leader asks whether an entry is of its own term
(``step._maybe_commit``, ``_control``'s committed-in-term, the terms
``_emit`` states). These tests hold it to that:

(i) the invariant itself, read against the ring, after every round of
the oracle's traces: the schedules of ``test_differential``,
``test_scan_faults``, ``test_scan_reconf`` and ``test_scan_replace`` run
again with every engine call followed by the check (those tests hold the
same rounds to the oracle, so the field rides programs that are right);
(ii) on the same states the predicates the parent read from the ring
against the ones that read the field, on every row whose answer is kept
(a leader's);
(iii) emit's two branches: the closed loop as it chooses against the
same loop with the per-row bit forced true (the ring read every round:
the parent's emit), round by round through elections, a hand-over and a
replacement;
(iv) who holds 0; (v) the counter (``eng.emit_ring_rounds()``).

Round-step programs (``conftest.py``): the configurations of the four
files named above and ``test_rare_lanes``'s, keys already; the forced bit
re-traces a key's round and adds none.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import MultiRaftEngine
from etcd_tpu.batched import step as step_mod
from etcd_tpu.batched.kernels import joint_committed, term_at
from etcd_tpu.batched.state import LEADER, empty_replica, init_state
from etcd_tpu.batched.step import NUM_KINDS, NUM_OCC

from . import test_differential as differential
from . import test_rare_lanes as rare
from . import test_scan_faults as faults
from . import test_scan_reconf as reconf
from . import test_scan_replace as replace
from . import test_scan_tiles as tiles_mod


# -- (i), (ii): the invariant and the predicates on the oracle's traces ------------


@jax.jit
def _read_against_the_ring(st):
    """Per row: whether the boundary is where the ring says the own term
    begins, over the row's window and one index past it, and whether the
    ring's answers to _maybe_commit's and _control's questions are the
    field's."""
    w = st.log_term.shape[-1]
    ta = jax.vmap(term_at)
    at = lambda i: ta(st.log_term, st.snap_index, st.snap_term, st.last, i)  # noqa: E731
    i = st.snap_index[:, None] + jnp.arange(w + 2)[None, :]
    i = jnp.minimum(i, st.last[:, None] + 1)
    own = st.own_from[:, None]
    boundary = jnp.all(
        (at(i) == st.term[:, None]) == ((own <= i) & (i <= st.last[:, None])),
        axis=-1)
    leads = (st.own_from > 0)
    mci = jax.vmap(joint_committed)(
        st.match, st.voter, st.voter_out, st.in_joint)
    commits = ((mci > st.commit) & (at(mci) == st.term)) == (
        (mci > st.commit) & leads & (mci >= st.own_from))
    in_term = (at(st.commit) == st.term) == (
        leads & (st.commit >= st.own_from))
    return boundary, commits, in_term


class Watch:
    """Every eager round and every scan of every engine, checked as it
    returns."""

    def __init__(self, monkeypatch):
        self.calls = self.leader_rows = 0
        for name in ("_eager_round", "_scan"):
            monkeypatch.setattr(
                MultiRaftEngine, name, self._after(getattr(MultiRaftEngine, name)))

    def _after(self, fn):
        def checked(eng, *a, **k):
            out = fn(eng, *a, **k)
            self.check(eng)
            return out
        return checked

    def check(self, eng):
        st = eng.state
        leader = np.asarray(st.role) == LEADER
        own = np.asarray(st.own_from)
        assert (own[~leader] == 0).all(), "a row that is no leader holds one"
        assert (own[leader] > 0).all(), "a leader holds none"
        boundary, commits, in_term = map(
            np.asarray, _read_against_the_ring(st))
        assert boundary[leader].all(), (
            "own_from is not where the ring says the term begins",
            np.flatnonzero(leader & ~boundary))
        assert commits[leader].all() and in_term[leader].all(), (
            "the ring and the field answer a leader differently")
        self.calls += 1
        self.leader_rows += int(leader.sum())


TRACES = {
    "differential-election-r3": lambda: (
        differential.test_election_and_replication_lockstep("r3")),
    "differential-election-r5": lambda: (
        differential.test_election_and_replication_lockstep("r5")),
    "differential-partition-r3": lambda: (
        differential.test_partition_divergence_and_heal_lockstep("r3")),
    "differential-partition-r5": lambda: (
        differential.test_partition_divergence_and_heal_lockstep("r5")),
    "faults-elections-k0": lambda: (
        faults.test_elections_under_etcd_defaults_match_the_oracle_every_round(0)),
    "faults-elections-k2": lambda: (
        faults.test_elections_under_etcd_defaults_match_the_oracle_every_round(2)),
    "reconf-drain-r3": lambda: (
        reconf.test_drain_cycle_matches_the_oracle_every_round(
            reconf.RC3, 1, 6)),
    "reconf-drain-r5": lambda: (
        reconf.test_drain_cycle_matches_the_oracle_every_round(
            reconf.RC5, 1, 4)),
    "replace-cycle-e0": lambda: (
        replace.test_replacement_cycle_matches_the_oracle_every_round(
            replace.RP4, 0)),
}


@pytest.mark.parametrize("trace", sorted(TRACES))
def test_the_boundary_is_where_the_ring_says_after_every_round(
        trace, monkeypatch):
    watch = Watch(monkeypatch)
    TRACES[trace]()
    assert watch.calls >= 16 and watch.leader_rows >= watch.calls, (
        watch.calls, watch.leader_rows)


# -- (iii) emit's two branches, round by round -------------------------------------


def _replacement(cfg):
    rows = [replace.replace_row(t, rare.SPARE) for t in range(replace.PERIOD)]
    return replace.isolate_rows(rows), replace.control_rows(rows)


STRETCHES = {
    # (configuration, the node that leads at first, (isolate, control) of
    # the stretch); the replacement retires node 3, so it leads there: a
    # cycle with its hand-over, and emit's bit leaves out the rows the
    # round cuts off (the retired node's replicas campaign unheard).
    "elections-r3": ("r3", 1, lambda cfg: rare.schedule("stale-leader", cfg, 1)),
    "hand-over-r3": ("r3", 1, lambda cfg: rare.schedule("hand-over", cfg, 1)),
    "replacement-r4": ("r4-replace", 3, _replacement),
}


@pytest.fixture
def fresh_rounds():
    """The round program is cached by configuration: a forced bit needs a
    trace of its own, and must leave none behind."""
    step_mod._step_round_jit.cache_clear()
    yield
    step_mod._step_round_jit.cache_clear()


@pytest.mark.parametrize("stretch", sorted(STRETCHES))
def test_emit_reads_the_ring_or_not_and_sends_the_same(
        stretch, monkeypatch, fresh_rounds):
    name, lead, make = STRETCHES[stretch]
    cfg = rare.CONFIGS[name]
    iso, ctl = make(cfg)
    props = jnp.full((cfg.num_instances,), 2, jnp.int32)

    def rounds(eng):
        for t in range(len(ctl)):
            eng.run_rounds(1, propose_n=props, isolate=iso[t:t + 1],
                           control=ctl[t:t + 1])
            yield rare.everything(eng)

    chosen = rare.settled(cfg, lead)
    after = list(rounds(chosen))
    # (The round is traced at an engine's first call of it: the patch is
    # in place for the second engine's alone.)
    step_mod._step_round_jit.cache_clear()
    monkeypatch.setattr(
        step_mod, "_asks_below", lambda *a: jnp.ones((), bool))
    forced = rare.settled(cfg, lead)
    for t, (want, got) in enumerate(zip(after, rounds(forced))):
        rare.assert_same(got, want, (stretch, t))
    took = chosen.emit_ring_rounds()
    assert forced.emit_ring_rounds() == len(ctl)
    assert 0 < took < len(ctl), took


# -- (iv) who holds 0 --------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(rare.CONFIGS))
def test_a_fresh_state_and_an_empty_replica_hold_none(name):
    cfg = rare.CONFIGS[name]
    spare = {"spare": rare.SPARE} if cfg.replace_replicas else {}
    st = init_state(cfg, **spare)
    assert st.own_from.dtype == jnp.int32
    assert st.own_from.shape == (cfg.num_instances,)
    assert not np.asarray(st.own_from).any()
    empty = empty_replica(cfg, st, jnp.arange(cfg.num_instances))
    assert not np.asarray(empty.own_from).any()


def test_a_wiped_slot_holds_none():
    """The leader's own rows wiped in the round it leads."""
    cfg, lead = replace.RP4, 1
    eng = rare.settled(cfg, lead)
    on_lead = np.arange(cfg.num_instances) % cfg.num_replicas == lead
    assert (np.asarray(eng.state.own_from)[on_lead] > 0).all()
    eng.step_round(wipe=jnp.asarray(on_lead))
    assert not np.asarray(eng.state.own_from)[on_lead].any()
    assert (np.asarray(eng.state.role)[on_lead] != LEADER).all()


@pytest.mark.parametrize("name", ["r3", "r5"])
def test_a_leader_that_steps_down_holds_none(name):
    """Cut off until CheckQuorum stands it down, and deposed by a higher
    term when it is healed."""
    cfg, lead = rare.CONFIGS[name], 1
    eng = rare.settled(cfg, lead)
    on_lead = np.arange(cfg.num_instances) % cfg.num_replicas == lead
    was = np.asarray(eng.state.own_from)[on_lead]
    assert (was > 0).all()
    cut = np.zeros((3 * cfg.election_timeout, cfg.num_replicas), bool)
    cut[:, lead] = True
    eng.run_rounds(len(cut), isolate=cut)
    st = eng.state
    assert (np.asarray(st.role)[on_lead] != LEADER).all()
    assert not np.asarray(st.own_from)[on_lead].any()
    # Somebody else leads, from an entry above the old leader's.
    led = np.asarray(st.role) == LEADER
    assert led.reshape(cfg.num_groups, -1).sum(axis=1).tolist() == (
        [1] * cfg.num_groups)
    assert (np.asarray(st.own_from)[led] > was).all()


# -- (v) the counter ---------------------------------------------------------------


def test_emit_ring_rounds_is_zero_over_steady_appends():
    cfg = reconf.RC3
    eng = rare.settled(cfg, 0)
    before = eng.emit_ring_rounds()
    eng.run_rounds(64, propose_n=jnp.full((cfg.num_instances,), 2, jnp.int32))
    assert eng.emit_ring_rounds() == before
    assert int(eng.lane_rounds()[step_mod.KIND_APP]) >= 63


@pytest.mark.parametrize("tiles", [0, 2, 4], ids=["untiled", "2", "4"])
def test_emit_ring_rounds_counts_the_tile_rounds_of_a_drain_period(
        tiles, monkeypatch):
    """``engine1m-r3``'s own cycle at 8 groups (``test_scan_tiles.run``:
    128 rounds with a hand-over from round 8 and a node cut off): emit
    reads the ring in a few tile-rounds of them, never in all; in tiles,
    in no more rounds a tile than the one scan over all rows and in no
    fewer altogether (a round the whole batch takes, some tile takes);
    the other counters keep their shapes."""
    eng = tiles_mod.run("engine1m-r3", tiles, monkeypatch)
    assert eng.lane_rounds().shape == (NUM_KINDS,)
    assert eng.rare_rounds().shape == (NUM_OCC - NUM_KINDS,)
    took = eng.emit_ring_rounds()
    assert 0 < took < tiles_mod.ROUNDS * max(tiles, 1) // 2, took
    if tiles:
        with pytest.MonkeyPatch.context() as mp:
            whole = tiles_mod.run("engine1m-r3", 0, mp).emit_ring_rounds()
        assert whole <= took <= whole * tiles, (took, whole)
