"""A phased control schedule (ISSUE 42): ``run_rounds(control=cycle,
starts=...)``: one cycle's rows and, a group, the round at which it
enters the cycle, where the lockstep form hands every group the same
row in the same round. Groups share nothing, so a group with start s in
the phased loop must equal, bit for bit, the same group in the lockstep
loop run on the cycle shifted by s; all starts 0 is today's scan; two
tiles equal one scan; and the oracle (``batched/shadow.py``) stepped
group by group on each group's own rows agrees history by history. With
no `starts` nothing of this reaches a program (the digest tests in
``test_scan_replace.py`` and ``test_scopes.py`` stay as they are).

Round-step programs (``conftest.py``, ISSUE 42 audit): none new. Every
engine here is ``test_scan_replace.RP4`` (the benchmark's
``engine512k-r3of4`` values at the CPU tests' 8 groups, a key since
ISSUE 34) or ``test_scan_reconf.RC3``: the phased schedule, like the
lockstep one, is an input of the closed-loop program and no key of the
round step.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import MultiRaftEngine
from etcd_tpu.batched import engine as engine_mod
from etcd_tpu.batched import step as step_mod
from etcd_tpu.batched.engine import (CTL_CONF, CTL_FROM, CTL_READS,
                                     CTL_RETIRE, CTL_STALL, CTL_TO, CTL_WIPE,
                                     NEVER, control_cols)
from etcd_tpu.batched.state import (CONF_ADD_LEARNER, CONF_LEAVE, CONF_SWAP,
                                    conf_code)
from etcd_tpu.obs import spans

from . import test_scan_replace as replace
from . import test_scan_tiles as tiles
from .test_scan_faults import inbox_equal
from .test_scan_reconf import RC3
from .test_scan_replace import RP4, make_shadows
from .test_scopes import SCOPES, scoped

R = 4
CYCLE = 128
ADD, TRANSFER, SWAP, RETIRE, LEAVE, WIPE = 8, 24, 40, 72, 96, 120
E0 = 1  # every group's empty slot: the node the replicas move to
CALL = 32
ROUNDS = 256
# Two groups a batch, three batches, and two groups nobody moves.
STARTS = np.asarray([0, 48, NEVER, 16, 0, 16, NEVER, 48], np.int32)


def cycle_row(k: int, e: int = E0) -> dict:
    """Round k of one group's move (``benchmark/traffic/
    trickle-readindex.json``'s cycle: the replacement cell's without
    the node-wide cut), or the steady row outside it."""
    d, n = (e + 1) % R, (e + 2) % R
    row = dict(drained=None, transfer_to=None, conf=0, cut=None,
               retired=None, wipe=None, stall=False)
    if not 0 <= k < CYCLE:
        return row
    if ADD <= k < SWAP:
        row["conf"] = conf_code(CONF_ADD_LEARNER, e)
    elif SWAP <= k < LEAVE:
        row["conf"] = conf_code(CONF_SWAP, e, d)
    elif k >= LEAVE:
        row["conf"] = conf_code(CONF_LEAVE)
    if TRANSFER <= k < LEAVE:
        row.update(drained=d, transfer_to=n)
    if k >= RETIRE:
        row["retired"] = d
    if k == WIPE:
        row["wipe"] = d
    return row


def cycle_table() -> np.ndarray:
    return replace.control_rows([cycle_row(k) for k in range(CYCLE)])


def shifted(start: int, rounds: int = ROUNDS) -> np.ndarray:
    """The lockstep schedule that moves every group at `start`."""
    return replace.control_rows(
        [cycle_row(t - int(start)) for t in range(rounds)])


def settled(cfg=RP4, tiles: int = 0, monkeypatch=None) -> MultiRaftEngine:
    """An engine on `cfg` with its leaders from a seed, settled by
    eager rounds."""
    if monkeypatch is not None:
        monkeypatch.setattr(engine_mod, "TILE_ALIGN", 1)
        monkeypatch.setattr(engine_mod, "TILE_ROWS",
                            cfg.num_instances // tiles if tiles else 1 << 40)
    eng = MultiRaftEngine(cfg, spare=E0)
    g_n, r = cfg.num_groups, cfg.num_replicas
    seated = np.asarray([s for s in range(r) if s != E0])
    lead = seated[np.random.default_rng(42).integers(0, r - 1, g_n)]
    eng.campaign(np.arange(g_n) * r + lead)
    for _ in range(16):
        eng.step_round()
    assert (eng.leaders() == lead).all()
    eng.first_leaders = lead
    return eng


def run_phased(eng, starts=STARTS, rounds=ROUNDS, cycle=None):
    props = jnp.full((eng.cfg.num_instances,), 2, jnp.int32)
    cycle = cycle_table() if cycle is None else cycle
    for _ in range(0, rounds, CALL):
        eng.run_rounds(CALL, propose_n=props, control=cycle, starts=starts)
    return eng


def run_lockstep(eng, control):
    props = jnp.full((eng.cfg.num_instances,), 2, jnp.int32)
    for lo in range(0, len(control), CALL):
        eng.run_rounds(CALL, propose_n=props, control=control[lo:lo + CALL])
    return eng


def observed(eng) -> dict:
    """``test_scan_tiles.observed`` and the ScanWatch's per-instance
    floor."""
    return dict(tiles.observed(eng),
                read_floor=np.asarray(eng._watch.read_floor))


def assert_same_run(got: dict, want: dict) -> None:
    """Two whole runs left the same behind: state, inbox (wherever a
    slot is valid), history, floor, lane counts, ScanWatch, telemetry."""
    for i, (x, y) in enumerate(zip(got["state"], want["state"])):
        assert x.dtype == y.dtype and (x == y).all(), i
    inbox_equal(got["inbox"], want["inbox"])
    for key in ("history", "read_floor", "lane_rounds", "commits"):
        assert (got[key] == want[key]).all(), key
    assert got["watch"] == want["watch"]
    for x, y in zip(got["telemetry"], want["telemetry"]):
        assert (x == y).all()


@functools.cache
def phased() -> dict:
    return observed(run_phased(settled()))


@functools.cache
def lockstep(start: int) -> dict:
    return observed(run_lockstep(settled(), shifted(start)))


def run_spans(eng) -> list:
    """The stats of `eng`'s ``engine.run_rounds`` spans, oldest first."""
    return [s.stats for s in spans.snapshot()
            if s.name == "engine.run_rounds"
            and s.stats.get("engine") == eng._serial]


def rows_of(groups) -> np.ndarray:
    return (np.asarray(groups)[:, None] * R + np.arange(R)).reshape(-1)


# -- (a) a phased group is the lockstep group on the cycle shifted by its start ------


@pytest.mark.parametrize("start", sorted(set(STARTS.tolist())))
def test_a_group_equals_the_lockstep_run_shifted_by_its_start(start):
    """State, inbox, history, the ScanWatch's per-instance floor and
    the telemetry plane, field by field, for every group of the batch
    that starts at `start`; a group never started against a run in
    which no change is ever on offer."""
    got, want = phased(), lockstep(start)
    rows = rows_of(np.flatnonzero(STARTS == start))
    assert len(rows) == 2 * R
    for i, (x, y) in enumerate(zip(got["state"], want["state"])):
        assert x.dtype == y.dtype and (x[rows] == y[rows]).all(), i
    for key in ("history", "read_floor"):
        assert (got[key][rows] == want[key][rows]).all(), key
    for x, y in zip(got["telemetry"], want["telemetry"]):
        assert (x[rows] == y[rows]).all()
    valid = got["inbox"].valid[rows]
    assert (valid == want["inbox"].valid[rows]).all()
    for f in step_mod.MsgSlots._fields:
        a, b = getattr(got["inbox"], f)[rows], getattr(want["inbox"], f)[rows]
        at = valid.reshape(valid.shape + (1,) * (a.ndim - 3))
        assert (np.where(at, a, 0) == np.where(at, b, 0)).all(), f
    if start == NEVER:
        assert not want["watch"]["swaps_taken"]
        assert not want["watch"]["joint_instance_rounds"]
    else:
        assert want["watch"]["swaps_taken"] == RP4.num_groups


def test_the_phased_run_moved_each_batch_once_and_nobody_else():
    got = phased()
    moved = int((STARTS != NEVER).sum())
    watch = got["watch"]
    assert watch["swaps_taken"] == watch["replicas_reset"] == moved
    assert watch["conf_restores"] == moved
    for name in ("reads_below_commit", "conf_marks_lost",
                 "outsider_votes_or_campaigns", "swaps_before_ready",
                 "joint_commits_in_stall"):
        assert watch[name] == 0, name
    assert not got["telemetry"][1].any(), "an invariant bit is set"
    # Every finished move left {n, m, e} as voters and slot d empty; a
    # group nobody moved holds what it was built with.
    voter = got["state"][list(type(settled().state)._fields).index("voter")]
    d = (E0 + 1) % R
    for g, start in enumerate(STARTS):
        for s in range(R):
            row = voter[g * R + s]
            if start == NEVER:
                want = [s != E0 and t != E0 for t in range(R)]
            else:
                want = [s != d and t != d for t in range(R)]
            assert row.tolist() == want, (g, s)
    # The counts add up over the batches: what each lockstep run counts
    # for all 8 groups, a quarter of it is two groups' share.
    for name in ("swaps_taken", "replicas_reset", "conf_restores"):
        assert watch[name] * 4 == sum(
            lockstep(int(s))["watch"][name] for s in set(STARTS.tolist()))


# -- (b) every start 0 is today's lockstep scan ---------------------------------------


def test_every_start_zero_is_the_lockstep_scan_over_a_whole_cycle():
    zero = np.zeros(RP4.num_groups, np.int32)
    got = observed(run_phased(settled(), starts=zero))
    want = lockstep(0)
    assert_same_run(got, want)
    assert got["watch"]["swaps_taken"] == RP4.num_groups


# -- (c) two tiles equal one scan ----------------------------------------------------


@pytest.mark.parametrize("tiles", [2, 4])
def test_the_tiled_phased_scan_equals_the_one_scan(tiles, monkeypatch):
    """Movers of one batch in both tiles (groups 0 and 4 start at 0,
    3 and 5 at 16, 1 and 7 at 48) and, in four tiles of two groups,
    one tile that holds a mover beside a group nobody moves and, with
    `starts` below, one with none at all."""
    starts = STARTS.copy()
    if tiles == 4:
        starts[[2, 3]] = NEVER  # the second tile: nobody moves
    want = observed(run_phased(settled(), starts=starts))
    eng = run_phased(settled(tiles=tiles, monkeypatch=monkeypatch),
                     starts=starts)
    assert eng._tiles == tiles
    got = observed(eng)
    assert_same_run(got, want)
    assert got["watch"]["swaps_taken"] == int((starts != NEVER).sum())


# -- (d) against the program's oracle, history by history -----------------------------


def test_the_phased_scan_against_the_oracle_history_by_history():
    cfg = RP4
    eng = settled()
    shadows = make_shadows(cfg, E0)
    for g, sh in enumerate(shadows):
        sh.round(campaigns=[int(eng.first_leaders[g])])
        for _ in range(16):
            sh.round()
    run_phased(eng)
    history = [0] * cfg.num_instances
    bits = lambda ids: sum(1 << i for i in ids)  # noqa: E731
    for t in range(ROUNDS):
        for g, sh in enumerate(shadows):
            row = cycle_row(t - int(STARTS[g]))
            away = [] if row["retired"] is None else [row["retired"]]
            sh.round(tick=True, offer=2, isolate=away, reads=True,
                     conf=row["conf"], drained=row["drained"],
                     transfer_to=row["transfer_to"], wipe=row["wipe"])
            for s, (st, mem, rd) in enumerate(zip(
                    sh.snapshot_state(), sh.membership(), sh.read_state())):
                term, role, lead, commit, last = st
                history[g * R + s] = engine_mod.history_fold(
                    history[g * R + s],
                    (term, role, lead, commit, last, *rd, bool(mem[1]),
                     bits(mem[0]), bits(mem[1]), bits(mem[2])))
    assert eng.scan_history().tolist() == history
    replace.assert_equal_to_the_oracle(eng, shadows, "after the run")


# -- the cycle as runs, the refusals, the span ----------------------------------------


def test_the_cycle_is_cut_into_its_runs_of_equal_rows():
    table = cycle_table()
    edges, runs = engine_mod._cycle_runs(table)
    # Nothing, the learner, the learner with the hand-over asked, the
    # swap, the swap with the old machine off, LeaveJoint, the wipe's
    # one round, LeaveJoint again, and the steady row from the end on.
    assert edges.tolist() == [0, ADD, TRANSFER, SWAP, RETIRE, LEAVE, WIPE,
                              WIPE + 1, CYCLE]
    assert not runs[-1].any() and not runs[0].any()
    assert not runs[:, [CTL_READS, CTL_STALL]].any()
    own = table.copy()
    own[:, [CTL_READS, CTL_STALL]] = 0
    for k in range(CYCLE):
        j = np.searchsorted(edges, k, side="right") - 1
        assert (runs[j] == own[k]).all(), k
    assert runs[6, CTL_WIPE] == (E0 + 1) % R + 1
    assert runs[4, CTL_RETIRE] == runs[6, CTL_RETIRE] == (E0 + 1) % R + 1
    assert (runs[2, CTL_FROM], runs[2, CTL_TO]) == (
        (E0 + 1) % R + 1, (E0 + 2) % R + 1)
    assert runs[1, CTL_CONF] == conf_code(CONF_ADD_LEARNER, E0)


def test_reads_and_the_stall_mark_are_a_rounds_and_no_groups():
    """CTL_READS and CTL_STALL are read from the cycle's row of the
    round, whatever a group's start: a cycle that asks for reads in its
    even rounds opens a batch for a group never started too."""
    table = cycle_table()
    table[1::2, CTL_READS] = 0
    eng = settled()
    props = jnp.full((RP4.num_instances,), 2, jnp.int32)
    never = np.full(RP4.num_groups, NEVER, np.int32)
    eng.run_rounds(CALL, propose_n=props, control=table, starts=never)
    stats = run_spans(eng)[-1]
    assert stats["reads"] == CALL // 2 * RP4.num_instances
    assert (stats["batches"], stats["started"]) == (0, 0)
    assert eng.scan_watch()["read_open_instance_rounds"] > 0


def test_the_span_says_which_batches_are_in_flight():
    eng = settled()
    props = jnp.full((RP4.num_instances,), 2, jnp.int32)
    for _ in range(ROUNDS // CALL):
        eng.run_rounds(CALL, propose_n=props, control=cycle_table(),
                       starts=STARTS)
    seen = run_spans(eng)
    assert eng.phase_round == ROUNDS
    # Starts 0, 16 and 48, two groups each: in flight until 128, 144
    # and 176.
    assert [s["batches"] for s in seen] == [2, 3, 3, 3, 2, 1, 0, 0]
    assert [s["started"] for s in seen] == [4, 6] + [6] * 6
    # The wipes fall at 120, 136 and 168: one round a batch.
    assert [s["wipes"] for s in seen] == [0, 0, 0, 1, 1, 1, 0, 0]
    assert all(s["rounds"] == CALL and s["tiles"] == 1 for s in seen)
    assert sum(s["retired"] for s in seen) == 3 * (CYCLE - RETIRE)
    # The lockstep span has neither stat.
    eng.run_rounds(CALL, propose_n=props, control=shifted(NEVER, CALL))
    stats = run_spans(eng)[-1]
    assert "batches" not in stats and "started" not in stats


def test_a_phased_schedule_of_the_wrong_kind_is_refused():
    eng = settled()
    table, n = cycle_table(), RP4.num_groups
    with pytest.raises(ValueError, match="needs control"):
        eng.run_rounds(4, starts=STARTS)
    with pytest.raises(ValueError, match="num_groups"):
        eng.run_rounds(4, control=table, starts=STARTS[:-1])
    with pytest.raises(ValueError, match="num_groups"):
        eng.run_rounds(4, control=table, starts=np.zeros(n))
    with pytest.raises(ValueError, match="rounds >= 0"):
        eng.run_rounds(4, control=table, starts=np.full(n, -1, np.int32))
    with pytest.raises(ValueError, match="cycle_rounds"):
        eng.run_rounds(4, control=table[:, :5], starts=STARTS)
    # The lockstep form still wants a row a round.
    with pytest.raises(ValueError, match=r"\[rounds, CTL_COLS\]"):
        eng.run_rounds(4, control=table)
    assert eng.phase_round == 0
    # A configuration that applies no change itself is offered none.
    plain = MultiRaftEngine(replace.RP4._replace(
        conf_entries=False, replace_replicas=False, num_groups=2))
    with pytest.raises(ValueError, match="conf_entries"):
        plain.run_rounds(4, control=table[:, :5],
                         starts=np.zeros(2, np.int32))


def test_placed_over_nodes_the_phased_form_is_refused():
    eng = MultiRaftEngine(RP4, spare=E0, nodes=jax.devices()[:R])
    with pytest.raises(ValueError, match="not with nodes"):
        eng.run_rounds(4, control=cycle_table(), starts=STARTS)


def test_a_configuration_without_replicas_to_replace_takes_a_phased_drain():
    """Five columns: the hand-over and the change on offer are a
    group's, nothing is retired or wiped. One group demotes a voter
    through a joint configuration at round 4, the others never."""
    from .test_scan_reconf import CONF_DEMOTE

    eng = MultiRaftEngine(RC3)
    g_n, r = RC3.num_groups, RC3.num_replicas
    eng.campaign(np.arange(g_n) * r)
    for _ in range(8):
        eng.step_round()
    table = np.zeros((16, control_cols(RC3)), np.int32)
    table[:, CTL_READS] = 1
    table[2:, CTL_CONF] = conf_code(CONF_DEMOTE, 2)
    starts = np.full(g_n, NEVER, np.int32)
    starts[3] = 4
    eng.run_rounds(CALL, propose_n=jnp.full((g_n * r,), 2, jnp.int32),
                   control=table, starts=starts)
    joint = np.asarray(eng.state.in_joint).reshape(g_n, r)
    assert joint[3].all() and not np.delete(joint, 3, axis=0).any()


# -- the new device work stands under its scope ---------------------------------------


def phased_loop_args(eng) -> tuple:
    ctl, phase, _ = eng._phased_schedule(cycle_table(), STARTS, 4)
    return (eng.state, eng.inbox, eng._zeros_b, eng._zeros_i, eng._tel(),
            eng._flt(), eng._lanes, None, 4, ctl, eng._watch, phase)


@pytest.mark.parametrize("tiles", [0, 2])
def test_every_equation_of_the_phased_loop_has_a_registered_scope(
        tiles, monkeypatch):
    eng = settled(tiles=tiles, monkeypatch=monkeypatch)
    args = phased_loop_args(eng)
    jaxpr = jax.make_jaxpr(eng._closed_loop, static_argnums=(8,))(*args).jaxpr
    by_scope, bare = scoped(jaxpr)
    assert not bare, bare[:10]
    assert "raft_phase" in SCOPES and by_scope["raft_phase"] > 0
    # Nine edges: as many compares and five columns of selects, each
    # with its scalar read out of the runs, the starts widened once a
    # call (and sliced once a tile).
    assert by_scope["raft_phase"] < 9 * (3 + 5 * 4) + 40
    lockstep_ctl, _ = eng._control_schedule(shifted(0, 4), 4)
    jaxpr = jax.make_jaxpr(eng._closed_loop, static_argnums=(8,))(
        *args[:9], lockstep_ctl, eng._watch).jaxpr
    assert "raft_phase" not in scoped(jaxpr)[0]


def test_without_its_scope_the_widening_is_filed_with_the_carry(monkeypatch):
    """``raft_phase`` taken away (its ``with`` a no-op): the starts'
    widening, once a call, stands under no name, and the round's lines
    under the scope round them: a trace would show the carry's share
    grown by the widening's."""
    import contextlib

    def trace():  # a new engine: a jit keeps the trace it made
        eng = settled()
        return scoped(jax.make_jaxpr(eng._closed_loop, static_argnums=(8,))(
            *phased_loop_args(eng)).jaxpr)

    named, _ = trace()
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope",
        lambda s: contextlib.nullcontext() if s == "raft_phase" else real(s))
    bare_of_it, bare = trace()
    assert "raft_phase" not in bare_of_it
    assert sorted(prim for prim, _stack in bare) == [
        "broadcast_in_dim", "reshape"]
    assert bare_of_it["raft_carry"] + len(bare) == (
        named["raft_carry"] + named["raft_phase"])
