"""The heartbeat lanes in two conds each (ISSUE 43): the occupancy
vector carries, after the six lanes, one bit for a MsgTimeoutNow in the
heartbeat lane and one for a MsgAppResp in the heartbeat-response lane
(``step.lane_occupancy``), and deliver runs the campaign and the
MsgAppResp column fold only in a batch that holds one
(``step._deliver_vectorized``). The split is exact by construction;
these tests hold it to that: the closed loop with the bits as computed
against the same loop with both forced true (the whole handlers, every
round: the parent's program), round by round; the whole branch against
the shadow oracle on injected messages; the counter
(``eng.rare_rounds()``); the bits agreed between nodes.

Round-step programs (``conftest.py``): ``test_scan_reconf``'s RC3 and
RC5 and ``test_scan_replace``'s RP4, keys since ISSUE 32 and 34; the
forced bits are an input of the round (``lane_any``), no key.
"""

import hashlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import MultiRaftEngine
from etcd_tpu.batched import engine as engine_mod
from etcd_tpu.batched import step as step_mod
from etcd_tpu.batched.engine import (CTL_FROM, CTL_READS, CTL_TO,
                                     control_cols)
from etcd_tpu.batched.step import (KIND_HB, KIND_HB_RESP, NUM_KINDS, NUM_OCC,
                                   T_APP_RESP, T_TIMEOUT_NOW, MsgSlots)
from etcd_tpu.batched.telemetry import TM_INDEX
from etcd_tpu.raft.types import Message, MessageType

from . import test_scan_reconf as reconf
from . import test_scan_replace as replace
from . import test_scan_tiles as tiles_mod
from .test_scan_faults import inbox_equal

SPARE = 2
CONFIGS = {"r3": reconf.RC3, "r4-replace": replace.RP4, "r5": reconf.RC5}
ROUNDS = 40


def schedule(kind: str, cfg, lead: int):
    """(isolate [ROUNDS, R], control [ROUNDS, cols]) of one stretch:
    node `lead` is asked to hand its leaderships over from round 4
    (``hand-over``), is cut off for 12 rounds and healed (``stale-
    leader``: the groups whose followers draw the shortest timeouts have
    elected by then, CheckQuorum has not stood the old leader down yet,
    and its first heartbeats after the heal draw the nudge; cut off
    until its own CheckQuorum fires, as in the election cell, it never
    sends one), or nothing happens (``steady``); reads are asked in
    every round."""
    r = cfg.num_replicas
    iso = np.zeros((ROUNDS, r), bool)
    ctl = np.zeros((ROUNDS, control_cols(cfg)), np.int32)
    ctl[:, CTL_READS] = 1
    if kind == "hand-over":
        to = next(s for s in range(r) if s not in (lead, SPARE))
        ctl[4:20, CTL_FROM], ctl[4:20, CTL_TO] = lead + 1, to + 1
    elif kind == "stale-leader":
        iso[2:14, lead] = True
    return iso, ctl


def forced(lanes):
    """``lane_occupancy`` with both rare bits set whatever the lanes
    hold: every occupied heartbeat lane runs its whole handler."""
    return step_mod.lane_occupancy(lanes).at[NUM_KINDS:].set(True)


def settled(cfg, lead: int) -> MultiRaftEngine:
    """Every group led from node `lead`, sixteen quiet rounds on."""
    eng = MultiRaftEngine(
        cfg, **({"spare": SPARE} if cfg.replace_replicas else {}))
    eng.campaign(np.arange(cfg.num_groups) * cfg.num_replicas + lead)
    for _ in range(16):
        eng.step_round()
    assert (eng.leaders() == lead).all()
    return eng


def everything(eng: MultiRaftEngine) -> dict:
    got = {"state": [np.asarray(x) for x in jax.tree.leaves(eng.state)],
           "inbox": jax.tree.map(np.asarray, eng.inbox),
           "history": eng.scan_history(), "watch": eng.scan_watch(),
           "lanes": eng.lane_rounds()}
    if eng.cfg.telemetry:
        got["telemetry"] = eng.telemetry()
    return got


def assert_same(got: dict, want: dict, what) -> None:
    for i, (x, y) in enumerate(zip(got["state"], want["state"])):
        assert x.dtype == y.dtype and (x == y).all(), (what, "state", i)
    inbox_equal(got["inbox"], want["inbox"])
    assert (got["history"] == want["history"]).all(), (what, "history")
    assert got["watch"] == want["watch"], (what, "watch")
    assert (got["lanes"] == want["lanes"]).all(), (what, "lanes")
    for x, y in zip(got.get("telemetry", ()), want.get("telemetry", ())):
        assert (x == y).all(), (what, "telemetry")


# -- (a) the split against the whole handlers, round by round ----------------------


@pytest.mark.parametrize("kind", ["hand-over", "stale-leader", "steady"])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_the_split_lanes_equal_the_whole_handlers_every_round(
        name, kind, monkeypatch):
    cfg, lead = CONFIGS[name], 1
    iso, ctl = schedule(kind, cfg, lead)
    props = jnp.full((cfg.num_instances,), 2, jnp.int32)

    def rounds(eng):
        """A call a round, and what the engine holds after each."""
        for t in range(ROUNDS):
            eng.run_rounds(1, propose_n=props, isolate=iso[t:t + 1],
                           control=ctl[t:t + 1])
            yield everything(eng)

    split = settled(cfg, lead)
    after = list(rounds(split))
    # (An engine's scan is traced at its first call: the patch is in
    # place for the second engine's alone.)
    monkeypatch.setattr(engine_mod, "lane_occupancy", forced)
    whole = settled(cfg, lead)
    for t, (want, got) in enumerate(zip(after, rounds(whole))):
        assert_same(got, want, (name, kind, t))
    ton, apr = split.rare_rounds().tolist()
    hb, hb_resp = (int(split.lane_rounds()[k]) for k in (KIND_HB, KIND_HB_RESP))
    assert hb > ROUNDS // 2 and hb_resp > ROUNDS // 2, "a leader most rounds"
    assert (whole.rare_rounds() == ROUNDS).all()
    # Each schedule holds what it is named for: the plain branches ran
    # in most rounds, the whole ones where the schedule says. (A
    # hand-over makes a stale leader of the old one for a round: its
    # last heartbeats draw the nudge too.)
    if kind == "hand-over":
        assert 0 < ton < ROUNDS // 4 and apr < ROUNDS // 4
        assert (split.leaders() != lead).all()
    elif kind == "stale-leader":
        assert ton == 0 and 0 < apr < ROUNDS // 4
        assert (split.terms().max(axis=1) > 1).any()
    else:
        assert (ton, apr) == (0, 0)
    assert split.commits().max(axis=1).min() > ROUNDS


# -- (b) the bits are a superset: injected messages take the whole branch ----------


def inject(eng, shadows, g, target, sender, kind, typ, mtype, **fields):
    """One message into the inbox of both, in place of whatever the
    slot held."""
    r = eng.cfg.num_replicas
    row = g * r + target
    inbox = eng.inbox
    slot = {f: x.at[row, sender, kind].set(jnp.zeros((), x.dtype))
            for f, x in zip(MsgSlots._fields, inbox)}
    slot["valid"] = inbox.valid.at[row, sender, kind].set(True)
    slot["type"] = inbox.type.at[row, sender, kind].set(typ)
    for f, v in fields.items():
        slot[f] = getattr(inbox, f).at[row, sender, kind].set(v)
    eng.inbox = MsgSlots(**slot)
    shadows[g].inbox[target][sender][kind] = Message(
        type=mtype, to=target + 1, from_=sender + 1, **fields)


def scan_both(eng, shadows, rounds=1):
    cfg = eng.cfg
    ctl = np.zeros((1, control_cols(cfg)), np.int32)
    ctl[:, CTL_READS] = 1
    for _ in range(rounds):
        eng.run_rounds(1, propose_n=jnp.full((cfg.num_instances,), 2,
                                             jnp.int32), control=ctl)
        for sh in shadows:
            sh.round(tick=True, offer=2, reads=True)


def test_an_injected_timeout_now_takes_the_whole_branch_and_the_oracles_step():
    eng, shadows, slots = reconf.settled_pair(reconf.RC3)
    r = eng.cfg.num_replicas
    scan_both(eng, shadows, 3)
    assert (eng.rare_rounds() == 0).all()
    term = eng.terms()
    for g in range(0, eng.cfg.num_groups, 2):
        lead = int(slots[g])
        inject(eng, shadows, g, (lead + 1) % r, lead, KIND_HB, T_TIMEOUT_NOW,
               MessageType.MsgTimeoutNow, term=int(term[g, lead]))
    scan_both(eng, shadows)
    reconf.assert_equal_to_the_oracle(eng, shadows, "the round it arrives")
    assert eng.rare_rounds().tolist() == [1, 0]
    # It campaigned at once, past PreVote and the lease.
    assert (eng.terms()[::2].max(axis=1) == term[::2].max(axis=1) + 1).all()
    for t in range(6):
        scan_both(eng, shadows)
        reconf.assert_equal_to_the_oracle(eng, shadows, ("after", t))
    want = np.where(np.arange(eng.cfg.num_groups) % 2 == 0,
                    (slots + 1) % r, slots)
    assert (eng.leaders() == want).all()
    # (The old leader's last heartbeats drew the stale-leader nudge.)
    assert eng.rare_rounds()[0] == 1 and eng.rare_rounds()[1] <= 2


def test_an_injected_same_term_app_resp_in_the_hb_resp_lane_is_folded():
    """The protocol never sends one (a MsgAppResp that answers a
    heartbeat is a stale leader's nudge and carries a higher term): the
    whole branch must take it as ``stepLeader`` does all the same."""
    eng, shadows, slots = reconf.settled_pair(reconf.RC3)
    r = eng.cfg.num_replicas
    scan_both(eng, shadows, 3)
    term, last = eng.terms(), np.asarray(eng.state.last)
    match = np.asarray(eng.state.match)
    moved = 0
    for g in range(eng.cfg.num_groups):
        lead = int(slots[g])
        peer = (lead + 1) % r
        # What the peer holds and the leader has not heard of yet.
        acked = int(last[g * r + peer])
        moved += acked > match[g * r + lead, peer]
        inject(eng, shadows, g, lead, peer, KIND_HB_RESP, T_APP_RESP,
               MessageType.MsgAppResp, term=int(term[g, lead]), index=acked)
    assert moved == eng.cfg.num_groups, "the injected ack says nothing new"
    scan_both(eng, shadows)
    reconf.assert_equal_to_the_oracle(eng, shadows, "the round it arrives")
    assert eng.rare_rounds().tolist() == [0, 1]
    got = np.asarray(eng.state.match)
    for g in range(eng.cfg.num_groups):
        lead = int(slots[g])
        assert got[g * r + lead, (lead + 1) % r] >= last[
            g * r + (lead + 1) % r]
    for t in range(4):
        scan_both(eng, shadows)
        reconf.assert_equal_to_the_oracle(eng, shadows, ("after", t))
    assert eng.rare_rounds().tolist() == [0, 1]


def test_the_occupancy_vector_names_its_bits():
    lanes = step_mod.split_lanes(
        step_mod.empty_msgs((6, 3, NUM_KINDS), 4))
    assert not np.asarray(step_mod.lane_occupancy(lanes)).any()

    def holding(kind, typ, valid=True):
        x = lanes[kind]
        return lanes[:kind] + (x._replace(
            valid=x.valid.at[2, 1].set(valid),
            type=x.type.at[2, 1].set(typ)),) + lanes[kind + 1:]

    occ = lambda ls: np.flatnonzero(  # noqa: E731
        np.asarray(step_mod.lane_occupancy(ls))).tolist()
    assert step_mod.NUM_OCC == NUM_KINDS + 2
    assert occ(holding(KIND_HB, step_mod.T_HB)) == [KIND_HB]
    assert occ(holding(KIND_HB, T_TIMEOUT_NOW)) == [
        KIND_HB, step_mod.RARE_TIMEOUT_NOW]
    assert occ(holding(KIND_HB_RESP, step_mod.T_HB_RESP)) == [KIND_HB_RESP]
    assert occ(holding(KIND_HB_RESP, T_APP_RESP)) == [
        KIND_HB_RESP, step_mod.RARE_APP_RESP]
    # A type in a slot that is not valid is no message; the same types
    # in the lanes they are common in are no rare bit.
    assert occ(holding(KIND_HB, T_TIMEOUT_NOW, valid=False)) == []
    assert occ(holding(step_mod.KIND_APP_RESP, T_APP_RESP)) == [
        step_mod.KIND_APP_RESP]


# -- (c) the counter ---------------------------------------------------------------


@pytest.mark.parametrize("tiles", [0, 2, 4], ids=["untiled", "2", "4"])
def test_rare_rounds_counts_the_hand_over_rounds_of_a_drain_period(
        tiles, monkeypatch):
    """``engine1m-r3``'s own cycle at 8 groups (``test_scan_tiles.run``:
    128 rounds of joint-readindex's drain, leaders drawn from a seed):
    a MsgTimeoutNow is in flight in the rounds after the hand-over is
    first asked (round 8) and nowhere else, and in as many rounds
    whatever the tiles; the counter keeps ``lane_rounds()`` its shape."""
    eng = tiles_mod.run("engine1m-r3", tiles, monkeypatch)
    assert eng.lane_rounds().shape == (NUM_KINDS,)
    assert eng.rare_rounds().shape == (NUM_OCC - NUM_KINDS,)
    assert (eng.lane_rounds() == tiles_mod.untiled("engine1m-r3")[
        "lane_rounds"]).all()
    ton, apr = eng.rare_rounds().tolist()
    sent = eng.telemetry()[0][:, TM_INDEX["sent_timeout_now"]].sum()
    assert 0 < ton <= sent and ton < 16, (ton, sent)
    assert apr < 16
    if tiles:
        with pytest.MonkeyPatch.context() as mp:
            whole = tiles_mod.run("engine1m-r3", 0, mp)
        assert (eng.rare_rounds() == whole.rare_rounds()).all()


def test_rare_rounds_is_zero_over_a_steady_run():
    cfg = reconf.RC3
    eng = settled(cfg, 0)
    eng.run_rounds(64, propose_n=jnp.full((cfg.num_instances,), 2, jnp.int32))
    assert int(eng.lane_rounds()[KIND_HB]) >= 63
    assert eng.rare_rounds().tolist() == [0, 0]


# -- without the lane skip the round is the parent's --------------------------------

# sha256 of the lowered round of the two configurations that are built
# with ``lane_skip=False`` anywhere in the suite (``test_route``'s
# BOTH_FORMS: keys already), handed [N, R, K] slots as a hosting member
# hands them. Under a mapped predicate a cond is a select, so there each
# heartbeat lane keeps its one cond (and emit its one body, the ring
# read every round) and PR 43 left the text of eaa210e (PR 42) as it
# was: a hosting member that shards its rows over a mesh compiled
# nothing anew. Re-pinned by PR 45 on its own text: the state carries
# `own_from`, and `_maybe_commit` and `_control`'s committed-in-term
# read it and not the ring, with or without the lane skip.
# Re-pinned by PR 48 on its own text: handed slots the round splits
# them into lanes of `step.LANE_FIELDS` alone, fills the rest with zero
# constants for the handlers and stacks zeros back, with or without the
# lane skip (a hosting member compiles its round anew once).
# Re-pinned by PR 49 on its own text: `_tick`'s campaign writes its one
# entry through one ring column, with or without the lane skip.
NO_SKIP_TEXT = {
    "r3-wide-noskip":
        "43843fa83d5ff17a162fd0d339e746aeb4fa2fcfb873bb05e950d5fd3aeaed84",
    "r5-narrow-noskip":
        "73997e2b3c71568ab9c914d6144a23dab718fd764ef7b37e4147ef040b3cc3dd",
}


@pytest.mark.parametrize("name", sorted(NO_SKIP_TEXT))
def test_without_the_lane_skip_the_round_is_the_parents_text(name):
    from .test_route import BOTH_FORMS

    make_cfg, lane_skip = BOTH_FORMS[name]
    assert not lane_skip
    cfg = make_cfg().validate().resolved()
    eng = MultiRaftEngine(cfg)
    zb = jnp.zeros((cfg.num_instances,), bool)
    zi = jnp.zeros((cfg.num_instances,), jnp.int32)
    text = jax.jit(step_mod.make_step_round(cfg, lane_skip=False)).lower(
        eng.state, eng.inbox, zb, zb, zi, zb).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == NO_SKIP_TEXT[name]
    assert "stablehlo.case" not in text  # every cond a select


def test_with_the_lane_skip_the_round_holds_nine_conds():
    """Six lanes, the two heartbeat lanes in two conds each, and emit's
    (ISSUE 45: the ring read for the terms a message states, or the
    sender's own term); the round that computes the occupancy itself (a
    hosting member on one device, ``make_step_round``'s default) splits
    them like the engine's."""
    eng = MultiRaftEngine(reconf.RC3)
    zb, zi = eng._zeros_b, eng._zeros_i
    text = jax.jit(eng._step).lower(
        eng.state, eng.inbox, zb, zb, zi, zb).as_text()
    assert text.count("stablehlo.case") == NUM_KINDS + 3


# -- (d) placed over nodes: the bits are agreed with the lanes ---------------------

CHILD = r"""
import sys
import jax, jax.numpy as jnp, numpy as np
from etcd_tpu.batched import MultiRaftEngine
from etcd_tpu.batched.engine import CTL_FROM, CTL_READS, CTL_TO, control_cols
from tests.batched.test_scan_replace import RP4
from tests.batched.test_rare_lanes import SPARE, everything, assert_same

cfg = RP4
r, n = cfg.num_replicas, cfg.num_instances
ctl = np.zeros((32, control_cols(cfg)), np.int32)
ctl[:, CTL_READS] = 1
ctl[4:20, CTL_FROM], ctl[4:20, CTL_TO] = 2, 1   # node 1 hands over to node 0
iso = np.zeros((32, r), bool)
iso[24:30, 3] = True
engines = []
for nodes in (None, jax.devices()[:r]):
    eng = MultiRaftEngine(cfg, spare=SPARE, nodes=nodes)
    eng.campaign(np.arange(cfg.num_groups) * r + 1)
    for _ in range(16):
        eng.step_round()
    assert (eng.leaders() == 1).all()
    for lo in (0, 16):
        eng.run_rounds(16, propose_n=jnp.full((n,), 2, jnp.int32),
                       isolate=iso[lo:lo + 16], control=ctl[lo:lo + 16])
    engines.append(eng)
one, placed = engines
for x, y in zip(jax.tree.leaves(one.state), jax.tree.leaves(placed.state)):
    assert (np.asarray(x) == placed.logical(y)).all()
assert (one.lane_rounds() == placed.lane_rounds()).all()
# Node 0 receives the MsgTimeoutNow that node 1 wrote: only the agreed
# bit tells node 0's deliver, and every node counts the same rounds.
assert (one.rare_rounds() == placed.rare_rounds()).all(), (
    one.rare_rounds(), placed.rare_rounds())
assert 0 < placed.rare_rounds()[0] < 12
assert (placed.leaders() == 0).all()
assert placed.lane_exchanges().shape == (6,)
print("AGREED", placed.rare_rounds().tolist())
"""


def test_placed_over_nodes_the_bits_are_agreed_with_the_lanes():
    """In a child process with a time limit, as ``test_scan_nodes``
    runs its node-placed engines: a collective in a branch only some
    nodes take hangs and does not fail."""
    root = os.path.join(os.path.dirname(__file__), "..", "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, "-c", CHILD], cwd=root, env=env, timeout=600,
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "AGREED" in out.stdout
