"""Batched-engine feature envelope: learners, joint membership,
leader transfer, ReadIndex — on-device implementations of the paths
the review of round 1 flagged as host-only (ref: raft.go:1339-1372
transfer; read_only.go; confchange/confchange.go; tracker learners)."""

import jax.numpy as jnp
import numpy as np
import pytest

from etcd_tpu.batched import BatchedConfig, MultiRaftEngine
from etcd_tpu.batched.shadow import ShadowCluster
from etcd_tpu.batched.state import FOLLOWER, LEADER
from etcd_tpu.raft.quorum import JointConfig, MajorityConfig

from .test_differential import device_state


def make_engine(groups=1, r=3, **kw):
    kw.setdefault("election_timeout", 1 << 20)
    cfg = BatchedConfig(
        num_groups=groups,
        num_replicas=r,
        window=64,
        max_ents_per_msg=16,
        max_props_per_round=4,
        heartbeat_timeout=1,
        max_inflight=1 << 20,
        **kw,
    )
    return cfg, MultiRaftEngine(cfg)


def elect(eng, instance=0, rounds=4):
    eng.campaign([instance])
    for _ in range(rounds):
        eng.step_round()


class TestLearners:
    def test_learner_replicates_but_does_not_vote(self):
        cfg, eng = make_engine(r=3)
        eng.set_membership(0, voters=[0, 1], learners=[2])
        elect(eng)
        assert int(eng.state.role[0]) == LEADER

        props = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(3)
        eng.step_round(propose_n=props)
        for _ in range(4):
            eng.step_round()
        # Learner caught up to the commit index.
        assert int(eng.state.commit[2]) == int(eng.state.commit[0])
        # Learner granted no vote (it's outside the electorate): the
        # leader won with votes from 0 and 1 only.
        assert not bool(eng.state.voter[0, 2])

    def test_learner_never_campaigns(self):
        cfg, eng = make_engine(r=3)
        eng.set_membership(0, voters=[0, 1], learners=[2])
        eng.campaign([2])  # must be ignored: learners aren't promotable
        for _ in range(3):
            eng.step_round()
        assert int(eng.state.role[2]) == FOLLOWER
        assert int(eng.state.term[2]) == 0

    def test_differential_with_learner(self):
        """Replication schedule vs the oracle with slot 2 a learner."""
        cfg, eng = make_engine(r=3)
        eng.set_membership(0, voters=[0, 1], learners=[2])
        shadow = ShadowCluster(3, learners=[2])

        eng.campaign([0])
        shadow.round(campaigns=[0])
        for rnd in range(8):
            props = jnp.zeros((cfg.num_instances,), jnp.int32)
            pr = {}
            if rnd == 2:
                props = props.at[0].set(2)
                pr = {0: 2}
            eng.step_round(propose_n=props)
            shadow.round(proposals=pr)
            assert device_state(eng, cfg) == shadow.snapshot_state(), rnd


class TestJointConfig:
    def test_joint_commit_needs_both_quorums(self):
        """In joint {0,1} x {1,2}, an entry acked by 0,1 commits the
        incoming half but not the outgoing one until 2 acks."""
        cfg, eng = make_engine(r=3)
        elect(eng)
        base = int(eng.state.commit[0])
        eng.set_membership(0, voters=[0, 1], voters_out=[1, 2], joint=True)

        # Propose while 2 is partitioned: {0,1} ack, {1,2} has only 1.
        props = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(1)
        iso = jnp.zeros((cfg.num_instances,), bool).at[2].set(True)
        eng.step_round(propose_n=props, isolate=iso)
        for _ in range(3):
            eng.step_round(isolate=iso)
        assert int(eng.state.commit[0]) == base, \
            "committed without the outgoing quorum"

        # Heal; heartbeat ticks drive the resend to the healed peer
        # (hb-resp -> empty append -> reject -> probe -> append takes
        # a few message rounds).
        for _ in range(10):
            eng.step_round(tick=True)
        assert int(eng.state.commit[0]) == base + 1

    def test_joint_election_needs_both_quorums(self):
        """A joint-config candidate must win both halves
        (quorum/joint.go:61-75)."""
        cfg, eng = make_engine(r=5)
        eng.set_membership(0, voters=[0, 1], voters_out=[2, 3, 4],
                           joint=True)
        # Outgoing majority {3, 4} partitioned: vote can't complete.
        iso = jnp.zeros((cfg.num_instances,), bool)
        iso = iso.at[3].set(True).at[4].set(True)
        eng.campaign([0])
        for _ in range(4):
            eng.step_round(isolate=iso)
        assert int(eng.state.role[0]) != LEADER
        # Heal and re-campaign (the dropped vote requests are not
        # retried without a timer election): now both halves answer.
        eng.campaign([0])
        for _ in range(4):
            eng.step_round()
        assert int(eng.state.role[0]) == LEADER

    def test_quorum_kernels_match_host_oracle(self):
        """Quickcheck: joint_committed / joint_vote_result against the
        host quorum module (the reference-verified oracle),
        ref: quorum/quick_test.go's alternative-definition check."""
        import random

        from etcd_tpu.batched.kernels import (
            VOTE_LOST, VOTE_PENDING, VOTE_WON,
            joint_committed, joint_vote_result,
        )
        from etcd_tpu.raft.quorum import VoteResult

        rng = random.Random(7)
        vr_map = {
            VoteResult.VoteWon: VOTE_WON,
            VoteResult.VoteLost: VOTE_LOST,
            VoteResult.VotePending: VOTE_PENDING,
        }
        for _ in range(200):
            r = rng.randint(1, 7)
            voters_in = {s for s in range(r) if rng.random() < 0.6}
            joint = rng.random() < 0.5
            voters_out = ({s for s in range(r) if rng.random() < 0.6}
                          if joint else set())
            match = [rng.randint(0, 20) for _ in range(r)]
            votes = [rng.choice((-1, 0, 1)) for _ in range(r)]

            jc = JointConfig(
                incoming={s + 1 for s in voters_in},
                outgoing={s + 1 for s in voters_out} if joint else set(),
            )
            want_ci = jc.committed_index(
                lambda vid: match[vid - 1])
            want_vr = jc.vote_result(
                {s + 1: votes[s] == 1 for s in range(r)
                 if votes[s] != -1})

            vin = jnp.asarray([s in voters_in for s in range(r)])
            vout = jnp.asarray([s in voters_out for s in range(r)])
            got_ci = int(joint_committed(
                jnp.asarray(match), vin, vout, jnp.asarray(joint)))
            got_vr = int(joint_vote_result(
                jnp.asarray(votes), vin, vout, jnp.asarray(joint)))
            # The kernel saturates empty-config "commit everything" to
            # MAX_I32; the host oracle uses a huge sentinel too.
            if want_ci > 2**30:
                assert got_ci > 2**30
            else:
                assert got_ci == want_ci, (voters_in, voters_out, match)
            assert got_vr == vr_map[want_vr], (voters_in, voters_out, votes)


class TestLeaderTransfer:
    def test_transfer_to_caught_up_follower(self):
        cfg, eng = make_engine(r=3)
        elect(eng)
        assert int(eng.state.role[0]) == LEADER
        eng.transfer_leader(0, target_slot=1)
        for _ in range(4):
            eng.step_round()
        assert int(eng.state.role[1]) == LEADER
        assert int(eng.state.role[0]) == FOLLOWER
        assert int(eng.state.term[1]) == int(eng.state.term[0])

    def test_transfer_waits_for_catch_up(self):
        """A lagging transferee first catches up, then gets TimeoutNow
        (raft.go:1358-1371)."""
        cfg, eng = make_engine(r=3)
        elect(eng)
        # Lag follower 1 behind with proposals it never sees.
        iso = jnp.zeros((cfg.num_instances,), bool).at[1].set(True)
        props = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(3)
        eng.step_round(propose_n=props, isolate=iso)
        eng.step_round(isolate=iso)
        assert int(eng.state.last[1]) < int(eng.state.last[0])

        tr = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(2)
        eng.step_round(transfer_to=tr, isolate=iso)
        # Still leader: transfer pending on catch-up.
        assert int(eng.state.role[0]) == LEADER
        for _ in range(12):  # heal: hb-probe catch-up then TimeoutNow
            eng.step_round(tick=True)
        assert int(eng.state.role[1]) == LEADER

    def test_proposals_dropped_during_transfer(self):
        cfg, eng = make_engine(r=3)
        elect(eng)
        iso = jnp.zeros((cfg.num_instances,), bool).at[1].set(True)
        tr = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(2)
        # Transfer to isolated follower 1: stays pending; proposals
        # must be dropped meanwhile (raft.go:1048-1053).
        eng.step_round(transfer_to=tr, isolate=iso)
        last = int(eng.state.last[0])
        props = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(2)
        eng.step_round(propose_n=props, isolate=iso)
        assert int(eng.state.last[0]) == last

    def test_transfer_aborts_after_election_timeout(self):
        cfg, eng = make_engine(r=3, election_timeout=4)
        eng.campaign([0])
        for _ in range(3):
            eng.step_round()
        iso = jnp.zeros((cfg.num_instances,), bool).at[1].set(True)
        tr = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(2)
        eng.step_round(transfer_to=tr, isolate=iso)
        assert int(eng.state.transferee[0]) == 2
        for _ in range(5):  # > election timeout of leader ticks
            eng.step_round(tick=True, isolate=iso)
        assert int(eng.state.transferee[0]) == 0, "transfer not aborted"
        # Proposals flow again.
        last = int(eng.state.last[0])
        props = jnp.zeros((cfg.num_instances,), jnp.int32).at[0].set(1)
        eng.step_round(propose_n=props, isolate=iso)
        assert int(eng.state.last[0]) == last + 1

    def test_differential_transfer(self):
        """Transfer schedule runs lockstep with the oracle."""
        from .test_differential import make_pair, run_lockstep

        cfg, eng, shadows = make_pair(groups=1)
        schedule = [
            {"campaign": [(0, 0)]},
            {}, {},
            {"propose": {(0, 0): 2}},
            {}, {},
            {"transfer": {(0, 0): 1}},
            {}, {}, {},
        ]
        n = cfg.num_instances
        for rnd, step in enumerate(schedule):
            camp = np.zeros(n, bool)
            props = np.zeros(n, np.int32)
            tr = np.zeros(n, np.int32)
            sh_camp, sh_props, sh_tr = [], {}, {}
            for g, s in step.get("campaign", []):
                camp[g * 3 + s] = True
                sh_camp.append(s)
            for (g, s), k in step.get("propose", {}).items():
                props[g * 3 + s] = k
                sh_props[s] = k
            for (g, s), t in step.get("transfer", {}).items():
                tr[g * 3 + s] = t + 1
                sh_tr[s] = t
            eng.step_round(
                campaign_mask=jnp.asarray(camp),
                propose_n=jnp.asarray(props),
                transfer_to=jnp.asarray(tr),
            )
            shadows[0].round(campaigns=sh_camp, proposals=sh_props,
                             transfers=sh_tr)
            assert device_state(eng, cfg) == shadows[0].snapshot_state(), rnd
        assert int(eng.state.role[1]) == LEADER


class TestNodeContract:
    """The raft.Node plugin boundary now carries ReadIndex and
    TransferLeadership on the batched backend (node.go:550-560)."""

    def _pump(self, nodes, rounds=40, until=None):
        for _ in range(rounds):
            for n in nodes.values():
                n.tick()
            for i, n in nodes.items():
                rd = n.ready(timeout=0.05)
                if rd is None:
                    continue
                for m in rd.messages:
                    if int(m.type) == 2:  # MsgProp host-forward
                        nodes[m.to].step(m)
                    else:
                        nodes[m.to].step(m)
                n.advance()
                if until is not None and until(rd):
                    return rd
        return None

    def test_node_read_index_roundtrip(self):
        from etcd_tpu.batched.node import BatchedNode

        nodes = {i: BatchedNode(i, [1, 2, 3], election_tick=4)
                 for i in (1, 2, 3)}
        self._pump(nodes, until=lambda rd: False)  # elect someone
        leader = next(n for n in nodes.values() if n.rn.is_leader(0))
        leader.read_index(b"rctx-1")
        rd = self._pump(nodes, until=lambda rd: bool(rd.read_states))
        assert rd is not None
        rs = rd.read_states[0]
        assert rs.request_ctx == b"rctx-1"
        assert rs.index == leader.rn.latest_commit(0)

    def test_node_transfer_leadership(self):
        from etcd_tpu.batched.node import BatchedNode

        nodes = {i: BatchedNode(i, [1, 2, 3], election_tick=4)
                 for i in (1, 2, 3)}
        self._pump(nodes)
        leader_id = next(i for i, n in nodes.items() if n.rn.is_leader(0))
        target = next(i for i in nodes if i != leader_id)
        nodes[leader_id].transfer_leadership(leader_id, target)
        self._pump(nodes, rounds=40,
                   until=lambda rd: nodes[target].rn.is_leader(0))
        assert nodes[target].rn.is_leader(0)


class TestConfChangeThroughLog:
    """propose_conf_change → committed EntryConfChange → Changer →
    device mask upload, through the Node contract (node.go
    ProposeConfChange / raft.go applyConfChange)."""

    def _cluster(self):
        from etcd_tpu.batched.node import BatchedNode

        return {i: BatchedNode(i, [1, 2, 3], election_tick=4)
                for i in (1, 2, 3)}

    def _pump_until(self, nodes, confstates, pred, rounds=60):
        from etcd_tpu.raft.types import ConfChange, ConfChangeV2, EntryType

        for _ in range(rounds):
            for n in nodes.values():
                n.tick()
            for i, n in nodes.items():
                rd = n.ready(timeout=0.05)
                if rd is None:
                    continue
                for e in rd.committed_entries:
                    if e.type == EntryType.EntryConfChange and e.data:
                        confstates[i] = n.apply_conf_change(
                            ConfChange.unmarshal(e.data))
                    elif e.type == EntryType.EntryConfChangeV2:
                        confstates[i] = n.apply_conf_change(
                            ConfChangeV2.unmarshal(e.data))
                for m in rd.messages:
                    nodes[m.to].step(m)
                n.advance()
            if pred():
                return True
        return False

    def test_remove_then_readd_voter(self):
        from etcd_tpu.raft.types import ConfChange, ConfChangeType

        nodes = self._cluster()
        confstates = {}
        assert self._pump_until(
            nodes, confstates,
            lambda: any(n.rn.is_leader(0) for n in nodes.values()))
        leader_id = next(i for i, n in nodes.items() if n.rn.is_leader(0))
        victim = next(i for i in nodes
                      if i != leader_id)

        # Remove a follower: every member's masks drop it.
        nodes[leader_id].propose_conf_change(ConfChange(
            id=1, type=ConfChangeType.ConfChangeRemoveNode,
            node_id=victim))
        assert self._pump_until(
            nodes, confstates,
            lambda: confstates.get(leader_id) is not None
            and victim not in confstates[leader_id].voters)
        lead_node = nodes[leader_id]
        import numpy as np
        # Mask uploads are STAGED and applied at the head of the next
        # round (set_membership is called from apply/transport threads;
        # an in-place device-state edit would race the round thread).
        assert self._pump_until(
            nodes, confstates,
            lambda: not bool(np.asarray(
                lead_node.rn.state.voter[0])[victim - 1]))

        # The 2-voter cluster still commits.
        lead_node.propose(b"two-voter-write")
        base = lead_node.rn.latest_commit(0)
        assert self._pump_until(
            nodes, confstates,
            lambda: lead_node.rn.latest_commit(0) > base)

        # Re-add as learner, then promote to voter.
        lead_node.propose_conf_change(ConfChange(
            id=2, type=ConfChangeType.ConfChangeAddLearnerNode,
            node_id=victim))
        assert self._pump_until(
            nodes, confstates,
            lambda: confstates.get(leader_id) is not None
            and victim in confstates[leader_id].learners)
        assert self._pump_until(
            nodes, confstates,
            lambda: bool(np.asarray(
                lead_node.rn.state.learner[0])[victim - 1]))

        lead_node.propose_conf_change(ConfChange(
            id=3, type=ConfChangeType.ConfChangeAddNode, node_id=victim))
        assert self._pump_until(
            nodes, confstates,
            lambda: confstates.get(leader_id) is not None
            and victim in confstates[leader_id].voters)
        assert self._pump_until(
            nodes, confstates,
            lambda: bool(np.asarray(
                lead_node.rn.state.voter[0])[victim - 1]))

    def test_joint_confchange_v2(self):
        """Explicit-joint V2 change passes through enter/leave joint
        with the device masks tracking both halves."""
        import numpy as np

        from etcd_tpu.raft.types import (
            ConfChangeSingle, ConfChangeTransition, ConfChangeType,
            ConfChangeV2)

        nodes = self._cluster()
        confstates = {}
        assert self._pump_until(
            nodes, confstates,
            lambda: any(n.rn.is_leader(0) for n in nodes.values()))
        leader_id = next(i for i, n in nodes.items() if n.rn.is_leader(0))
        lead_node = nodes[leader_id]
        victim = next(i for i in nodes if i != leader_id)

        cc = ConfChangeV2(
            transition=ConfChangeTransition.ConfChangeTransitionJointExplicit,
            changes=[ConfChangeSingle(
                ConfChangeType.ConfChangeRemoveNode, victim)],
        )
        lead_node.propose_conf_change(cc)
        assert self._pump_until(
            nodes, confstates,
            lambda: confstates.get(leader_id) is not None
            and bool(confstates[leader_id].voters_outgoing))
        assert self._pump_until(
            nodes, confstates,
            lambda: bool(np.asarray(lead_node.rn.state.in_joint)[0]))

        # Leave joint.
        lead_node.propose_conf_change(ConfChangeV2())
        assert self._pump_until(
            nodes, confstates,
            lambda: confstates.get(leader_id) is not None
            and not confstates[leader_id].voters_outgoing
            and victim not in confstates[leader_id].voters)
        assert self._pump_until(
            nodes, confstates,
            lambda: not bool(np.asarray(lead_node.rn.state.in_joint)[0]))


class TestReadIndex:
    def test_read_confirms_with_quorum(self):
        cfg, eng = make_engine(r=3)
        elect(eng)
        commit0 = int(eng.state.commit[0])
        eng.read_index([0])
        seq, idx, ready = eng.read_states()
        assert idx[0] == commit0 and not ready[0]
        eng.step_round()  # heartbeats out
        eng.step_round()  # acks back
        seq, idx, ready = eng.read_states()
        assert ready[0] and idx[0] == commit0

    def test_read_blocked_without_quorum(self):
        cfg, eng = make_engine(r=3)
        elect(eng)
        iso = jnp.zeros((cfg.num_instances,), bool)
        iso = iso.at[1].set(True).at[2].set(True)
        req = jnp.zeros((cfg.num_instances,), bool).at[0].set(True)
        eng.step_round(read_req=req, isolate=iso)
        for _ in range(3):
            eng.step_round(isolate=iso)
        _, _, ready = eng.read_states()
        assert not ready[0]
        for _ in range(4):  # heal: ticked heartbeats re-carry the ctx
            eng.step_round(tick=True)
        _, idx, ready = eng.read_states()
        assert ready[0] and idx[0] == int(eng.state.commit[0])

    def test_single_voter_read_instant(self):
        cfg, eng = make_engine(r=3)
        eng.set_membership(0, voters=[0], learners=[1, 2])
        elect(eng)
        eng.read_index([0])
        _, idx, ready = eng.read_states()
        assert ready[0] and idx[0] == int(eng.state.commit[0])

    def test_read_state_cleared_on_leader_change(self):
        cfg, eng = make_engine(r=3)
        elect(eng)
        eng.read_index([0])
        eng.transfer_leader(0, target_slot=1)
        for _ in range(4):
            eng.step_round()
        assert int(eng.state.role[1]) == LEADER
        _, idx, _ = eng.read_states()
        assert idx[0] == -1  # old leader's read state died with the term

    def test_follower_read_req_ignored(self):
        cfg, eng = make_engine(r=3)
        elect(eng)
        req = jnp.zeros((cfg.num_instances,), bool).at[1].set(True)
        eng.step_round(read_req=req)
        _, idx, ready = eng.read_states()
        assert idx[1] == -1 and not ready[1]

    def test_pending_batch_not_clobbered_by_new_requests(self):
        """Requests during an in-flight batch latch instead of
        resetting it — sustained read traffic can't starve quorum
        confirmation (read_only.go pending queue semantics). Without
        the latch every round would open a fresh seq (orphaning all
        in-flight acks); with it, batches coalesce and confirm."""
        cfg, eng = make_engine(r=3)
        elect(eng)
        req = jnp.zeros((cfg.num_instances,), bool).at[0].set(True)
        eng.step_round(read_req=req)  # opens seq 1
        # Hammer new requests every round.
        for _ in range(5):
            eng.step_round(read_req=req)
        # Coalescing bound: a batch takes 2 rounds to confirm, so 6
        # request rounds open at most ~4 batches (clobbering would
        # open 6 and confirm none mid-stream).
        assert int(eng.state.read_seq[0]) <= 4
        for _ in range(4):  # quiesce: the last batch confirms
            eng.step_round()
        _, idx, ready = eng.read_states()
        assert ready[0]

    def test_node_later_waiter_not_served_stale_batch(self):
        """A waiter enqueued after a batch opened is served by a LATER
        batch whose index covers its request time."""
        from etcd_tpu.batched.node import BatchedNode

        nodes = {i: BatchedNode(i, [1, 2, 3], election_tick=4)
                 for i in (1, 2, 3)}
        pump = TestNodeContract()._pump
        pump(nodes)
        leader = next(n for n in nodes.values() if n.rn.is_leader(0))

        leader.read_index(b"early")
        # One round: batch opens at the current commit.
        rd = leader.ready(timeout=1)
        msgs = rd.messages if rd else []
        leader.advance()
        # Writes land AFTER the batch opened...
        leader.propose(b"w1")
        # ...then a second reader arrives.
        leader.read_index(b"late")
        served = {}
        for _ in range(40):
            for n in nodes.values():
                n.tick()
            for i, n in nodes.items():
                r2 = n.ready(timeout=0.05)
                if r2 is None:
                    continue
                for m in r2.messages:
                    nodes[m.to].step(m)
                for rs in r2.read_states:
                    served[rs.request_ctx] = rs.index
                n.advance()
            if b"early" in served and b"late" in served:
                break
        for m in msgs:
            pass  # first-round messages were intentionally dropped
        assert b"early" in served and b"late" in served
        # The late reader's index must cover the write proposed before
        # its request (commit advanced past the early batch's index).
        assert served[b"late"] >= served[b"early"]
        assert served[b"late"] >= leader.rn.latest_commit(0) - 1

    def test_node_read_index_on_follower_raises(self):
        from etcd_tpu.batched.node import BatchedNode, ProposalDroppedError

        nodes = {i: BatchedNode(i, [1, 2, 3], election_tick=4)
                 for i in (1, 2, 3)}
        TestNodeContract()._pump(nodes)
        follower = next(n for n in nodes.values()
                        if not n.rn.is_leader(0))
        with pytest.raises(ProposalDroppedError):
            follower.read_index(b"x")

    def test_node_transfer_via_follower_forwards(self):
        """transfer_leadership on a follower forwards to the leader
        (stepFollower MsgTransferLeader, raft.go:1457-1464)."""
        from etcd_tpu.batched.node import BatchedNode

        nodes = {i: BatchedNode(i, [1, 2, 3], election_tick=4)
                 for i in (1, 2, 3)}
        pump = TestNodeContract()._pump
        pump(nodes)
        leader_id = next(i for i, n in nodes.items() if n.rn.is_leader(0))
        follower_id = next(i for i in nodes if i != leader_id)
        # Ask the FOLLOWER to transfer leadership to itself.
        nodes[follower_id].transfer_leadership(leader_id, follower_id)
        pump(nodes, rounds=40,
             until=lambda rd: nodes[follower_id].rn.is_leader(0))
        assert nodes[follower_id].rn.is_leader(0)
