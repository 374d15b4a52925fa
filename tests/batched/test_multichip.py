"""The CPU multichip dry-run helper must stay green.

Covers ``__graft_entry__.dryrun_multichip`` — a CPU test helper over
virtual devices; the real-chip counterpart is ``chip_smoke.py``'s
four-chip phase:

- the sharded impl itself, in-process (conftest forces a virtual
  8-device CPU platform);
- the delegating parent from a plain one-device CPU process: the child
  must get the virtual 8-device mesh and succeed.
"""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles():
    fn, args = graft.entry()
    out_state, out_inbox = jax.jit(fn)(*args)
    jax.block_until_ready(out_state.term)
    # The campaigned instance became leader of its single-vote round? No:
    # R=3, so campaign only emits vote requests; terms must have advanced.
    assert int(out_state.term[0]) >= 1


def test_dryrun_inprocess_8_devices():
    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    graft._dryrun_impl(8)


def test_dryrun_subprocess_fallback():
    """A plain CPU outer process with one device: the delegating path
    must force the virtual 8-device mesh in the child and succeed."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = ""  # no virtual devices in the outer process
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("GRAFT_DRYRUN_CHILD", None)
    proc = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sys; sys.path.insert(0, sys.argv[1]);"
            "import __graft_entry__ as g;"
            "g.dryrun_multichip(8);"
            "print('outer ok')",
            REPO,
        ],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "outer ok" in proc.stdout


def test_hosted_put_roundtrip_on_mesh(tmp_path):
    """A 3-member hosted cluster whose members each shard their [G,...]
    device state over the virtual 8-device mesh: puts round-trip
    through WAL + transport + apply with the sharded step (review round
    4, task 3: 'sharded engine under the hosting layer')."""
    from etcd_tpu.batched.hosting import MultiRaftCluster

    from .test_hosting import wait_until

    g = 64  # divides 8
    c = MultiRaftCluster(str(tmp_path), num_members=3, num_groups=g,
                         mesh_devices=8)
    try:
        # Members' states really span the mesh.
        m1 = c.members[1]
        shards = m1.rn.state.term.sharding
        assert len(shards.device_set) == 8, shards
        leads = c.wait_leaders()
        assert (leads > 0).all()
        for grp in range(0, g, 7):
            c.put(grp, b"mk", b"mv%d" % grp)
        wait_until(
            lambda: all(
                m.get(grp, b"mk") == b"mv%d" % grp
                for m in c.members.values() for grp in range(0, g, 7)
            ),
            timeout=30, msg="sharded hosted puts converge")
    finally:
        c.stop()


@pytest.mark.slow
def test_sharded_vs_unsharded_differential_g4096(tmp_path):
    """Sharded (8-device mesh) and unsharded members at G=4096 must
    produce identical applied KV state for the same workload, end to
    end through WAL + transport + apply (review round 4, task 3)."""
    from etcd_tpu.batched.hosting import MultiRaftCluster
    from etcd_tpu.batched.state import BatchedConfig

    g = 4096
    cfg = BatchedConfig(
        num_groups=g, num_replicas=3, window=32, max_ents_per_msg=4,
        max_props_per_round=4, election_timeout=10, heartbeat_timeout=1,
        pre_vote=True, check_quorum=True, auto_compact=True)
    sample = list(range(0, g, 173)) + [g - 1]
    results = {}
    for label, mesh in (("sharded", 8), ("unsharded", 0)):
        c = MultiRaftCluster(
            str(tmp_path / label), num_members=3, num_groups=g, cfg=cfg,
            mesh_devices=mesh)
        try:
            c.wait_leaders(timeout=180)
            for grp in sample:
                c.put(grp, b"dk", b"dv%d" % grp, timeout=60.0)
            from .test_hosting import wait_until

            wait_until(
                lambda: all(
                    m.get(grp, b"dk") == b"dv%d" % grp
                    for m in c.members.values() for grp in sample
                ),
                timeout=120, msg=f"{label} puts converge")
            results[label] = {
                grp: {mid: dict(m.kvs[grp].data)
                      for mid, m in c.members.items()}
                for grp in sample
            }
        finally:
            c.stop()
    assert results["sharded"] == results["unsharded"]
