"""chip_smoke.py is debugged here, not on chip time: without a TPU the
command refuses fast and prints no result, and its phase functions —
the same code ``main()`` runs at full size on the chip — pass their own
oracle comparisons at a tiny size on CPU. ``main()`` itself takes no
size."""

import json
import os
import subprocess
import sys
import time

import chip_smoke  # tests/conftest.py puts the repo root on sys.path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "platform='cpu'" in r.stderr
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            assert "ok" not in json.loads(line), r.stdout


def test_engine_phase_matches_oracles(monkeypatch):
    monkeypatch.setenv("ETCD_TPU_TRANSFER_GUARD", "disallow")
    out = chip_smoke.phase_engine(groups=8, calls=2)
    assert out["shadow_groups_equal"] == 8
    assert out["groups_equal_group0"] == 8
    assert out["layout"] == "minor"
    assert out["commit_min"] > 0


def test_served_phase_acks_reads_back_and_survives_restart(monkeypatch):
    monkeypatch.setenv("ETCD_TPU_TRANSFER_GUARD", "disallow")
    out = chip_smoke.phase_served(groups=8, keys_per_group=2, seed=3,
                                  sample_n=4)
    assert out["puts"] == 16
    assert out["linearizable_reads"] == 4
    assert out["reads_back"] == 16 * chip_smoke.MEMBERS
    assert out["kv_hash_groups"] == 8
    assert out["restart_reads_back"] == 4 * chip_smoke.MEMBERS
    assert all(n > 0 for n in out["wal_fsyncs"].values())
