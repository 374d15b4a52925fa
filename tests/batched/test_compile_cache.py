"""Persistent XLA compilation cache wiring.

The expensive artifact is the closed-loop round scan; compile_cache.py
points every engine entry point at one on-disk cache so the second
build of an identical config is a disk hit. Where the cache lives is
decided outside the program: ``JAX_COMPILATION_CACHE_DIR`` when set
(JAX reads it itself, nothing sets a directory in code), else the fixed
``<checkout>/.jax_cache``. These tests pin that contract and the actual
cross-process behavior: a fresh process re-building the same config
must hit the cache (no new cache entries, faster build) rather than
recompile.
"""

import json
import os
import subprocess
import sys

import pytest

import etcd_tpu.batched.compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def restore_jax_cache_dir():
    """Leave jax's process-wide cache-dir config as the session had it."""
    import jax

    old_dir = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old_dir)


class TestWiring:
    def test_env_set_uses_it_and_sets_no_directory(
            self, restore_jax_cache_dir, monkeypatch, tmp_path):
        import jax

        env_dir = str(tmp_path / "from_env")
        # What `import jax` does with the variable in a fresh process
        # (TestCrossProcessWarmStart drives that for real).
        jax.config.update("jax_compilation_cache_dir", env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        updated = []
        real_update = jax.config.update
        monkeypatch.setattr(
            jax.config, "update",
            lambda k, v: (updated.append(k), real_update(k, v))[1])
        assert cc.enable_compile_cache() == env_dir
        assert "jax_compilation_cache_dir" not in updated
        assert jax.config.jax_compilation_cache_dir == env_dir

    def test_env_unset_is_the_checkout_dir(
            self, restore_jax_cache_dir, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert cc.enable_compile_cache() == want
        assert cc.enable_compile_cache() == want  # second call: no-op
        assert jax.config.jax_compilation_cache_dir == want
        assert os.path.isdir(want)

    def test_caches_every_program(self, restore_jax_cache_dir):
        import jax

        cc.enable_compile_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


_BUILD_SNIPPET = """
import json, sys, time
import jax
from etcd_tpu.batched import BatchedConfig, MultiRaftEngine

cfg = BatchedConfig(num_groups=4, num_replicas=3, window=8,
                    max_ents_per_msg=2, max_props_per_round=1,
                    election_timeout=1 << 20)
eng = MultiRaftEngine(cfg)  # enables the cache from the env
t0 = time.perf_counter()
eng.run_rounds(8, tick=False)  # compiles the closed-loop scan
jax.block_until_ready(eng.state.commit)
print(json.dumps({"compile_s": time.perf_counter() - t0}))
"""


class TestCrossProcessWarmStart:
    def test_second_process_hits_persistent_cache(self, tmp_path):
        """Cold process populates the cache; a warm process re-building
        the IDENTICAL config must add no new entries (every compile is
        a hit) and build faster — the property sweeps and restarted
        members lean on (tiny CPU programs here can't pin a ratio
        without flaking)."""
        cache = tmp_path / "xla"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache)

        def build():
            r = subprocess.run(
                [sys.executable, "-c", _BUILD_SNIPPET], env=env,
                cwd=REPO, capture_output=True, timeout=600)
            assert r.returncode == 0, r.stderr.decode()[-2000:]
            out = json.loads(r.stdout.decode().strip().splitlines()[-1])
            return out["compile_s"]

        cold_compile = build()
        entries = {f for f in os.listdir(cache) if f.endswith("-cache")}
        assert entries, "cold build wrote no persistent cache entries"

        warm_compile = build()
        entries2 = {f for f in os.listdir(cache) if f.endswith("-cache")}
        assert entries2 == entries, (
            "warm build recompiled: new cache entries "
            f"{entries2 - entries}")
        assert warm_compile < cold_compile, (
            f"warm dispatch {warm_compile:.2f}s not faster than cold "
            f"compile {cold_compile:.2f}s")
