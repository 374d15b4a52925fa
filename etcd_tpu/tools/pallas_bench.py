"""Time the Pallas quorum/ring kernels against their XLA forms on the
current backend (run on TPU to decide the hot-path integration gate —
see pallas_kernels.py and BENCH_NOTES.md).

    python -m etcd_tpu.tools.pallas_bench [N] [R] [W]
"""

from __future__ import annotations

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, calls=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 65536 * 3
    r = int(sys.argv[2]) if len(sys.argv) > 2 else 3
    w = int(sys.argv[3]) if len(sys.argv) > 3 else 32

    from etcd_tpu.batched.kernels import (
        joint_committed,
        joint_vote_result,
        term_at,
    )
    from etcd_tpu.batched.pallas_kernels import (
        quorum_commit_vote,
        term_at_batch,
    )

    platform = jax.devices()[0].platform
    interpret = platform == "cpu"
    if interpret:
        # Interpret mode executes the kernel in Python per grid step —
        # CPU timings are meaningless; this is a smoke run only.
        n = min(n, 1024)
    calls = 2 if interpret else 20
    mode = ("interpret (smoke only, timings meaningless)" if interpret
            else "compiled")
    print(f"[{platform}] pallas mode: {mode}, N={n}", flush=True)
    rng = np.random.RandomState(0)
    match = jnp.asarray(rng.randint(0, 50, size=(n, r)), jnp.int32)
    voter = jnp.asarray(rng.rand(n, r) < 0.9)
    vout = jnp.asarray(rng.rand(n, r) < 0.3)
    joint = jnp.asarray(rng.rand(n) < 0.2)
    votes = jnp.asarray(rng.randint(-1, 2, size=(n, r)), jnp.int32)
    log = jnp.asarray(rng.randint(1, 9, size=(n, w)), jnp.int32)
    snapi = jnp.asarray(rng.randint(0, 100, size=n), jnp.int32)
    snapt = jnp.asarray(rng.randint(1, 9, size=n), jnp.int32)
    last = snapi + jnp.asarray(rng.randint(0, w, size=n), jnp.int32)
    idx = snapi + jnp.asarray(rng.randint(-2, w + 2, size=n), jnp.int32)

    xla_quorum = jax.jit(jax.vmap(joint_committed))
    xla_vote = jax.jit(jax.vmap(joint_vote_result))
    xla_term = jax.jit(jax.vmap(term_at))

    tq = _time(lambda: quorum_commit_vote(
        match, voter, vout, joint, votes, interpret=interpret),
        calls=calls)
    tx = _time(lambda: (xla_quorum(match, voter, vout, joint),
                        xla_vote(votes, voter, vout, joint)),
        calls=calls)
    print(f"[{platform}] quorum+vote N={n} R={r}: "
          f"pallas={tq*1e3:.3f}ms xla={tx*1e3:.3f}ms", flush=True)

    tp = _time(lambda: term_at_batch(
        log, snapi, snapt, last, idx, interpret=interpret),
        calls=calls)
    tx = _time(lambda: xla_term(log, snapi, snapt, last, idx),
               calls=calls)
    print(f"[{platform}] term_at N={n} W={w}: "
          f"pallas={tp*1e3:.3f}ms xla={tx*1e3:.3f}ms", flush=True)


if __name__ == "__main__":
    main()
