"""Persistent XLA compilation cache wiring.

The batched round programs are the most expensive artifacts this repo
builds, and every engine, member and bench process builds the same few.
JAX ships a persistent on-disk compilation cache keyed by the (program,
backend, flags) fingerprint; pointing every entry point at one
directory makes the second compile of an identical config a disk hit
instead of a recompile.

Where the cache lives is decided outside this program:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; this module
  sets no directory in code.
* unset — ``<checkout>/.jax_cache`` (git-ignored), a fixed path
  computed from this package's location. The path is part of what a
  later process must find again, so it never depends on ``~``, a temp
  name, a pid or the time.

Called by ``MultiRaftEngine``/``BatchedRawNode`` (idempotent) and by
the tools that log the directory.

Layout: one ``jit_<name>-<fingerprint>-cache`` blob per compiled
program plus an ``-atime`` sidecar (JAX's own format; safe to delete
wholesale — the next run recompiles and repopulates).
"""

from __future__ import annotations

import os

_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Idempotently switch JAX's persistent compilation cache on and
    return its directory (module docstring: the environment's, else the
    checkout's).

    Every program is cached regardless of size or compile time: the
    round kernels are worth the disk hit at every size (sweeps and
    restarted members re-enter identical configs constantly).
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if jax.config.jax_compilation_cache_dir != _CHECKOUT_DIR:
        os.makedirs(_CHECKOUT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_DIR)
    return _CHECKOUT_DIR
