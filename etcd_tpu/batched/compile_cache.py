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

The same call makes JAX's own compile phases spans of the round-span
recorder (``obs/spans.py``), once a process: ``compile.trace`` (a
function traced to a jaxpr; one nests in another where a jitted
function calls a jitted function), ``compile.lower`` (the jaxpr
lowered to an MLIR module) and ``compile.backend`` (the module compiled
by XLA, or fetched from this cache: ``hit`` 1), each with the
program's name as ``fun_name`` and, as every span of a thread, under
the span that was open when JAX did the work: a ``compile.trace`` of
ten seconds is seen inside the ``engine.run_rounds`` that paid it.
Nothing but JAX's compile path calls the listener, so a window that
compiles nothing pays nothing.

Layout: one ``jit_<name>-<fingerprint>-cache`` blob per compiled
program plus an ``-atime`` sidecar (JAX's own format; safe to delete
wholesale — the next run recompiles and repopulates).
"""

from __future__ import annotations

import os
import threading
import time

_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


# JAX's duration events by the span each becomes (``jax/_src/
# dispatch.py``); all three carry ``fun_name``.
COMPILE_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    # Includes the fetch where the program was a hit of this cache.
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
# Sent, with no name, on the same thread just before the
# backend_compile_duration of a program the cache held, and not before
# one that was compiled (``jax/_src/compiler.py``).
_CACHE_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
_fetched = threading.local()
_listening = False


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == _CACHE_FETCH:
        _fetched.hit = True
        return
    name = COMPILE_SPANS.get(event)
    if name is None:
        return
    from ..obs import spans

    t1 = time.monotonic_ns()
    stats = {"fun_name": kw.get("fun_name", "?")}
    if name == "compile.backend":
        stats["hit"] = int(getattr(_fetched, "hit", False))
        _fetched.hit = False
    # End now, start the duration before: the event comes as the phase
    # ends, on the thread that ran it.
    spans.record(name, t1 - int(secs * 1e9), t1, **stats)


def listen_to_compiles() -> None:
    """Idempotently register the one listener of a process (JAX keeps
    its listeners for the life of the process)."""
    global _listening
    if _listening:
        return
    import jax.monitoring

    _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def enable_compile_cache() -> str:
    """Idempotently switch JAX's persistent compilation cache on and
    return its directory (module docstring: the environment's, else the
    checkout's).

    Every program is cached regardless of size or compile time: the
    round kernels are worth the disk hit at every size (sweeps and
    restarted members re-enter identical configs constantly).
    """
    import jax

    listen_to_compiles()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if jax.config.jax_compilation_cache_dir != _CHECKOUT_DIR:
        os.makedirs(_CHECKOUT_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_DIR)
    return _CHECKOUT_DIR
