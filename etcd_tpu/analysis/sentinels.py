"""Runtime sentinels for the batched hot path: transfer guard +
recompile budget.

Transfer guard
--------------
``round_guard()`` returns ``jax.transfer_guard(mode)`` when
``ETCD_TPU_TRANSFER_GUARD`` is set (tests/batched/conftest.py and the
benches set ``disallow``), else a no-op context. The engine/rawnode
wrap exactly the *warm* device dispatch of the round program in it, so
any implicit transfer sneaking into the steady-state loop — an eager
scalar op, a stray ``jnp.zeros``, a concretized tracer — is a hard
error instead of a silent per-round sync (the BENCH r4 675M/s artifact
class). Two deliberate scope limits, measured on this jax build:

* compilation itself transfers host constants, so a cold program must
  be dispatched once unguarded — callers use ``warm_guard(key)`` which
  guards every call after the first per program/static-arg key;
* on CPU, array transfers are zero-copy aliases and do NOT trip the
  guard (scalar transfers do) — the AST side (jitlint's sync-in-loop)
  covers the class the runtime guard can't see on CPU. On the TPU they
  do trip it: ``chip_smoke.py`` runs the engine and the served path
  under ``disallow`` there (PR 21's run found the warm dispatch clean).

Recompile sentinel
------------------
``step._step_round_jit`` notes one key per distinct round-step config
via ``note_compile_key``; ``distinct_shapes("round_step")`` is then the
number of round programs built this session. tests/batched/conftest.py
declares the tier-1 shape budget and fails the session when new configs
exceed it — the ~15s tier-1 margin dies by one unnoticed compile at a
time. ``CompileBudget`` additionally watches live jit wrappers via
``_cache_size()`` for genuine cache-miss counting (new static args /
new input shapes on the same wrapper).
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Dict, Optional, Set

_TRANSFER_GUARD_ENV = "ETCD_TPU_TRANSFER_GUARD"

_lock = threading.Lock()
_compile_keys: Dict[str, Set[str]] = {}
_warm_keys: Set[str] = set()


def transfer_guard_mode() -> str:
    """'' (off) or a jax transfer-guard level ('disallow', 'log', ...)."""
    return os.environ.get(_TRANSFER_GUARD_ENV, "")


def round_guard():
    """Context manager for the round dispatch: jax.transfer_guard(mode)
    when enabled, no-op otherwise. Only wrap already-compiled dispatch
    with all-device args — compilation transfers host constants."""
    mode = transfer_guard_mode()
    if not mode:
        return contextlib.nullcontext()
    import jax

    return jax.transfer_guard(mode)


@contextlib.contextmanager
def warm_guard(key: str):
    """round_guard() for every call after the first with this `key`.

    The first dispatch of a (program, static-args) pair compiles, and
    compilation legitimately transfers host constants; keying warmth by
    (program, statics) keeps recompiles unguarded too, while the
    steady-state loop runs fully fenced."""
    mode = transfer_guard_mode()
    if not mode:
        yield
        return
    with _lock:
        warm = key in _warm_keys
    if warm:
        import jax

        with jax.transfer_guard(mode):
            yield
    else:
        yield
        with _lock:
            _warm_keys.add(key)


# -----------------------------------------------------------------------------
# Recompile sentinel
# -----------------------------------------------------------------------------


class RecompileBudgetExceeded(RuntimeError):
    pass


def note_compile_key(program: str, key: str) -> None:
    """Record that `program` built a trace for shape/config `key`
    (called from the build path, e.g. step._step_round_jit — once per
    distinct config thanks to its lru_cache)."""
    with _lock:
        _compile_keys.setdefault(program, set()).add(key)


def distinct_shapes(program: Optional[str] = None) -> int:
    with _lock:
        if program is not None:
            return len(_compile_keys.get(program, ()))
        return sum(len(v) for v in _compile_keys.values())


def compile_keys(program: str) -> Set[str]:
    with _lock:
        return set(_compile_keys.get(program, ()))


def reset_compile_tracking() -> None:
    with _lock:
        _compile_keys.clear()
        _warm_keys.clear()


def jit_cache_size(jitted) -> int:
    """Entries in a jax.jit wrapper's trace cache (one per distinct
    (shapes, dtypes, static args) signature)."""
    return int(jitted._cache_size())


class CompileBudget:
    """Counts jit cache misses across tracked wrappers against a hard
    limit.

        budget = CompileBudget(limit=1)
        budget.track("closed_loop", eng._closed_loop)
        ...drive the engine...
        budget.check()   # raises RecompileBudgetExceeded when over

    A miss is a new entry in a tracked wrapper's trace cache: a new
    static-arg value (e.g. a new `rounds`) or a new input shape. The
    declared tier-1 budget lives in tests/batched/conftest.py.
    """

    def __init__(self, limit: int):
        self.limit = int(limit)
        self._baseline: Dict[str, int] = {}
        self._fns: Dict[str, object] = {}

    def track(self, name: str, jitted) -> "CompileBudget":
        self._fns[name] = jitted
        self._baseline[name] = jit_cache_size(jitted)
        return self

    def misses(self) -> int:
        return sum(max(n, 0) for n in self.report().values())

    def report(self) -> Dict[str, int]:
        return {
            name: jit_cache_size(fn) - self._baseline[name]
            for name, fn in self._fns.items()
        }

    def check(self) -> int:
        m = self.misses()
        if m > self.limit:
            raise RecompileBudgetExceeded(
                f"jit cache misses {m} > declared budget {self.limit} "
                f"(per-wrapper: {self.report()}); a new static arg or "
                "input shape recompiled the hot program — make it "
                "conscious (bump the budget) or make it go away")
        return m
