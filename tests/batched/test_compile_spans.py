"""JAX's trace, lower and compile phases as spans of the round-span
recorder, by program and under the span that paid for them
(``compile_cache.listen_to_compiles``), and the span over a tiled
engine's abstract pre-trace (``engine.pretrace``): ISSUE 38.

Round-step programs (``conftest.py``): ``engine512k-r3of4`` at 8 groups
(``test_scan_replace.RP4``), a key already.
"""

import jax
import jax.numpy as jnp
import numpy as np

from etcd_tpu.batched import compile_cache
from etcd_tpu.obs import spans

from .test_scopes import engine_of

PHASES = ("compile.trace", "compile.lower", "compile.backend")


def named(snap, fun: str):
    """The compile spans of one program (``jit(f)`` is ``f``'s)."""
    return [s for s in snap if s.name in PHASES
            and s.stats["fun_name"] in (fun, f"jit({fun})")]


def test_a_compile_leaves_its_three_spans_under_the_span_that_paid():
    compile_cache.enable_compile_cache()

    def issue38_inner(x):
        return x * 3

    def issue38_outer(x):
        return jax.jit(issue38_inner)(x) + 1

    with spans.span("engine.test_compile", 0, 38, engine=-1) as paid:
        jax.jit(issue38_outer)(jnp.arange(8)).block_until_ready()
    snap = spans.snapshot()
    mine = named(snap, "issue38_outer")
    assert [s.name for s in mine] == list(PHASES)
    for s in mine:
        assert (s.parent, s.member, s.round) == (paid.seq, 0, 38)
        assert paid.t0 <= s.t0 <= s.t1 <= paid.t1
        assert s.cpu_ns == -1
    assert mine[2].stats["hit"] in (0, 1)  # the cache may hold it
    assert "hit" not in mine[0].stats and "hit" not in mine[1].stats
    # The callee is traced inside the caller's trace (an event of its
    # own, within the outer one's time) and is no program by itself.
    inner = named(snap, "issue38_inner")
    assert [s.name for s in inner] == ["compile.trace"]
    assert mine[0].t0 <= inner[0].t0 <= inner[0].t1 <= mine[0].t1
    # Warm, nothing is traced or compiled: no span more.
    with spans.span("engine.test_compile", 0, 39, engine=-1):
        jax.jit(issue38_outer)(jnp.arange(8)).block_until_ready()
    assert len(named(spans.snapshot(), "issue38_outer")) == 3


def test_registering_twice_records_once():
    compile_cache.listen_to_compiles()
    compile_cache.listen_to_compiles()
    compile_cache.enable_compile_cache()

    def issue38_once(x):
        return x - 2

    jax.jit(issue38_once)(jnp.arange(4)).block_until_ready()
    got = named(spans.snapshot(), "issue38_once")
    assert [s.name for s in got] == list(PHASES)
    # Under no span: top level, member 0, no round.
    assert all((s.parent, s.member, s.round) == (-1, 0, -1) for s in got)


def test_a_fetch_marks_the_backend_span_that_follows_on_its_thread():
    """JAX sends the cache's retrieval time, with no name, just before
    the backend duration of a program it fetched, and nothing before
    one it compiled."""
    backend = "/jax/core/compile/backend_compile_duration"
    compile_cache._on_duration(compile_cache._CACHE_FETCH, 0.25)
    compile_cache._on_duration(backend, 0.5, fun_name="jit(issue38_hit)")
    compile_cache._on_duration(backend, 2.0, fun_name="jit(issue38_miss)")
    compile_cache._on_duration("/jax/some/other_duration", 1.0)
    snap = spans.snapshot()
    (hit,), (miss,) = named(snap, "issue38_hit"), named(snap, "issue38_miss")
    assert hit.stats["hit"] == 1 and miss.stats["hit"] == 0
    assert hit.t1 - hit.t0 == 500_000_000
    assert miss.t1 - miss.t0 == 2_000_000_000
    assert not [s for s in snap if s.name not in PHASES
                and s.name.startswith("compile.")]


def test_record_under_a_span_and_with_a_member_of_its_own():
    rec = spans.Recorder(slots=16)
    with rec.span("member.round", 2, 7) as sp:
        rec.record("compile.trace", 10, 20, fun_name="f")
        rec.record("member.ready_q", 1, 2, 3, 9)
    rec.record("compile.trace", 30, 40, fun_name="g")
    got = {(s.name, s.t0): s for s in rec.snapshot()}
    under = got["compile.trace", 10]
    assert (under.parent, under.member, under.round) == (sp.seq, 2, 7)
    queued = got["member.ready_q", 1]
    assert (queued.parent, queued.member, queued.round) == (-1, 3, 9)
    bare = got["compile.trace", 30]
    assert (bare.parent, bare.member, bare.round) == (-1, 0, -1)


def test_the_pretrace_is_a_span_once_a_traced_program(monkeypatch):
    """T > 1: the eager round's program and each scan program trace one
    tile's round abstractly first, under the call that traces them; a
    call that finds its program traced records none, and the span
    numbers no call."""
    eng = engine_of("engine512k-r3of4", monkeypatch)
    assert eng._tiles == 2
    cfg = eng.cfg
    r = cfg.num_replicas
    eng.campaign(np.arange(cfg.num_groups) * r)   # call 1: traces _round
    eng.step_round()                              # call 2: finds it traced
    eng.run_rounds(4)                             # call 3: traces the scan
    eng.run_rounds(4)                             # call 4
    iso = np.zeros((4, r), bool)
    eng.run_rounds(4, isolate=iso)                # call 5: a second program
    assert eng._calls == 6
    mine = [s for s in spans.snapshot() if s.name.startswith("engine.")
            and s.stats["engine"] == eng._serial]
    calls = {s.round: s for s in mine if s.name != "engine.pretrace"}
    assert sorted(calls) == [0, 1, 2, 3, 4, 5]
    pre = [s for s in mine if s.name == "engine.pretrace"]
    assert [s.round for s in pre] == [1, 3, 5]
    for s in pre:
        paid = calls[s.round]
        assert s.parent == paid.seq and s.member == 0
        assert paid.t0 <= s.t0 <= s.t1 <= paid.t1
        assert paid.name == ("engine.step_round" if s.round == 1
                             else "engine.run_rounds")
    # The loops' traces find the round's jaxpr cached by a pre-trace
    # (or by an earlier test of this process): no trace of
    # `jit(step_round)` in earnest, seconds here, stands outside a
    # pre-trace. (The eager round's jit bears the same name: its trace
    # is the one that holds a pre-trace.)
    within = lambda a, b: b.t0 <= a.t0 and a.t1 <= b.t1  # noqa: E731
    rounds = [s for s in named(spans.snapshot(), "step_round")
              if s.name == "compile.trace"
              and any(within(s, c) for c in calls.values())]
    outer = [s for s in rounds if any(within(p, s) for p in pre)]
    assert len(outer) == 1 and len(rounds) > 1
    outside = [s for s in rounds if s not in outer
               and not any(within(s, p) for p in pre)]
    assert all(s.t1 - s.t0 < 250_000_000 for s in outside)


def test_one_tile_traces_no_pretrace(monkeypatch):
    eng = engine_of("engine64k-r3", monkeypatch)
    assert eng._tiles == 1
    eng.step_round()
    eng.run_rounds(4)
    assert not [s for s in spans.snapshot() if s.name == "engine.pretrace"
                and s.stats["engine"] == eng._serial]
