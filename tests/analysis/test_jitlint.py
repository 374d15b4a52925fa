"""jitlint analyzer tests (ISSUE 7): one known violation per rule,
asserting exact rule IDs and line numbers, plus waiver semantics,
jit-reachability propagation, and the repo gate itself.

Pure AST — no jax import, no backend, milliseconds per test.
"""

import os
import textwrap

from etcd_tpu.analysis.jitlint import RULES, lint_paths, lint_source

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def run(src, path="fx.py", **kw):
    return lint_source(textwrap.dedent(src), path, **kw)


def hits(findings, waived=False):
    return {(f.line, f.rule) for f in findings if f.waived == waived}


# -----------------------------------------------------------------------------
# One violation per rule, exact (line, rule)
# -----------------------------------------------------------------------------


def test_tracer_branch():
    fs = run("""\
    import jax


    @jax.jit
    def f(x):
        if x > 0:
            x = x + 1
        while x.sum() > 0:
            x = x - 1
        y = 1 if x else 2
        ok = (x > 0) and (x < 9)
        for v in x:
            y += v
        return x, y, ok
    """)
    assert hits(fs) == {
        (6, "tracer-branch"),   # if on tracer
        (8, "tracer-branch"),   # while on tracer
        (10, "tracer-branch"),  # ternary on tracer
        (11, "tracer-branch"),  # and/or on tracer
        (12, "tracer-branch"),  # iteration over tracer
    }


def test_host_sync_in_jit():
    fs = run("""\
    import jax
    import numpy as np


    @jax.jit
    def f(x):
        a = float(x)
        b = x.item()
        c = np.asarray(x)
        d = x.tolist()
        return a, b, c, d
    """)
    assert hits(fs) == {
        (7, "host-sync-in-jit"),
        (8, "host-sync-in-jit"),
        (9, "host-sync-in-jit"),
        (10, "host-sync-in-jit"),
    }


def test_host_sync_requires_device_value():
    # np.asarray on host data at trace time is legal and common.
    fs = run("""\
    import jax
    import numpy as np

    TABLE = [1, 2, 3]


    @jax.jit
    def f(x):
        t = np.asarray(TABLE)
        return x + t.sum()
    """)
    assert hits(fs) == set()


def test_narrow_lane_arith():
    fs = run("""\
    import jax
    import jax.numpy as jnp


    @jax.jit
    def f(x):
        nar = x.astype(jnp.int8)
        bad = nar + 1
        ok = nar.astype(jnp.int32) + 1
        return bad, ok
    """)
    assert hits(fs) == {(8, "narrow-lane-arith")}


def test_narrow_lane_widen_at_entry_contract():
    # A jit ROOT taking BatchedState must not read a narrow lane before
    # widen_state; after widening, access is clean.
    fs = run("""\
    import jax


    @jax.jit
    def root(st: BatchedState, tick):
        early = st.role
        st = widen_state(st)
        late = st.role
        return early, late
    """)
    assert hits(fs) == {(6, "narrow-lane-arith")}


def test_donated_use():
    fs = run("""\
    import jax

    def helper(v):
        return v

    h = jax.jit(helper, donate_argnums=(0,))


    def drive(buf):
        out = h(buf)
        return buf + out


    def drive_rebound(buf):
        buf = h(buf)
        return buf + 1
    """)
    assert hits(fs) == {(11, "donated-use")}


def test_impure_jit():
    fs = run("""\
    import time
    import jax
    import numpy as np


    @jax.jit
    def f(x):
        t = time.time()
        r = np.random.rand()
        return x + t + r
    """)
    assert hits(fs) == {(8, "impure-jit"), (9, "impure-jit")}


def test_dict_order_static():
    fs = run("""\
    import jax

    D = {"b": 1, "a": 2}


    def f(x, names):
        return x

    g = jax.jit(f, static_argnames=tuple(D.keys()))
    h = jax.jit(f, static_argnames=tuple(sorted(D.keys())))
    """)
    assert hits(fs) == {(9, "dict-order-static")}


def test_sync_in_loop():
    fs = run("""\
    import jax
    import numpy as np


    def host_collect(rows):
        out = []
        for r in rows:
            out.append(np.asarray(r))
        bulk = np.asarray(rows)
        return out, bulk
    """)
    assert hits(fs) == {(8, "sync-in-loop")}


def test_sync_in_loop_only_in_jax_modules():
    # The same loop in a numpy-only module (e.g. telemetry.py, the
    # msgblock codec) is host-pure by construction: no finding.
    fs = run("""\
    import numpy as np


    def host_collect(rows):
        return [np.asarray(r) for r in rows] or [
            np.asarray(r) for r in rows]


    def loop_collect(rows):
        out = []
        for r in rows:
            out.append(np.asarray(r))
        return out
    """)
    assert hits(fs) == set()


# -----------------------------------------------------------------------------
# Waivers
# -----------------------------------------------------------------------------


def test_waived_finding_suppressed_and_reported_waived():
    fs = run("""\
    import jax
    import numpy as np


    def host(rows):
        for r in rows:
            x = np.asarray(r)  # jitlint: waive(sync-in-loop) -- test fixture reason
        return x
    """)
    assert hits(fs) == set()
    assert hits(fs, waived=True) == {(7, "sync-in-loop")}
    (w,) = [f for f in fs if f.waived]
    assert w.reason == "test fixture reason"


def test_waiver_on_preceding_comment_line():
    fs = run("""\
    import jax
    import numpy as np


    def host(rows):
        for r in rows:
            # jitlint: waive(sync-in-loop) -- standalone pragma form
            x = np.asarray(r)
        return x
    """)
    assert hits(fs) == set()
    assert hits(fs, waived=True) == {(8, "sync-in-loop")}


def test_waiver_without_reason_is_malformed_and_inert():
    fs = run("""\
    import jax
    import numpy as np


    def host(rows):
        for r in rows:
            x = np.asarray(r)  # jitlint: waive(sync-in-loop)
        return x
    """)
    assert (7, "sync-in-loop") in hits(fs)  # NOT suppressed
    assert (7, "waiver-malformed") in hits(fs)


def test_unused_waiver_is_a_finding():
    fs = run("""\
    import jax


    def clean():
        return 1  # jitlint: waive(sync-in-loop) -- stale pragma
    """)
    assert hits(fs) == {(5, "waiver-unused")}


def test_unknown_rule_waiver_is_malformed():
    fs = run("""\
    import jax


    def clean():
        return 1  # jitlint: waive(no-such-rule) -- whatever
    """)
    assert (5, "waiver-malformed") in hits(fs)


# -----------------------------------------------------------------------------
# Reachability
# -----------------------------------------------------------------------------


def test_reachability_propagates_through_helpers():
    fs = run("""\
    import jax


    @jax.jit
    def root(x):
        return helper(x)


    def helper(v):
        if v > 0:
            return v
        return -v


    def host_only(v):
        if v > 0:
            return float(v)
        return v
    """)
    # helper is jit-reachable -> flagged; host_only is not.
    assert hits(fs) == {(10, "tracer-branch")}


def test_reachability_crosses_modules_via_imports():
    kernels = """\
    def kern(v):
        if v > 0:
            return v
        return -v
    """
    fs = run("""\
    import jax
    from kernels import kern


    @jax.jit
    def root(x):
        return kern(x)
    """, extra_modules={"kernels": textwrap.dedent(kernels)})
    # The finding lands in the other module, so this file is clean —
    # and linting the pair together must flag kernels.py line 2.
    assert hits(fs) == set()
    from etcd_tpu.analysis.jitlint import _collect_module, lint_modules
    main = _collect_module("main.py", textwrap.dedent("""\
    import jax
    from kernels import kern


    @jax.jit
    def root(x):
        return kern(x)
    """))
    kmod = _collect_module("kernels.py", textwrap.dedent(kernels))
    all_f = lint_modules({m.path: m for m in (main, kmod)})
    assert {(f.path, f.line, f.rule) for f in all_f} == {
        ("kernels.py", 2, "tracer-branch")}


def test_scan_body_and_vmapped_fn_are_roots():
    fs = run("""\
    import jax


    def outer(x0):
        def body(c, _):
            if c > 0:
                c = c - 1
            return c, None
        c, _ = jax.lax.scan(body, x0, None, length=4)
        return jax.vmap(per_row)(c)


    def per_row(r):
        return r.item()
    """)
    assert hits(fs) == {(6, "tracer-branch"), (14, "host-sync-in-jit")}


def test_static_annotated_params_are_not_tracers():
    fs = run("""\
    import jax


    @jax.jit
    def f(x, pre: bool, n: int, cfg):
        if pre:
            x = x + n
        if cfg.flag:
            x = x - 1
        return x
    """)
    assert hits(fs) == set()


# -----------------------------------------------------------------------------
# The repo gate: the batched hot path must be clean (this IS the
# acceptance criterion, pinned as a test so it cannot rot)
# -----------------------------------------------------------------------------


def test_repo_batched_hot_path_is_clean():
    findings = lint_paths([os.path.join(REPO, "etcd_tpu", "batched")])
    unwaived = [f.format() for f in findings if not f.waived]
    assert unwaived == [], (
        "jitlint findings in etcd_tpu/batched/ — fix or waive with a "
        "reasoned pragma:\n" + "\n".join(unwaived))
    # The waivers that exist must all carry reasons (enforced by the
    # parser, asserted here as the contract).
    for f in findings:
        if f.waived:
            assert f.reason.strip()


def test_repo_analysis_and_bench_scope_is_clean():
    findings = lint_paths([
        os.path.join(REPO, "etcd_tpu", "analysis"),
        os.path.join(REPO, "etcd_tpu", "tools"),
        os.path.join(REPO, "tools"),
        os.path.join(REPO, "bench.py"),
        os.path.join(REPO, "chip_smoke.py"),
    ])
    unwaived = [f.format() for f in findings if not f.waived]
    assert unwaived == [], "\n".join(unwaived)


def test_bad_path_fails_the_gate_loudly():
    # A typo'd directory must raise, not lint zero files and pass —
    # the gate going silently vacuous is the worst failure mode a
    # lint gate has.
    import pytest

    from etcd_tpu.analysis.jitlint import collect_files

    with pytest.raises(FileNotFoundError):
        collect_files([os.path.join(REPO, "etcd_tpu", "no_such_dir")])
    with pytest.raises(FileNotFoundError):
        lint_paths(["no/such/file.py"])


def test_rule_catalog_documented():
    # Every rule the engine can emit is in the catalog the CLI prints.
    fs = run("""\
    import jax
    import numpy as np


    @jax.jit
    def f(x):
        return float(x)
    """)
    for f in fs:
        assert f.rule in RULES
