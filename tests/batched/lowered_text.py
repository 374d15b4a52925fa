"""What the digest tests of a lowered program say when they fail.

A pinned sha256 says that a text moved and not where. This family's
digests (``test_scan_replace.py::test_with_the_new_fields_off_the_round_
is_the_parents_text``) have moved in whole tier-1 runs and not alone
(PERF.md section 7, "A digest that moved"): the text holds some hundred
private copies of ``jnp.where``'s ``_where`` and its kin, which JAX
shares by the identity of a cached jaxpr; a process whose cache has let
one go prints one function more (``@_where_113``), numbered by when it
was made. `held_to_the_pin` keeps the pin as it is and, where the
digest differs, lowers the same program again in a fresh process, writes
both texts into the test's directory, and puts the first differing line
into the assertion. Two texts that differ only in such copies are one
program: `canonical` names every private function by its content
(callees first), drops the duplicates and orders what is left, and the
test passes, with a warning, if the fresh text has the pinned digest and
the canonical texts are equal. Nothing else is forgiven: a line of the
program that moved moves the canonical text too.
"""

import hashlib
import importlib
import os
import re
import subprocess
import sys
import warnings

_FUNC = re.compile(r"^  func\.func (private |public )?@([\w.$]+)\(")
_REF = re.compile(r"@([A-Za-z_][\w.$]*)")


def digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def _functions(text: str):
    """(lines before the first function, [(name, private, lines)],
    lines after the last): a module's top-level functions."""
    lines = text.split("\n")
    head, funcs, tail, cur = [], [], [], None
    for ln in lines:
        m = _FUNC.match(ln) if cur is None else None
        if m:
            cur = (m.group(2), m.group(1) == "private ", [ln])
        elif cur is not None:
            cur[2].append(ln)
            if ln == "  }":
                funcs.append(cur)
                cur = None
        elif funcs:
            tail.append(ln)
        else:
            head.append(ln)
    assert cur is None, "a function of the text does not end"
    return head, funcs, tail


def canonical(text: str) -> str:
    """`text` with every private function named by its content, the
    callees' names first, one copy of each, in the order of the names;
    the public functions stay where they are."""
    head, funcs, tail = _functions(text)
    private = {name: "\n".join(body) for name, is_p, body in funcs if is_p}
    named = {}
    while len(named) < len(private):
        before = len(named)
        for name, body in private.items():
            if name in named:
                continue
            calls = set(_REF.findall(body)) & set(private) - {name}
            if calls <= set(named):
                body = _REF.sub(
                    lambda m: "@" + ("SELF" if m.group(1) == name
                                     else named.get(m.group(1), m.group(1))),
                    body)
                named[name] = "p_" + digest(body)[:16]
        assert len(named) > before, "private functions that call in a ring"

    def rename(body: str) -> str:
        return _REF.sub(lambda m: "@" + named.get(m.group(1), m.group(1)),
                        body)

    kept = {}
    for name, is_p, body in funcs:
        if is_p:
            kept[named[name]] = rename("\n".join(body))
    out = head + [rename("\n".join(body)) for _n, is_p, body in funcs
                  if not is_p] + [kept[k] for k in sorted(kept)] + tail
    return "\n".join(out)


def first_difference(got: str, want: str):
    """(line number from 1, got's line, want's line) of the first line
    in which two texts differ, a missing line as ''; None if equal."""
    a, b = got.split("\n"), want.split("\n")
    for i in range(max(len(a), len(b))):
        x, y = (a[i] if i < len(a) else ""), (b[i] if i < len(b) else "")
        if x != y:
            return i + 1, x, y
    return None


def lower_again(module: str, function: str, arg: str, outdir: str):
    """`module.function(arg)`'s texts from a fresh process (its strings
    only: a configuration or an engine it hands back first is left
    out), written to ``outdir/fresh_<i>.txt`` and read back."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    subprocess.run(
        [sys.executable, "-m", "tests.batched.lowered_text", module,
         function, arg, outdir],
        cwd=root, check=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    texts, i = [], 0
    while os.path.exists(os.path.join(outdir, f"fresh_{i}.txt")):
        with open(os.path.join(outdir, f"fresh_{i}.txt")) as f:
            texts.append(f.read())
        i += 1
    return texts


def held_to_the_pin(texts, pinned, outdir, again, what: str) -> None:
    """Each of `texts` has the sha256 `pinned` gives it, or the test
    fails saying what differed. `again` is ``(module, function, arg)``
    for `lower_again`, `outdir` the test's own directory, `what` the
    assertion's first words."""
    got = tuple(digest(t) for t in texts)
    if got == tuple(pinned):
        return
    outdir = str(outdir)
    for i, t in enumerate(texts):
        with open(os.path.join(outdir, f"failed_{i}.txt"), "w") as f:
            f.write(t)
    fresh = lower_again(*again, outdir)
    fresh_ok = tuple(digest(t) for t in fresh) == tuple(pinned)
    notes, same_program = [], fresh_ok
    for i, (t, f) in enumerate(zip(texts, fresh)):
        if digest(t) == pinned[i]:
            continue
        line = first_difference(t, f)
        canon = first_difference(canonical(t), canonical(f))
        same_program &= canon is None
        notes.append(
            f"text {i}: {len(_functions(t)[1])} functions for "
            f"{len(_functions(f)[1])} in a fresh process; first differing "
            f"line {line}; with the private functions named by content "
            + ("the two are equal" if canon is None
               else f"they still differ, first at {canon}"))
    message = (
        f"{what}: digests {got} for {tuple(pinned)}; the same program "
        f"lowered in a fresh process "
        f"{'has' if fresh_ok else 'does NOT have'} the pinned digests; "
        f"both texts are in {outdir} (failed_<i>.txt, fresh_<i>.txt); "
        + "; ".join(notes))
    assert same_program, message
    warnings.warn(message + " -- the copies are JAX's, the program is the "
                  "pinned one", stacklevel=2)


if __name__ == "__main__":
    mod, fn, arg, out = sys.argv[1:5]
    result = getattr(importlib.import_module(mod), fn)(arg)
    for i, text in enumerate(t for t in result if isinstance(t, str)):
        with open(os.path.join(out, f"fresh_{i}.txt"), "w") as f:
            f.write(text)
