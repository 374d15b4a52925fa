"""``lowered_text.py``: what a digest test of a lowered program says
when it fails (ISSUE 47; PERF.md section 7, "A digest that moved"). The
helper is shown its three cases on a small program: the pinned text
passes and lowers nothing again; a text with one more copy of a private
function passes with a warning, both texts written and the copy counted;
a text with a line of the program moved fails, and the assertion names
the line."""

import os
import warnings

import jax
import jax.numpy as jnp
import pytest

from . import lowered_text
from .lowered_text import (canonical, digest, first_difference,
                           held_to_the_pin)

AGAIN = ("tests.batched.test_lowered_text", "small", "3")


def small(n: str):
    """A program with private functions that call one another, as
    text; first something that is no text, as `_lowered` hands back."""
    @jax.jit
    def inner(x, y):
        return jnp.where(x > y, x, y)

    @jax.jit
    def outer(x):
        for _ in range(int(n)):
            x = inner(x, x * 2) + inner(x + 1, x)
        return x

    return (None, jax.jit(lambda x: outer(x) - outer(x + 1)).lower(
        jnp.arange(8, dtype=jnp.int32)).as_text())


def one_copy_more(text: str) -> str:
    """`text` as a process that lost a cached jaxpr prints it: a second
    copy of one private function under the next number, and one call
    site moved to it."""
    head, funcs, tail = lowered_text._functions(text)
    name, _private, body = [f for f in funcs if f[1]][-1]
    copy = "\n".join(body).replace(f"@{name}(", f"@{name}_77(", 1)
    out = text.replace("\n".join(body), "\n".join(body) + "\n" + copy, 1)
    at = out.index(f"call @{name}(")
    return out[:at] + out[at:].replace(f"call @{name}(",
                                       f"call @{name}_77(", 1)


def test_the_small_program_has_private_functions_that_call():
    text = small("3")[1]
    _head, funcs, _tail = lowered_text._functions(text)
    assert sum(1 for f in funcs if f[1]) >= 2 and len(funcs) >= 3
    assert canonical(text) == canonical(canonical(text))
    assert digest(small("3")[1]) == digest(text)


def test_the_pinned_text_passes_and_lowers_nothing_again(tmp_path,
                                                         monkeypatch):
    text = small("3")[1]
    monkeypatch.setattr(lowered_text, "lower_again", None)
    held_to_the_pin((text,), (digest(text),), tmp_path, AGAIN, "small")
    assert not os.listdir(tmp_path)


def test_a_copy_more_of_a_private_function_is_the_same_program(tmp_path):
    text = small("3")[1]
    more = one_copy_more(text)
    assert digest(more) != digest(text)
    assert len(lowered_text._functions(more)[1]) == len(
        lowered_text._functions(text)[1]) + 1
    assert canonical(more) == canonical(text)
    with pytest.warns(UserWarning, match="named by content the two are"):
        held_to_the_pin((more,), (digest(text),), tmp_path, AGAIN, "small")
    assert sorted(os.listdir(tmp_path)) == ["failed_0.txt", "fresh_0.txt"]
    with open(tmp_path / "fresh_0.txt") as f:
        assert f.read() == text


def test_a_line_of_the_program_moved_fails_and_is_named(tmp_path):
    text = small("3")[1]
    moved = text.replace("stablehlo.subtract", "stablehlo.add", 1)
    assert moved != text and canonical(moved) != canonical(text)
    line = first_difference(moved, text)
    assert "stablehlo.add" in line[1] and "stablehlo.subtract" in line[2]
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(AssertionError) as e:
            held_to_the_pin((moved,), (digest(text),), tmp_path, AGAIN,
                            "small")
    said = str(e.value)
    assert f"first differing line ({line[0]}," in said
    assert "they still differ" in said and str(tmp_path) in said
    assert "has the pinned digests" in said


def test_a_pin_that_no_fresh_process_meets_fails_whatever_the_copies(
        tmp_path):
    """The pin itself is not loosened: a text equal to the fresh one up
    to copies does not pass if the fresh one is not the pinned text."""
    more = one_copy_more(small("3")[1])
    with pytest.raises(AssertionError, match="does NOT have the pinned"):
        held_to_the_pin((more,), ("0" * 64,), tmp_path, AGAIN, "small")
