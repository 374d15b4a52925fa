"""The native loader builds from the committed source, keyed by a hash
of that source: an unchanged source reuses the built library, a changed
one rebuilds, and a failed build raises with the compiler's words."""

import os

import pytest

from etcd_tpu import native

_SRC = 'extern "C" int answer() { return %d; }\n'


@pytest.fixture
def tree(tmp_path, monkeypatch):
    src, lib = tmp_path / "src", tmp_path / "lib"
    src.mkdir()
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_LIB", str(lib))
    return src, lib


def test_unchanged_source_reuses_changed_source_rebuilds(tree):
    src, lib = tree
    (src / "toy.cc").write_text(_SRC % 1)
    first = native._build("toy")
    built_at = os.stat(first).st_mtime_ns
    # A newer source timestamp with the same bytes is not a change.
    os.utime(src / "toy.cc", ns=(built_at + 10**9, built_at + 10**9))
    assert native._build("toy") == first
    assert os.stat(first).st_mtime_ns == built_at

    (src / "toy.cc").write_text(_SRC % 2)
    second = native._build("toy")
    assert second != first
    assert os.listdir(lib) == [os.path.basename(second)]


def test_failed_build_raises_with_compiler_stderr(tree):
    src, lib = tree
    (src / "toy.cc").write_text("this is not C++\n")
    with pytest.raises(RuntimeError, match="error"):
        native._build("toy")
    assert not list(lib.glob("*.so"))
