"""Every file the two documents a session reads first point at is in
the tree: ``README.md`` and ``PERF.md`` name files in backticks, and a
deletion that leaves such a pointer behind fails here. ``ROADMAP.md``
and ``CHANGES.md`` are plan and history, and are not held to it.

A backticked span is a file's name when it ends in one of ``EXTS`` (a
``:line`` suffix allowed). It must resolve from the root of the
checkout, or be the tail of a file's path in the tree (the documents
write ``readers/lanes.py`` for ``benchmark/readers/lanes.py``); ``*``
and ``<...>`` match anything. A file under ``artifacts/`` that is not
there is written at run time: then some source file must still name it,
so the pointer dies with its writer.

And no line of either outgrows what a session can read, grep or diff:
``PERF.md`` once held a line of 26 KB under a limit that counted lines.

Pure file reads — no jax import.
"""

import fnmatch
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# The reference's own files (``raft.go``, ``testdata/*.txt``) are named
# in the documents too; none of them has one of these endings.
EXTS = ("py", "md", "json", "jsonl", "sh", "yml", "toml", "cc")
NAME = re.compile(
    r"`([\w.\-/<>*]+\.(?:%s))(?::[\d,\- ]+)?`" % "|".join(EXTS))
# A read of a file by line stops at 2,000 characters of a line.
LINE_LIMIT = 2000
NOT_SOURCE = {".git", "__pycache__", ".jax_cache", ".pytest_cache",
              ".hypothesis", ".scratch", "chiprun_out", "artifacts", "lib"}


def tree():
    files = []
    for d, subdirs, names in os.walk(REPO):
        subdirs[:] = [s for s in subdirs if s not in NOT_SOURCE]
        rel = os.path.relpath(d, REPO)
        files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return files


def written_by_something(path, files):
    """``artifacts/flightrec_*.json``: the fixed part of the name stands
    in a source file of the tree."""
    stem = os.path.basename(path).split("*")[0].rstrip("_")
    for f in files:
        if stem and f.endswith((".py", ".sh", ".yml")):
            with open(os.path.join(REPO, f), errors="replace") as src:
                if stem in src.read():
                    return True
    return False


@pytest.mark.parametrize("doc", ["README.md", "PERF.md"])
def test_every_file_the_document_names_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        named = sorted(set(NAME.findall(f.read())))
    assert named, f"{doc} names no file: the pattern has rotted"
    files = tree()
    gone = []
    for path in named:
        pattern = re.sub(r"<[^>]*>", "*", path)
        if os.path.exists(os.path.join(REPO, path)):
            continue
        if any(fnmatch.fnmatch(f, pattern) or fnmatch.fnmatch(f, "*/" + pattern)
               for f in files):
            continue
        if path.startswith("artifacts/") and written_by_something(path, files):
            continue
        gone.append(path)
    assert not gone, f"{doc} points at files that are not in the tree: {gone}"


@pytest.mark.parametrize("doc", ["README.md", "PERF.md"])
def test_no_line_of_the_document_outgrows_a_read(doc):
    with open(os.path.join(REPO, doc)) as f:
        sizes = {n: len(line.rstrip("\n")) for n, line in enumerate(f, 1)}
    long = {n: size for n, size in sizes.items() if size > LINE_LIMIT}
    assert not long, f"{doc}: line: characters over {LINE_LIMIT}: {long}"
