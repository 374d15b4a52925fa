"""Shared by the benchmark's CPU tests: a temporary copy of the benchmark
with every size cut to a few groups, so that the harness's own functions
can be driven end to end without the chip."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
XPROF = os.path.join(REPO, "artifacts", "tpu_r05", "xprof")

# The cells live when PR 36 made the per-layer list open, in their
# order: what an entry of that PR that "lists every cell" lists. A cell
# a later PR appends is that PR's to list under its own entries.
CELLS_AT_36 = ["engine64k-r3.append", "engine10k-r5.append",
               "engine100k-r3.elections", "engine1m-r3.joint-readindex",
               "engine512k-r3of4.replace-readindex"]

TINY_TRAFFIC = {
    "put": {"clients": 16, "ramp_s": 0.3},
    "lread": {"clients": 4, "preload_keys_per_group": 2, "ramp_s": 0.3},
    "append": {"rounds_per_call": 4},
}


def _edit(path, fn):
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def tiny_root(dst: str, groups: int = 8) -> str:
    """Copy ``BENCHMARK.json`` and ``benchmark/`` into ``dst`` and cut
    the sizes; returns ``dst``."""
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    for name in os.listdir(os.path.join(dst, "benchmark", "configs")):
        def cut(c):
            c["sizes"]["num_groups"] = groups
            c["shadow_groups"] = 4
        _edit(os.path.join(dst, "benchmark", "configs", name), cut)
    for name, upd in TINY_TRAFFIC.items():
        _edit(os.path.join(dst, "benchmark", "traffic", name + ".json"),
              lambda c, upd=upd: c.update(upd))
    _edit(os.path.join(dst, "BENCHMARK.json"), add_parked)
    return dst


def parked() -> dict:
    with open(os.path.join(REPO, "benchmark", "parked", "served.json")) as f:
        return json.load(f)


def add_parked(b: dict) -> None:
    """The served cells are parked (PERF.md, Open questions): every file
    they name is kept, their entries are in ``benchmark/parked/``. The
    tests add the entries, as the PR that takes a cell up again will."""
    p = parked()
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        b[key].extend(p[key])


def bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def edited_copy(tmp_path, edit) -> dict:
    """``BENCHMARK.json`` as a temporary copy of it reads after
    ``edit``: what a rule on the file is shown to admit and to refuse."""
    path = os.path.join(str(tmp_path), "BENCHMARK.json")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), path)
    _edit(path, edit)
    with open(path) as f:
        return json.load(f)


def swap(rows: list, i: int, j: int) -> None:
    rows[i], rows[j] = rows[j], rows[i]


def listed_cells(names) -> dict:
    """{name: its ``workloads``} of the live per-layer entries of these
    names, which have to stand in ``per_layer`` in this order, one
    after the other."""
    rows = bench()["per_layer"]
    at = [m["name"] for m in rows].index(names[0])
    assert [m["name"] for m in rows[at:at + len(names)]] == list(names)
    return {m["name"]: m["workloads"] for m in rows[at:at + len(names)]}
