"""``BENCHMARK.json`` and the data files it names, against the contract's
own rules: every name resolves to a file that exists, every name and
unit is of the allowed characters, every bound is within its limit."""

import json
import os
import re

import pytest

from benchmark import harness

from .util import REPO, add_parked, bench, parked, tiny_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
B = bench()
CELLS = [w["name"] for w in B["workloads"]]
# The parked served cells' entries are held to the same rules, so that
# the PR that takes them up again pastes entries that pass.
WITH_PARKED = bench()
add_parked(WITH_PARKED)
ALL_CELLS = [w["name"] for w in WITH_PARKED["workloads"]]
PARKED_CELLS = [w["name"] for w in parked()["workloads"]]
METRICS = WITH_PARKED["end_to_end"] + WITH_PARKED["per_layer"]


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "benchmark/run.py"]
    assert B["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    # A full check with the full 24 cells has to fit into 43,200 s.
    runs = 2 + 14 * 24
    assert (runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200) <= 43200


# -- the list of cells, by rule: a later PR adds a cell with files and
# -- entries alone, and these hold whatever the list is


@pytest.mark.parametrize("w", WITH_PARKED["workloads"],
                         ids=lambda w: w["name"])
def test_cell_names_a_configuration_of_its_own_list(w):
    """A live cell's configuration is live; a parked cell's is live or
    parked with it."""
    pool = B if w["name"] in CELLS else WITH_PARKED
    assert w["config"] in {c["name"] for c in pool["configs"]}


@pytest.mark.parametrize("cfg", B["configs"], ids=lambda c: c["name"])
def test_live_configuration_has_a_live_cell(cfg):
    assert any(w["config"] == cfg["name"] for w in B["workloads"])


def test_no_two_cells_share_a_configuration_and_a_traffic_mix():
    pairs = [(w["config"], w["traffic"]) for w in WITH_PARKED["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_at_most_24_cells_and_half_of_them_on_four_chips():
    assert 1 <= len(CELLS) <= 24
    four = [w["name"] for w in B["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2), four


@pytest.mark.parametrize("w", WITH_PARKED["workloads"],
                         ids=lambda w: w["name"])
def test_cell_takes_one_chip_or_four(w):
    assert w["chips"] in (1, 4)


@pytest.mark.parametrize("cell", PARKED_CELLS)
def test_parked_cell_is_not_live(cell):
    assert cell not in CELLS


@pytest.mark.parametrize("cell", CELLS)
def test_live_cell_reports_one_end_to_end_metric_and_setup_s(cell):
    listing = [m["name"] for m in B["end_to_end"]
               if cell in m.get("workloads", [])]
    assert len(listing) == 1 and listing != ["setup_s"], listing
    unlisted = [m["name"] for m in B["end_to_end"] if "workloads" not in m]
    assert unlisted == ["setup_s"]


@pytest.mark.parametrize("w", WITH_PARKED["workloads"],
                         ids=lambda w: w["name"])
def test_perf_md_says_why_the_cell_exists(w):
    with open(os.path.join(REPO, "PERF.md")) as f:
        perf = f.read()
    missing = [n for n in (w["name"], w["config"])
               if f"`{n}`" not in perf]
    assert not missing, f"PERF.md does not name {missing}"


@pytest.mark.parametrize("cfg", WITH_PARKED["configs"],
                         ids=lambda c: c["name"])
def test_config_entry_and_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
    assert cfg["file"].startswith("benchmark/")
    with open(os.path.join(REPO, cfg["file"])) as f:
        data = json.load(f)
    assert data["name"] == cfg["name"]
    assert data["source"] == cfg["source"]
    assert data["reduced"] == cfg["reduced"]
    assert data["guarantees"] and data["reference"]
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "drivers", data["driver"] + ".py"))
    assert any(w["config"] == cfg["name"]
               for w in WITH_PARKED["workloads"])


@pytest.fixture(scope="module")
def root_with_parked(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("contract")))


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_cell_resolves_to_files_that_exist(cell, root_with_parked):
    c = harness.Cell(REPO if cell in CELLS else root_with_parked, cell)
    assert set(c.entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell) and NAME.match(c.entry["traffic"])
    assert 1 <= len(c.entry["why"]) <= 200
    assert c.module("drivers", c.config["driver"]).Driver
    gen = c.module("generators", c.traffic["generator"])
    assert gen.make and gen.run and gen.preload
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for spec in c.per_layer:
        assert callable(c.reader(spec))


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    end = m in WITH_PARKED["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if end else {"layer", "moves"})
    assert set(m) <= allowed and allowed - {"workloads"} <= set(m)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    for w in m.get("workloads", []):
        assert w in ALL_CELLS
    if end:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["moves"] in {e["name"]
                              for e in WITH_PARKED["end_to_end"]}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (m["name"], m["unit"], m["layer"], m["moves"])
        assert "workloads" not in spec, "cells are named on the cell's side"


def test_names_are_unique_and_setup_s_is_there():
    for rows in (WITH_PARKED["configs"], WITH_PARKED["workloads"], METRICS):
        names = [r["name"] for r in rows]
        assert len(set(names)) == len(names)
    setup = [m for m in B["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]


def test_files_under_paths_are_named_from_allowed_characters():
    for path in B["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_a_share_over_100_is_refused():
    ok = {"x": {"value": 99.9, "unit": "%"}}
    harness.refuse_bad_values(ok)
    for bad in (100.5, -1.0, float("nan")):
        with pytest.raises(harness.BenchmarkError):
            harness.refuse_bad_values({"x": {"value": bad, "unit": "%"}})
