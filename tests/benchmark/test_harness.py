"""The harness driven on the CPU at a tiny size, through its own
functions: the cells as committed, a cell made of nothing but new files
and entries, and the timed path broken underneath. The command itself
refuses a CPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from benchmark import harness

from .test_contract import ALL_CELLS
from .util import REPO, tiny_root

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", "engine64k-r3.append", "--seed", "1",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def no_result_line(stdout: str) -> bool:
    for line in stdout.splitlines():
        if line.startswith("{") and RESULT_KEYS <= set(json.loads(line)):
            return False
    return True


def test_command_refuses_without_a_tpu():
    t0 = time.monotonic()
    r = run_command(REPO)
    assert r.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "platform='cpu'" in r.stderr
    assert no_result_line(r.stdout)


def test_command_fails_where_only_the_benchmark_is(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` has no system to measure."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for path in ("benchmark", "tests/benchmark"):
        shutil.copytree(os.path.join(REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = run_command(str(tmp_path))
    assert r.returncode != 0
    assert no_result_line(r.stdout)


def test_unknown_workload_is_an_error():
    with pytest.raises(harness.BenchmarkError):
        harness.Cell(REPO, "no-such.cell")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(str(tmp_path_factory.mktemp("bench")))


def drive(root, workload, seed, seconds=0.6):
    cell = harness.Cell(root, workload)
    ctx, checks = harness.measure(cell, seed, seconds, False,
                                  time.perf_counter(), require_tpu=False)
    return cell, ctx, checks


# Every live cell and every parked one, so that a cell a later PR adds
# runs here without an edit; seeds past 32 bits and small ones by turns.
@pytest.mark.parametrize("workload,seed", [
    (name, 2**31 + 7 + i if i % 2 == 0 else 3 + i)
    for i, name in enumerate(ALL_CELLS)])
def test_cell_runs_tiny_and_is_correct(root, workload, seed):
    cell, ctx, checks = drive(root, workload, seed)
    assert harness.verdict(checks), [c for c in checks if not c.ok]
    assert all(c.limit == 0 for c in checks)
    e2e = harness.end_to_end_metrics(cell, ctx)
    assert set(e2e) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in e2e.values())
    layer = harness.per_layer_metrics(cell, ctx)
    harness.refuse_bad_values({**e2e, **layer})
    assert "compile.in_window" in layer
    assert layer["compile.in_window"]["value"] == 0
    if cell.config["driver"] == "served":
        assert {"member.round_ms", "member.ops_per_round",
                "rawnode.host_pct", "client.busy_pct",
                "fabric.lost"} <= set(layer)
        assert ctx["raw"]["failed"] == 0


def test_main_prints_each_number_compared_beside_its_limit(
        root, monkeypatch, capsys, tmp_path):
    """The command's own ``main`` past its look for a chip: the result
    line ends with the numbers compared, stderr ends with them too."""
    real = harness.check_device
    monkeypatch.setattr(harness, "check_device",
                        lambda chips, require_tpu=True: real(chips, False))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    rc = harness.main(["--workload", ALL_CELLS[0], "--seed",
                       str(2**31 + 99), "--seconds", "0.3"],
                      time.perf_counter(), root)
    out, err = capsys.readouterr()
    assert rc == 0
    line = json.loads(out.splitlines()[-1])
    assert RESULT_KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True and line["checks"]
    tail = err.splitlines()[-len(line["checks"]) - 1:]
    assert tail[-1] == "benchmark: correct = True"
    for text, (name, c) in zip(tail, line["checks"].items()):
        assert text == (f"benchmark: check {name} = {c['value']} "
                        f"(limit {c['limit']})")


def test_a_new_cell_is_files_and_entries_only(root):
    """A configuration, a traffic mix and a per-layer metric added as
    files of their own plus one entry each; no file that was there is
    edited but BENCHMARK.json, which gains the entries."""
    base = os.path.join(root, "benchmark")
    with open(os.path.join(base, "configs", "served1k-r3.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "served4-r3"
    cfg["sizes"]["num_groups"] = 4
    with open(os.path.join(base, "configs", "served4-r3.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(base, "traffic", "put.json")) as f:
        mix = json.load(f)
    mix.update(name="mixed", clients=8, read_share=0.25,
               preload_keys_per_group=2, check_restart=True,
               check_lread=True)
    with open(os.path.join(base, "traffic", "mixed.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(base, "readers", "extra.py"), "w") as f:
        f.write("def refusals(ctx, scale=1.0):\n"
                "    return scale * ctx['raw'].get('refusals', 0)\n")
    with open(os.path.join(base, "layer_metrics",
                           "client.refusals.json"), "w") as f:
        json.dump({"name": "client.refusals", "unit": "ops",
                   "layer": "load generator", "moves": "ops_per_s",
                   "reader": "extra.refusals", "params": {"scale": 1.0}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "served4-r3", "source": cfg["source"],
        "file": "benchmark/configs/served4-r3.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "served4-r3.mixed", "config": "served4-r3",
        "traffic": "mixed", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "served1k-r3.put" in m["workloads"]:
            m["workloads"].append("served4-r3.mixed")
    bench["per_layer"].append({
        "name": "client.refusals", "unit": "ops", "better": "lower",
        "source": "host_clock", "layer": "load generator",
        "moves": "ops_per_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell, ctx, checks = drive(root, "served4-r3.mixed", 9)
    assert harness.verdict(checks), [c for c in checks if not c.ok]
    assert {c.name for c in checks} >= {
        "restart_sample_not_served", "linearizable_reads_stale_or_wrong"}
    assert ctx["raw"]["clients_reading"] == 2
    assert ctx["raw"]["clients_putting"] == 6
    layer = harness.per_layer_metrics(cell, ctx)
    assert layer["client.refusals"]["unit"] == "ops"
    assert "wal.fsync_ms" not in layer  # names its cells; this is none
    assert set(harness.end_to_end_metrics(cell, ctx)) == {
        "ops_per_s", "op_p95_ms", "setup_s"}


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    """The served timed path broken underneath: one member stores
    another value than the one committed, in groups it does not lead."""
    from benchmark.drivers import served

    real_place = served.Driver._place_leaders

    def place_then_break(self, *a, **kw):
        real_place(self, *a, **kw)
        victim = self.members[2]
        for g, kv in enumerate(victim.kvs):
            if self.target[g] == 3:
                continue
            real_apply = kv.apply

            def apply(payload, real_apply=real_apply):
                if payload[:1] == b"P":
                    payload = payload[:-1] + bytes([payload[-1] ^ 1])
                real_apply(payload)
            kv.apply = apply

    monkeypatch.setattr(served.Driver, "_place_leaders", place_then_break)
    _cell, ctx, checks = drive(root, "served1k-r3.put", 21)
    assert ctx["raw"]["attempted"] > 0
    assert not harness.verdict(checks)
    bad = {c.name for c in checks if not c.ok}
    assert "acked_puts_not_on_every_member" in bad
    assert "groups_with_replica_hash_mismatch" in bad


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    """The engine's timed path broken underneath: one call of the
    window is counted and does nothing."""
    from benchmark.drivers import engine

    real_call = engine.Driver.call

    def call(self):
        if self.calls == 3:
            self.calls += 1
            return
        real_call(self)

    monkeypatch.setattr(engine.Driver, "call", call)
    _cell, ctx, checks = drive(root, "engine64k-r3.append", 4, 0.3)
    assert ctx["raw"]["calls"] > 4
    assert not harness.verdict(checks)
