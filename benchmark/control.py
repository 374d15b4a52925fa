"""The controls: the comparison that decides ``correct``, shown to fail.

    python3 benchmark/control.py --workload <name> --seed <n> [--seconds <s>]

The system states no numeric precision, so a control breaks one
guarantee the configuration states and stands in the program's place;
the comparison then has to say ``correct: false``, and with nothing
broken ``true``.

* served cells — ``reference.kv.PlainCluster`` is driven by the cell's
  own generator at the cell's own client count, once sound and once for
  each broken guarantee (an acknowledgement before replication, a stale
  linearizable read, no fsync).
* engine cells — the engine is built and run at the cell's own size (so
  this needs the chip), and its state is compared with the reference
  once sound and once with commit-without-quorum.

Prints one line per case and exits 0 only if every sound case is
correct and every control is not. The benchmark's own runs never call
this.
"""

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def served_case(cell, seed: int, seconds: float, broken):
    from benchmark.compare import verdict
    from benchmark.harness import Probe
    from benchmark.reference.kv import PlainCluster

    gen = cell.module("generators", cell.traffic["generator"])
    load = gen.make(cell.traffic, cell.config["sizes"], seed)
    target = PlainCluster(load["groups"],
                          int(cell.config["sizes"]["num_replicas"]), broken)
    gen.preload(target, load, cell.traffic)
    raw = gen.run(target, load, cell.traffic, seconds,
                  Probe(False, 0.0, tempfile.gettempdir()))
    checks = target.checks(raw, bool(cell.traffic.get("check_lread")))
    return verdict(checks), checks, raw["attempted"]


def engine_case(driver, load, control: bool):
    from benchmark.compare import verdict

    checks = driver.check(load, {}, control=control)
    return verdict(checks), checks, driver.calls


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    a = ap.parse_args()
    sys.path[0] = ROOT
    from benchmark import harness
    from benchmark.reference.kv import BROKEN

    harness.place_compile_cache(ROOT)
    cell = harness.Cell(ROOT, a.workload)
    rows = []
    if cell.config["reference"] == "served_kv":
        cases = [b for b in BROKEN if b != "stale_read"
                 or cell.traffic.get("check_lread")]
        for broken in [None] + cases:
            ok, checks, n = served_case(cell, a.seed, a.seconds, broken)
            rows.append((broken or "sound", ok, checks, n))
    else:
        harness.check_device(cell.chips)
        gen = cell.module("generators", cell.traffic["generator"])
        load = gen.make(cell.traffic, cell.config["sizes"], a.seed)
        driver = cell.module("drivers", cell.config["driver"]).Driver(
            cell.config, cell.traffic, a.seed, tempfile.gettempdir())
        driver.setup(load, gen)
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < a.seconds:
            driver.call()
        for control in (False, True):
            ok, checks, n = engine_case(driver, load, control)
            rows.append(("commit_without_quorum" if control else "sound",
                         ok, checks, n))
    good = True
    for name, ok, checks, n in rows:
        want = name == "sound"
        good &= ok == want
        print("[control] " + json.dumps({
            "workload": a.workload, "seed": a.seed, "case": name,
            "correct": ok, "expected": want, "work": n,
            "failed_checks": {c.name: c.value for c in checks if not c.ok},
        }), flush=True)
    return 0 if good else 1


if __name__ == "__main__":
    os._exit(main())
