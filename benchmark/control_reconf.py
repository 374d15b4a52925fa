"""The reconfiguration cell's controls: ``correct`` shown to fail, once
for each guarantee the driver can break in the reference (``Driver``'s
module lists them as ``CONTROLS``).

    python3 benchmark/control_reconf.py --workload <name> --seed <n> [--seconds <s>]

Stands beside ``control_faults.py`` and is not an edit of it: that
script judges a control by the sampled replicas' final state, and this
cell's controls leave none behind. With commit on the incoming majority
alone the reference commits through the cut while the program stalls,
and is caught up with once the node is back; with reads confirmed
without the quorum the reference's batches run a round ahead. What
tells them apart is each replica's history (the hash of its state after
every round, ``reconf_checks.sample_checks``) and, for the reads, the
read state. Here the engine is built and run at the cell's own size (so
this needs the chip) by the cell's own generator, window opened and
closed as in a benchmark run, and compared with the reference once
sound and once under each control. Prints one line a case and exits 0
only if the sound case is correct and no control is. A broken guarantee
can take a reference group out of raft's own envelope (it raises:
``derailed``, equal to nothing), and a control that only derailed would
show the reference crashing, not the comparison catching it: so a
control counts only if replicas of groups that stayed inside the
protocol differ too (``in_protocol_replicas_differing``). The
benchmark's own runs never call this.
"""

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TELLS = "sampled_replicas_history_differs_from_reference"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--root", default=ROOT,
                    help="for the CPU tests: a copy cut to a few groups")
    ap.add_argument("--any-device", action="store_true",
                    help="for the CPU tests: do not insist on a TPU")
    a = ap.parse_args()
    sys.path[0] = ROOT
    from benchmark import harness
    from benchmark.compare import verdict

    harness.place_compile_cache(ROOT)
    cell = harness.Cell(a.root, a.workload)
    harness.check_device(cell.chips, require_tpu=not a.any_device)
    gen = cell.module("generators", cell.traffic["generator"])
    driver_mod = cell.module("drivers", cell.config["driver"])
    load = gen.make(cell.traffic, cell.config["sizes"], a.seed)
    workdir = tempfile.gettempdir()
    driver = driver_mod.Driver(cell.config, cell.traffic, a.seed, workdir)
    driver.setup(load, gen)
    raw = gen.run(driver, load, cell.traffic, a.seconds,
                  harness.Probe(False, 0.0, workdir))
    good = True
    for control in (None,) + tuple(driver_mod.CONTROLS):
        checks = driver.check(load, raw, control=control)
        ok, want = verdict(checks), control is None
        differing = {c.name: c.value for c in checks}[TELLS]
        in_protocol = differing - (
            len(driver.derailed) * int(cell.config["sizes"]["num_replicas"]))
        good &= ok == want and (want or in_protocol > 0)
        print("[control] " + json.dumps({
            "workload": a.workload, "seed": a.seed,
            "case": control or "sound", "correct": ok, "expected": want,
            "work": driver.calls,
            "failed_checks": {c.name: c.value for c in checks if not c.ok},
            "derailed_groups": len(driver.derailed),
            "in_protocol_replicas_differing": in_protocol,
        }), flush=True)
    return 0 if good else 1


if __name__ == "__main__":
    os._exit(main())
