#!/usr/bin/env bash
# One-shot static gate (ISSUE 7, grown by ISSUEs 9/10): ruff + jitlint
# + runtime-sentinel smoke (transfer guard, recompile budget, lock
# order) + trace smoke (one traced in-proc round, exporter validated)
# + fleet smoke (tiny in-proc cluster with the fleet observatory on,
# fleet_console --once --json validated) + rebalance smoke (seeded
# leader skew, rebalancerd --once --json must converge it) + walpipe
# smoke (async group-commit WAL pipeline: fsync coverage > 1, clean
# stop-drain replay) + diskfault smoke (ISSUE 15 IO-error contract:
# fsync-error fail-stop + ENOSPC back-pressure recover, zero acked
# loss) + shmfabric smoke (ISSUE 16 mmap ring transport: 3-member shm
# cluster, put wave, console transport column + shm metric families)
# + lifecycle smoke (ISSUE 17 log-lifecycle plane: rotation, cadence
# snapshots, fleet-min release, restart replay from snapshot files)
# + applyplane smoke (ISSUE 19 device apply plane: lease-hit read,
# watch frame, TTL expiry on the plane clock, transfer fallback). CI
# runs exactly this script
# (.github/workflows/lint.yml); run it locally before pushing anything
# that touches the batched hot path.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== ruff (ruff.toml: error-class rules over the hot-path scope) =="
if command -v ruff >/dev/null 2>&1; then
    ruff check .
elif python -m ruff --version >/dev/null 2>&1; then
    python -m ruff check .
else
    echo "ruff not installed in this environment -- SKIPPED (CI enforces it)"
fi

echo "== jitlint (trace safety / dtype discipline / purity) =="
python tools/jitlint.py \
    etcd_tpu/batched/ etcd_tpu/analysis/ etcd_tpu/tools/ tools/ bench.py \
    chip_smoke.py

echo "== sentinel smoke (transfer guard, recompile budget, lock order) =="
python -m pytest tests/analysis tests/batched/test_sentinels.py -q

echo "== trace smoke (one traced in-proc round, exporter validates) =="
python tools/trace_smoke.py

echo "== fleet smoke (in-proc cluster with fleet on, console --once --json) =="
python tools/fleet_smoke.py

echo "== rebalance smoke (seeded leader skew, rebalancerd --once --json) =="
python tools/rebalance_smoke.py

echo "== walpipe smoke (async group-commit WAL pipeline, fsync coverage > 1) =="
python tools/walpipe_smoke.py

echo "== diskfault smoke (fsync-error fail-stop + ENOSPC recover, IO-error contract) =="
python tools/diskfault_smoke.py

echo "== fused-round smoke (election, commits, ReadIndex; transfer guard disallow) =="
python tools/fused_smoke.py

echo "== shmfabric smoke (3-member shm ring cluster, console transport column) =="
python tools/shmfabric_smoke.py

echo "== lifecycle smoke (WAL rotation -> cadence snapshot -> release -> replay) =="
python tools/lifecycle_smoke.py

echo "== applyplane smoke (lease-hit read, watch frame, TTL expiry, transfer fallback) =="
python tools/applyplane_smoke.py

echo "check.sh: all gates green"
