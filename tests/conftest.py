"""Test configuration.

Sharding/distributed tests run on a virtual 8-device CPU mesh: real
multi-chip TPU hardware is not available in CI, and XLA's
host-platform-device-count flag gives N independent devices with the
same SPMD semantics.

Tests run on the CPU whatever the machine holds: a chip belongs to one
process, and the suite spawns many. The platform is forced via
jax.config before any backend initializes; children the tests spawn pin
JAX_PLATFORMS=cpu themselves. The chip is exercised by chip_smoke.py,
not by this suite.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REFERENCE_ROOT = "/root/reference"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "e2e: spawns real member/CLI processes (slower)"
    )
    config.addinivalue_line(
        "markers", "slow: long-running soak/differential suites"
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection episodes over the batched "
        "multi-raft hosting path (quick subset in tier-1; the full "
        "matrix soak is also marked slow; reproduce a failing seed "
        "with ETCD_TPU_CHAOS_SEED)"
    )
