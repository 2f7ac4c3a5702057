"""Test env: force JAX onto CPU with 8 virtual devices BEFORE jax imports.

This simulates a multi-chip mesh on the single-host test machine
(SURVEY.md §4): shard_map/all_to_all code paths run unchanged. Child
processes the tests spawn inherit JAX_PLATFORMS=cpu and the device count.
Tests are never chip evidence; benchmark/run.py on the chip is.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()


def pytest_configure(config):
    # Tier-1 runs `-m 'not slow'`: the slow marker carries the full chaos
    # matrix (every seeded fault scenario as OS processes, trace-merged);
    # the seeded smoke scenario stays in the default selection.
    config.addinivalue_line(
        "markers", "slow: long-running scenario suites excluded from tier-1"
    )
