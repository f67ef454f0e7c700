"""Test bootstrap: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (tests/unit/common.py
DistributedTest): "distributed" logic tests run against a fake backend.
Here that is JAX's host-platform device multiplexing —
``--xla_force_host_platform_device_count=8`` — so every sharding /
collective path compiles and executes exactly as it would on an 8-chip
slice.
"""

import os
import sys

# Must run before the first JAX backend initialization.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

# The suite runs on the CPU whatever the machine has.
jax.config.update("jax_platforms", "cpu")

_tests_dir = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_tests_dir))  # repo root
sys.path.insert(0, _tests_dir)  # so fixtures import as `unit.simple_model`

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running sweeps excluded from tier-1 (-m 'not slow')")


@pytest.fixture(autouse=True)
def reset_global_state():
    """Fresh mesh/comm state per test."""
    yield
    from deepspeed_tpu.parallel import groups
    groups.destroy_mesh()
    groups.mpu = None
