"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths
compile and execute without TPU hardware (mirrors the reference's strategy
of testing multi-node with mpiexec on one node, SURVEY.md §4).

Every test runs on the CPU.  On a machine with a chip JAX would take
the TPU by default, and a jax imported before this file ran has already
captured its environment — so the platform is forced through jax.config
as well as through the environment.  What must hold on the chip itself
is asked of the TPU compiler in tests/test_chip_compile.py (no chip
needed) and proven by chip_smoke.py (on the chip).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running acceptance loops (excluded from tier-1 via "
        "-m 'not slow')")
