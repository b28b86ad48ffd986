"""Test config: run everything on a virtual 8-device CPU mesh.

Must set env BEFORE jax initializes (SURVEY.md §4): multi-chip sharding
tests use the 8 virtual CPU devices; the chip is reached only through
chip_smoke.py and bench.py.
"""

import os

# JAX_PLATFORMS=cpu in the environment is how the suite chooses the CPU;
# set before jax is imported anywhere.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running convergence test")
