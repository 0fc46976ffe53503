"""Test configuration: force an 8-device virtual CPU mesh for all tests.

Tests run on the CPU backend, whatever accelerator the machine has:
JAX_PLATFORMS and jax.config pin it, and XLA_FLAGS must request the
virtual host devices before the CPU backend initializes. Tests exercise
sharding on the 8-device virtual CPU mesh. The chip runs go through
``chip_smoke.py`` (and ``--chips 4`` for the mesh) on the chip machine.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy fault-injection / stress cases (tier-1 runs -m 'not slow')"
    )
