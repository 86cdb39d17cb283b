"""Test harness config: JAX on a virtual 8-device CPU mesh.

Multi-chip hardware is not available to the tests; sharding tests run
against XLA's host-platform device partitioning instead. Nothing imports
JAX at interpreter startup, so the XLA_FLAGS set here and the
``jax_platforms`` update below take effect at the first backend init
(``JAX_PLATFORMS=cpu`` in the environment does the same).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy cases tier-1 skips (-m 'not slow'); the device-crypto "
        "CI job and `pytest -m slow` run them",
    )
