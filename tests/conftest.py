"""Test configuration: force CPU with 8 virtual devices.

Parity/unit tests run on CPU for determinism and speed; the virtual 8-device
topology exercises the same `jax.sharding.Mesh` code paths as a real TPU slice
(standard JAX practice via `--xla_force_host_platform_device_count`). TPU
benchmarks live in `bench.py`, not the test suite.

`JAX_PLATFORMS=cpu` pins the test process to CPU; it is set here, before
jax is imported, so a bare `pytest` run never reaches for an accelerator.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)
