"""Test fixture: force an 8-device virtual CPU mesh BEFORE jax initializes.

Mirrors the reference's test strategy (SURVEY.md §4): multi-device tests run on
local virtual devices, no cluster needed. Real-TPU runs (chip_smoke.py,
bench.py) don't import this.

Backend init is lazy, so setting the platform here (before any jnp op runs)
pins the suite to the virtual CPU mesh even where a chip is present.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(2024)


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_tpu

    paddle_tpu.seed(1234)
    yield


@pytest.fixture(autouse=True, scope="session")
def _strict_op_registry():
    """Every op dispatched anywhere in the suite must have a registry row
    (catches dynamically-named ops the source scan cannot see)."""
    from paddle_tpu.framework import op_registry

    op_registry.set_strict(True)
    yield
    op_registry.set_strict(False)
