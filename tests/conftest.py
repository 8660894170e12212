"""Test harness: simulate an 8-device TPU pod slice on CPU.

The reference cannot test distributed behavior without >=4 real GPUs + NCCL
(SURVEY.md §4) — we fix that here: every sharding/collective path is exercised
on a virtual 8-device CPU mesh via XLA host-platform device multiplexing.
Must set flags BEFORE jax initializes.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)
