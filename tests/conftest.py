"""Test fixture — the analog of the reference's BaseDL4JTest
(deeplearning4j-core/src/test/java/org/deeplearning4j/BaseDL4JTest.java).

All tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the reference's analog: Spark local[N] +
ParallelWrapper CPU workers, SURVEY §4). The suite never runs on the chip:
``chip_smoke.py`` is the on-chip check.
"""

import os

# Must be set before jax initializes backends: 8 virtual CPU devices.
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture()
def rng():
    return np.random.default_rng(12345)
