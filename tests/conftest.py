"""Test fixture configuration.

Unit tests run on a virtual 8-device CPU mesh — the JAX analog of the
reference's shared ``local[*]`` SparkSession per suite
(core/test/base/src/main/scala/SparkSessionFactory.scala:40-51): multi-worker
parallelism exercised in one process, no real pod needed.

The suite always runs on the CPU, whatever the caller's environment pins:
XLA_FLAGS is set before the first backend initialization and the platform
is forced through jax.config as well as the environment.
"""

import os

import re

flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, (
    "tests require the virtual 8-device CPU mesh; backend was initialized "
    f"too early (got {jax.devices()})"
)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def basic_dataset():
    """Tiny mixed-type dataset (reference TestBase.makeBasicDF,
    core/test/base/src/main/scala/TestBase.scala:138-152)."""
    from mmlspark_tpu.data.dataset import Dataset

    return Dataset(
        {
            "numbers": np.array([0, 1, 2, 3], dtype=np.int64),
            "doubles": np.array([0.0, 1.5, 3.0, 4.5]),
            "words": ["guitars", "drums", "bass", "keys"],
            "flags": np.array([True, False, True, False]),
        }
    )
