"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on 8 virtual CPU devices (the same Mesh/NamedSharding code paths
XLA uses on a real slice). Must set env before the first jax import.
"""

import os

# Tests run on the CPU, always: the sharding tests need 8 virtual
# devices, and pytest must never hold the chip (one process per chip —
# the chip is reached only through chip_smoke.py / benchmarks/run.py).
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

# The explicit cpu request, for this process and every child a test
# spawns: jaxenv.ensure_platform honors exactly JAX_PLATFORMS=cpu and
# otherwise insists on a TPU.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert not jax._src.xla_bridge._backends, \
    "jax backends initialized before conftest could force CPU"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from rafiki_tpu.datasets import make_synthetic_image_dataset  # noqa: E402


@pytest.fixture(scope="session")
def synth_image_data(tmp_path_factory):
    out = tmp_path_factory.mktemp("data")
    return make_synthetic_image_dataset(str(out), n_train=256, n_val=64,
                                        image_shape=(12, 12, 1), n_classes=4)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
