"""Test environment: force an 8-device virtual CPU mesh BEFORE jax import
so sharding/collective tests run without real multi-chip hardware
(mirrors the reference's virtual multi-node trick in
python/ray/cluster_utils.py — declared fake resources on one machine)."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# jax.config makes the pin hold whatever JAX_PLATFORMS was on the
# command line: the tests never take a chip (a chip belongs to one
# process at a time, and xdist runs several).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    """One local 'node' with a small worker pool (reference fixture name)."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_workers=4, ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_tensor_sched():
    """Same but with the device-tensor scheduler backend."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_workers=4, scheduler="tensor", ignore_reinit_error=True)
    yield ray_tpu
    ray_tpu.shutdown()
