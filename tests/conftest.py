"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

The reference validates on real NICs/GPUs only (SURVEY.md §4); the idiomatic TPU
answer for CI without a pod is XLA's host-platform device virtualization — every
sharding/collective test here runs on 8 virtual CPU devices and is
topology-faithful to an 8-chip slice.
"""

import os

# Tests run on the CPU backend with 8 virtual devices, whatever the ambient
# environment points JAX at.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(pp=1, dp=2, cp=2, tp=2), devices)


@pytest.fixture(scope="session")
def mesh_dp8(devices):
    from uccl_tpu.parallel.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(dp=8), devices)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
