"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

This is the pjit analog of the reference's CPU-MirroredStrategy trick
("CPU or single GPU also works", YOLO/tensorflow/README.md:2): multi-device
sharding semantics are exercised without TPU hardware.
"""
import os

# hard-set: tests always run on the virtual 8-device CPU mesh, whatever the
# shell carries.
os.environ["JAX_PLATFORMS"] = "cpu"
# the entry points place JAX's persistent compile cache in the checkout
# (core/excache.place_compile_cache); tests count compiles, so executables
# must not carry over between tests or runs — here or in spawned children
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from deep_vision_tpu.parallel import create_mesh

    assert len(jax.devices()) == 8
    return create_mesh()


@pytest.fixture(scope="session")
def mesh4x2():
    from deep_vision_tpu.parallel import create_mesh

    return create_mesh(data=4, model=2)
