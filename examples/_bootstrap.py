"""Shared example bootstrap: an 8-virtual-device CPU mesh unless real
TPUs are attached (same harness as tests/conftest.py)."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

if os.environ.get("JAX_PLATFORMS", "") != "tpu":
    # Examples default to the 8-device CPU simulation; on a real pod
    # run with JAX_PLATFORMS=tpu.
    jax.config.update("jax_platforms", "cpu")


def make_mesh(axes=("tp",), shape=None):
    devs = jax.devices()
    shape = shape or (len(devs),)
    n = int(np.prod(shape))
    return Mesh(np.array(devs[:n]).reshape(shape), axes)
