"""Test harness: run everything on a virtual 8-device CPU mesh.

TPU hosts always see >=1 local cores; forcing 8 CPU "devices" reproduces the
single-host 8-core scenario (the reference's `mp.spawn` world,
/root/reference/mpspawn_dist.py:140) without TPU hardware, per SURVEY.md §4.

Must run before the first `import jax` anywhere in the test session.
"""

import os

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()
# tests run on the virtual 8-device CPU mesh whatever the machine holds;
# JAX honours the variable when it is set before the first import
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest


@pytest.fixture(scope="session")
def eight_devices():
    import jax
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture
def rng():
    return np.random.default_rng(0)
