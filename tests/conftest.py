import os

# The suite runs on CPU with 4 VIRTUAL host devices so the shard_map
# serving path (kernels/lut_gather/ops.lut_network_fused_sharded) is
# exercised in CI without accelerators — the flag must be set before
# jax initialises.  Single-device behaviour is unchanged: unsharded
# tests simply run on device 0.  (The dry-run alone requests 512
# placeholder devices in its own subprocess; test_moe_ep likewise
# spawns a subprocess for its own mesh.)
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

from repro.xla_env import ensure_host_devices

ensure_host_devices(4)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import pytest

from time_limit import time_limit

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _per_test_time_limit():
    """A hung test fails by name, with a traceback, after
    ``time_limit.TEST_TIME_LIMIT_S``."""
    with time_limit():
        yield


@pytest.fixture
def lut_mesh():
    """Factory: 1-D data-parallel serving mesh over the first n virtual
    CPU devices (skips when the host exposes fewer)."""
    from repro.parallel.sharding import serving_mesh

    def make(n: int):
        if jax.device_count() < n:
            pytest.skip(f"needs {n} devices, have {jax.device_count()}")
        return serving_mesh(n)

    return make
