"""Test config: force an 8-device virtual CPU mesh BEFORE jax initializes.

Multi-chip TPU hardware isn't available in CI; sharding tests run on
xla_force_host_platform_device_count=8 per the driver's dryrun contract.
"""

import os
import sys

import pytest

# Unconditional: on a machine with a chip JAX would pick the TPU, but the
# test contract is an 8-device virtual CPU mesh.
_platform = os.environ.get("KTPU_TEST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _platform
flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# A pytest plugin may have imported jax already (baking the ambient env into
# jax.config); override programmatically — the backend itself initializes
# lazily on first use, which is after conftest.
try:
    import jax

    jax.config.update("jax_platforms", _platform)
except Exception:  # noqa: BLE001 — jax absent: nothing to force
    pass


@pytest.fixture(autouse=True)
def _restore_heap_policy():
    """A test that warms a scheduler freezes the process's heap and raises
    the young collector threshold (utils/heap.py): put both back after it,
    so the policy never reaches the tests that run after it in this
    process."""
    yield
    heap = sys.modules.get("kubernetes_tpu.utils.heap")
    if heap is not None and heap.settled():
        heap.restore()
