"""Test harness: run every test on a virtual 8-device CPU mesh.

This is the TPU-native analog of the reference's "multi-node on localhost"
testing trick (`/root/reference/README.md:119-144`): instead of faking nodes
with multiple launcher processes, we fake an 8-chip slice inside one process
via XLA's host-platform device partitioning, so all sharding/collective code
paths (psum over the data axis, SyncBN, sharded eval) execute for real.

Must set env vars BEFORE jax is imported anywhere.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# The suite is a CPU suite whatever the machine holds: pin the platform
# before first backend use (same effect as JAX_PLATFORMS=cpu, without
# depending on how pytest was launched).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache (repo-local, gitignored): heavy compiles
# dedupe across processes (the multi-process CLI tests) and across runs.
from distribuuuu_tpu.runtime.compile_cache import enable_persistent_cache  # noqa: E402

enable_persistent_cache()

# No chip here: the Pallas kernels run in the interpreter because this suite
# asks for it — nothing in the package infers it from the platform. Tests
# that compile for a described chip (test_chip_compile.py) pass
# ``interpret=False`` explicitly.
from distribuuuu_tpu.ops.interpret import set_pallas_interpret  # noqa: E402

set_pallas_interpret(True)

import pytest  # noqa: E402

# Control-plane test modules that build thread+lock machinery (the serve,
# fleet, dataplane, autoscale and deploy tiers): under DTPU_LOCK_ORDER=1
# every test in these files runs inside a LockOrderGuard, so any lock-order
# inversion the suite's real thread interleavings produce fails the test
# that produced it (the dynamic complement of dtpu-lint's DT202; CI's lint
# job sets the variable).
_LOCK_ORDER_MODULES = (
    "test_serve",
    "test_fleet",
    "test_dataplane",
    "test_autoscale",
    "test_deploy",
    "test_ingress",
)


@pytest.fixture(autouse=True)
def _lock_order_guard(request):
    mod = os.path.splitext(os.path.basename(str(request.node.fspath)))[0]
    if os.environ.get("DTPU_LOCK_ORDER") != "1" or mod not in _LOCK_ORDER_MODULES:
        yield
        return
    from distribuuuu_tpu.analysis.guards import LockOrderGuard

    with LockOrderGuard():
        yield


@pytest.fixture()
def fresh_cfg():
    """Reset the global config singleton (and the BN-boundary-dtype global the
    trainer derives from it) around a test."""
    from distribuuuu_tpu import config
    from distribuuuu_tpu.models import layers

    config.reset_cfg()
    yield config.cfg
    config.reset_cfg()
    layers.set_bn_compute_dtype(jax.numpy.float32)
