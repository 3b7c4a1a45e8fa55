"""Test harness config.

Forces the CPU platform with 8 virtual XLA devices (the reference tests
against gloo on CPU CI runners the same way, SURVEY.md §4) BEFORE jax
initializes its backend.  Worker subprocesses spawned by distributed
tests get their platform via plugin env plumbing instead.
"""

import os

_REAL_HW = os.environ.get("CLUSTER") == "1"   # opt-in real-TPU session
                                              # (test_cluster_optin.py)

# Must happen before jax backend init: append the virtual-device flag.
_flags = os.environ.get("XLA_FLAGS", "")
if not _REAL_HW and "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# The persistent compilation cache is ON by default and lives in the
# checkout (compile/cache.py).  The CPU test session keeps it off — and
# forgets a directory the environment names — so tier-1 is hermetic: no
# run warms the next, no entry lands outside tmp_path.  Tests of the
# cache pass ``compile_cache=`` explicitly or set the variables
# themselves; worker subprocesses inherit this environment.
if not _REAL_HW:
    os.environ.setdefault("RLT_COMPILE_CACHE", "0")
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import jax  # noqa: E402

if not _REAL_HW:
    jax.config.update("jax_platforms", "cpu")


def pytest_collection_modifyitems(config, items):
    """Under CLUSTER=1 only the opt-in real-hardware tests run: the rest
    of the suite assumes the 8-virtual-device CPU platform this session
    deliberately did not force."""
    if not _REAL_HW:
        return
    import pytest as _pytest
    skip = _pytest.mark.skip(
        reason="CLUSTER=1 session runs only opt-in real-hardware tests")
    for item in items:
        if "test_cluster_optin" not in str(item.fspath):
            item.add_marker(skip)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from ray_lightning_tpu.utils.seed import seed_everything  # noqa: E402


@pytest.fixture
def seed():
    seed_everything(0)


@pytest.fixture
def tmp_root(tmp_path):
    return str(tmp_path)


def assert_tree_allclose(a, b, rtol=1e-5, atol=1e-6):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)
