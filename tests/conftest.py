"""Shared fixtures.  Every test runs in one process on 8 virtual CPU
devices (XLA_FLAGS, set here before jax initializes; the device count is
fixed at first init) — the multi-device tests build their meshes on them.
"""
import os

# 8 virtual CPU devices for every test in the session (NOT 512 — the
# dry-run owns that configuration in its own process).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

try:  # hypothesis is optional in the container; fall back to the shim
    import hypothesis  # noqa: F401  # noqa: E402
except ImportError:
    import os.path as _osp  # noqa: E402
    import sys as _sys  # noqa: E402

    _sys.path.insert(0, _osp.dirname(__file__))
    import _hypothesis_shim  # noqa: E402

    _hypothesis_shim.install()


@pytest.fixture(scope="session")
def devices8():
    d = jax.devices()
    assert len(d) >= 8, "test session expects 8 virtual CPU devices"
    return d


def assert_trees_close(a, b, rtol=1e-4, atol=1e-5, what=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_allclose(
            np.asarray(x, np.float32), np.asarray(y, np.float32),
            rtol=rtol, atol=atol, err_msg=f"{what} leaf {i}")
