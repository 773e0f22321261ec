"""Shared fixtures.  Tests run on the single real CPU device — the 512-way
forced host platform is reserved for the dry-run (and the subprocess-based
multi-device tests, which set XLA_FLAGS in a child process)."""
from __future__ import annotations

import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Lock the backend to the single real CPU device *before* any test module
# import can touch XLA_FLAGS (repro.launch.dryrun sets the 512-device flag at
# import time for its own __main__ use; with the backend already initialized
# here it has no effect on this process).
jax.devices()

# Keep hypothesis deadlines off: jit compilation makes first calls slow.
# hypothesis is optional (test extra): without it, test_properties.py skips.
try:
    from hypothesis import settings  # noqa: E402

    settings.register_profile("repro", deadline=None, max_examples=25,
                              derandomize=True)
    settings.load_profile("repro")
except ModuleNotFoundError:
    pass


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def tree_allclose(a, b, rtol=1e-5, atol=1e-5):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)
