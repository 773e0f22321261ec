"""Pallas TPU kernels.

Layout per kernel family: ``<name>.py`` holds the ``pl.pallas_call`` +
BlockSpec implementation, ``ops.py``-level wrappers (jit + custom_vjp) live
next to it, and ``ref.py`` is the pure-jnp oracle tests compare against.

All kernels are written for TPU (VMEM BlockSpec tiling, (8,128) alignment,
MXU-sized matmul tiles).  They run compiled on a TPU backend and in Pallas
interpret mode on any other backend (:func:`pallas_interpret`), which is how
the CPU test suite validates them.
"""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """Whether ``pl.pallas_call`` runs in interpret mode: compiled on a
    TPU backend, interpreted everywhere else.  The one place this is
    decided; every kernel asks at trace time."""
    return jax.default_backend() != "tpu"
