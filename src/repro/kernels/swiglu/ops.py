"""Differentiable dispatch for the fused SwiGLU gate."""
from __future__ import annotations

import functools

import jax

from repro.kernels.swiglu import ref as ref_mod
from repro.kernels.swiglu import swiglu as kernel_mod

#: Gate activations the fused kernel implements — the kernel registry
#: only rewrites ``act(gate) * up`` clusters whose act is one of these.
ACTS = tuple(kernel_mod._ACTS)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def swiglu(gate, up, act: str = "silu", block_rows: int = 256):
    return kernel_mod.swiglu_fwd(gate, up, act=act, block_rows=block_rows)


def _fwd(gate, up, act, block_rows):
    return swiglu(gate, up, act, block_rows), (gate, up)


def _bwd(act, block_rows, res, g):
    gate, up = res
    _, vjp = jax.vjp(lambda a, b: ref_mod.swiglu_ref(a, b, act=act), gate, up)
    return vjp(g)


swiglu.defvjp(_fwd, _bwd)
