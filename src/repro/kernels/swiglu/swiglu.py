"""Fused SwiGLU / GeGLU gate kernel: ``y = act(gate) * up``.

Depth-first over ``(block_rows, block_f)`` tiles: gate and up are each read
once, the activation and product happen in VMEM, one write.  Breadth-first
materializes ``act(gate)`` to HBM first (an extra full read+write of an
``(T, d_ff)`` tensor — the largest activation in the block).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=True),
    "squared_relu": lambda x: jnp.square(jnp.maximum(x, 0.0)),
}


def _kernel(act: str, g_ref, u_ref, y_ref) -> None:
    g = g_ref[...]
    y_ref[...] = (_ACTS[act](g.astype(jnp.float32)).astype(g.dtype)
                  * u_ref[...])


#: Widest feature block: three double-buffered (rows, block) windows plus
#: the float32 activation stay well inside the default scoped VMEM.
MAX_BLOCK_F = 1024


def feature_block(f: int) -> int:
    """The widest lane multiple (128) that divides ``f``, up to
    :data:`MAX_BLOCK_F`; a width that is no lane multiple is taken whole."""
    if f % 128:
        return f
    return max(b for b in range(128, min(f, MAX_BLOCK_F) + 1, 128)
               if f % b == 0)


def swiglu_fwd(gate: jnp.ndarray, up: jnp.ndarray, *, act: str = "silu",
               block_rows: int = 256) -> jnp.ndarray:
    if act not in _ACTS:
        raise ValueError(f"unknown activation {act!r}")
    lead = gate.shape[:-1]
    f = gate.shape[-1]
    rows = 1
    for s in lead:
        rows *= s
    gf = gate.reshape(rows, f)
    uf = up.reshape(rows, f)
    block_rows = min(block_rows, max(rows, 1))
    pad = (-rows) % block_rows
    if pad:
        gf = jnp.pad(gf, ((0, pad), (0, 0)))
        uf = jnp.pad(uf, ((0, pad), (0, 0)))
    # the gate is elementwise, so the feature axis tiles freely: a whole
    # (block_rows, d_ff) row block does not fit VMEM at LM widths
    block_f = feature_block(f)
    tile = pl.BlockSpec((block_rows, block_f), lambda i, j: (i, j))
    y = pl.pallas_call(
        functools.partial(_kernel, act),
        grid=((rows + pad) // block_rows, f // block_f),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((rows + pad, f), gate.dtype),
        interpret=kernels.pallas_interpret(),
    )(gf, uf)
    return y[:rows].reshape(*lead, f)
