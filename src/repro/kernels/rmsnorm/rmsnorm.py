"""Fused residual-add + RMSNorm Pallas kernel.

The highest-frequency BrainSlug stack instance in the LM families:
``h = x + residual; y = rmsnorm(h) * scale``.  Depth-first: each
``(block_rows, D)`` tile is read once, the add, the row reduction and the
normalization all happen while the tile is VMEM-resident, and both outputs
(normalized value + new residual stream) are written once.  Breadth-first
execution would round-trip ``h`` through HBM between the add and the norm.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.core import resource

#: VMEM the tiles may take: the double-buffered in/out windows plus the
#: float32 working copies, inside the 16 MiB scoped default of a v5e.
VMEM_BUDGET = resource.TPU_V5E.vmem_bytes // 2


def fit_rows(block_rows: int, n_windows: int, d: int, itemsize: int) -> int:
    """``block_rows`` cut to what fits :data:`VMEM_BUDGET`: ``n_windows``
    double-buffered ``(rows, d)`` windows and three float32 temporaries
    per row, in multiples of 16 rows (the bf16 sublane tile)."""
    per_row = (2 * n_windows * itemsize + 3 * 4) * d
    return min(block_rows, max(16, VMEM_BUDGET // per_row // 16 * 16))


def _kernel(eps: float, has_residual: bool, x_ref, *refs) -> None:
    if has_residual:
        res_ref, scale_ref, y_ref, h_ref = refs
        h = x_ref[...] + res_ref[...]
        h_ref[...] = h
    else:
        (scale_ref, y_ref) = refs
        h = x_ref[...]
    hf = h.astype(jnp.float32)
    var = jnp.mean(jnp.square(hf), axis=-1, keepdims=True)
    y = hf * jax.lax.rsqrt(var + eps)
    y_ref[...] = (y * scale_ref[...].astype(jnp.float32)).astype(h.dtype)


def rmsnorm_fwd(x: jnp.ndarray,
                scale: jnp.ndarray,
                residual: jnp.ndarray | None = None,
                *,
                eps: float = 1e-6,
                block_rows: int = 256):
    """Returns ``(y, h)`` where ``h = x (+ residual)`` is the new residual
    stream and ``y = rmsnorm(h) * scale``."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    rows = 1
    for s in lead:
        rows *= s
    xf = x.reshape(rows, d)
    has_res = residual is not None
    rf = residual.reshape(rows, d) if has_res else None

    block_rows = min(fit_rows(block_rows, 4 if has_res else 2, d,
                              jnp.dtype(x.dtype).itemsize),
                     max(rows, 1))
    pad = (-rows) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        if has_res:
            rf = jnp.pad(rf, ((0, pad), (0, 0)))
    n = (rows + pad) // block_rows

    tile = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    pspec = pl.BlockSpec((1, d), lambda i: (0, 0))
    out_shape = [jax.ShapeDtypeStruct(((rows + pad), d), x.dtype)]
    out_specs = [tile]
    operands = [xf]
    in_specs = [tile]
    if has_res:
        operands.append(rf)
        in_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct(((rows + pad), d), x.dtype))
        out_specs.append(tile)
    operands.append(scale.reshape(1, d))
    in_specs.append(pspec)

    outs = pl.pallas_call(
        functools.partial(_kernel, eps, has_res),
        grid=(n,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        name="rmsnorm_kernel",
        interpret=kernels.pallas_interpret(),
    )(*operands)
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    y = outs[0][:rows].reshape(*lead, d)
    h = outs[1][:rows].reshape(*lead, d) if has_res else x
    return y, h
