"""Pallas kernel for the SSD intra-chunk block (Mamba2).

Per (batch, head, chunk) grid cell the kernel computes, on VMEM tiles:

    G       = C_c B_c^T                       (L, L) MXU matmul
    M       = G * exp(a_i - a_j) * tril       decay-masked scores
    Y_intra = M @ (dt*x)_c                    (L, P) MXU matmul
    S_c     = (B_c * exp(a_L - a))^T (dt*x)_c (N, P) chunk state

i.e. the whole masked-matmul chain runs depth-first on a chunk tile —
the (L, L) score matrix never exists in HBM.  The tiny inter-chunk state
recurrence stays at the JAX level (``chunked.py``); it is O(S/L) work.

The within-chunk cumulative decay ``a`` is computed at the JAX level too
(an element-wise cumsum that XLA fuses into the surrounding reshapes), so
the kernel body is pure matmul + VPU math — no scans inside Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels


def _kernel(chunk: int, dtx_ref, a_ref, b_ref, c_ref, y_ref, s_ref) -> None:
    dtx = dtx_ref[0, 0, 0]                       # (L, P) f32
    a = a_ref[0, 0, 0]                           # (L, 1) f32
    bb = b_ref[0, 0]                             # (L, N) f32
    cc = c_ref[0, 0]                             # (L, N) f32

    g = jax.lax.dot_general(cc, bb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, L)
    seg = a - a.reshape(1, chunk)                # a_i - a_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    m = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    y_ref[0, 0, 0] = jax.lax.dot_general(
        g * m, dtx, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    a_last = a[chunk - 1]                        # (1,)
    state_decay = jnp.exp(a_last.reshape(1, 1) - a)          # (L, 1)
    s_ref[0, 0, 0] = jax.lax.dot_general(
        bb * state_decay, dtx, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (N, P)


def ssd_intra_chunk(dtx: jnp.ndarray, a: jnp.ndarray, B: jnp.ndarray,
                    C: jnp.ndarray):
    """dtx: (b,h,nc,L,P) f32; a: (b,h,nc,L,1) f32; B/C: (b,nc,L,N) f32.
    Returns (y_intra (b,h,nc,L,P), S (b,h,nc,N,P))."""
    b, h, nc, L, p = dtx.shape
    n = B.shape[-1]
    grid = (b, h, nc)
    y, s = pl.pallas_call(
        functools.partial(_kernel, L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, L, p), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, 1), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, L, n), lambda b_, h_, c_: (b_, c_, 0, 0)),
            pl.BlockSpec((1, 1, L, n), lambda b_, h_, c_: (b_, c_, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, 1, L, p), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
            pl.BlockSpec((1, 1, 1, n, p), lambda b_, h_, c_: (b_, h_, c_, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, h, nc, L, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, nc, n, p), jnp.float32),
        ),
        interpret=kernels.pallas_interpret(),
    )(dtx, a, B, C)
    return y, s
