"""Depth-first (flash) attention forward kernel for TPU.

BrainSlug's thesis — push a cache-resident tile through the *whole* op chain
instead of materializing every layer — is exactly the flash-attention
schedule: the ``(block_q, block_k)`` score tile never leaves VMEM; the
softmax chain (scale → mask → max → exp → normalize → weight) is applied
depth-first with an online rescaling, so the O(S²) score matrix is never
written to HBM.

Grid: ``(batch, q_heads, num_q_blocks, num_k_blocks)`` with the k-block axis
innermost (sequential on TPU), carrying the running max / denominator /
accumulator in VMEM scratch across k blocks.  GQA maps q head ``h`` onto KV
head ``h // (H // G)`` in the k/v index_maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _kernel(scale: float, causal: bool, block_q: int, block_k: int,
            seq_k: int, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref) -> None:
    i = pl.program_id(2)
    j = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)            # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = k_idx < seq_k                           # padded tail of K
    if causal:
        q_idx = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        valid = valid & (k_idx <= q_idx)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                             # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True, scale: float | None = None,
                        block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """q: (B, H, Sq, D); k, v: (B, G, Sk, D) with H a multiple of G."""
    b, h, sq, d = q.shape
    _, g, sk, _ = k.shape
    if h % g:
        raise ValueError(f"q heads {h} not a multiple of kv heads {g}")
    rep = h // g
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v

    grid = (b, h, (sq + pq) // block_q, (sk + pk) // block_k)

    out = pl.pallas_call(
        functools.partial(_kernel, scale, causal, block_q, block_k, sk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j, rep=rep: (b_, h_ // rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, i, j, rep=rep: (b_, h_ // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq + pq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=kernels.pallas_interpret(),
    )(qp, kp, vp)
    return out[:, :, :sq, :]
