"""Depth-first fused cross-entropy over the vocab head.

The (T, V) logits tensor of a big-vocab LM head (paligemma V=257k,
minitron 256k) is the single largest activation of the training step.
Breadth-first execution materializes it to HBM three times (matmul out,
logsumexp in, gather in).  This kernel runs the whole chain

    logits_chunk = h_tile @ W[:, chunk]          (MXU)
    online logsumexp over chunks                 (VPU, f32 stats)
    gold-logit extraction for the label column

depth-first on VMEM tiles: the logits exist only chunk-at-a-time in VMEM
and the outputs are two (T,)-vectors (logsumexp and gold logit).  This is
the same schedule transformation the paper applies to pooling chains,
applied to the head — BrainSlug's "non-matmul chain" restriction lifted
by fusing *through* the matmul with an online reduction (beyond-paper).

Grid: (row_tiles, v_chunks, d_chunks) with d innermost — the partial
matmul accumulates a (bR, bV) logits scratch over d, then the v-level
online-softmax update fires on the last d step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _kernel(block_v: int, v_real: int, h_ref, w_ref, lab_ref, lse_ref,
            gold_ref, logits_ref, m_ref, l_ref, g_ref) -> None:
    j = pl.program_id(1)                     # v chunk
    k = pl.program_id(2)                     # d chunk
    nv = pl.num_programs(1)
    nd = pl.num_programs(2)

    @pl.when((j == 0) & (k == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        g_ref[...] = jnp.zeros_like(g_ref)

    @pl.when(k == 0)
    def _zero_logits():
        logits_ref[...] = jnp.zeros_like(logits_ref)

    logits_ref[...] += jax.lax.dot_general(
        h_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == nd - 1)
    def _online_update():
        logits = logits_ref[...]                       # (bR, bV) f32
        labels = lab_ref[...]                          # (bR, 1) int32
        col = j * block_v + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1)
        logits = jnp.where(col < v_real, logits, NEG_INF)  # padded vocab
        is_gold = col == labels
        g_ref[...] += jnp.sum(jnp.where(is_gold, logits, 0.0), axis=-1,
                              keepdims=True)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        l_ref[...] = l_ref[...] * jnp.exp(m_prev - m_new) + jnp.sum(
            jnp.exp(logits - m_new), axis=-1, keepdims=True)
        m_ref[...] = m_new

        @pl.when(j == nv - 1)
        def _finalize():
            lse_ref[...] = m_ref[...] + jnp.log(
                jnp.maximum(l_ref[...], 1e-30))
            gold_ref[...] = g_ref[...]


def fused_ce_fwd(h: jnp.ndarray, w: jnp.ndarray, labels: jnp.ndarray,
                 *, block_rows: int = 128, block_v: int = 512,
                 block_d: int = 512
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """h: (T, D); w: (D, V); labels: (T,) int32 (may exceed V-1 for pad).
    Returns (logsumexp (T,), gold_logit (T,)) in f32 — the per-row NLL is
    ``lse - gold`` (mask handled by the caller)."""
    t, d = h.shape
    v = w.shape[1]
    block_rows = min(block_rows, t)
    block_v = min(block_v, v)
    block_d = min(block_d, d)
    pr = (-t) % block_rows
    pv = (-v) % block_v
    pd = (-d) % block_d
    hp = jnp.pad(h, ((0, pr), (0, pd))) if (pr or pd) else h
    wp = jnp.pad(w, ((0, pd), (0, pv))) if (pd or pv) else w
    labp = jnp.pad(labels, (0, pr), constant_values=-1) if pr else labels
    labp = labp.reshape(-1, 1).astype(jnp.int32)

    grid = ((t + pr) // block_rows, (v + pv) // block_v,
            (d + pd) // block_d)
    lse, gold = pl.pallas_call(
        functools.partial(_kernel, block_v, v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_d, block_v), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j, k: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j, k: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((t + pr, 1), jnp.float32),
            jax.ShapeDtypeStruct((t + pr, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_rows, block_v), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
        ],
        name="vocab_ce_kernel",
        interpret=kernels.pallas_interpret(),
    )(hp, wp, labp)
    return lse[:t, 0], gold[:t, 0]
