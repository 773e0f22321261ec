"""Flash-decode kernel: one new query position against a long KV cache.

The decode step is memory-bound — its roofline is the KV-cache read — so
the only thing that matters is touching each cache block exactly once.  The
kernel streams ``(block_k, d)`` cache tiles through VMEM, maintains the
online softmax state in scratch, and emits the output after the last tile.
A per-batch ``length`` operand masks the unwritten tail of the cache, so
one compiled kernel serves every decode position — and because it is
per-batch, one dispatch serves a *ragged* batch of slots (the continuous-
batching engine drives every slot at its own position).

Empty-slot convention: ``lengths == 0`` (a freed / not-yet-admitted cache
slot) means the softmax is taken over zero keys.  The kernel emits exactly
zero output for such rows instead of NaN or a stale-cache average: the
running max ``m`` only leaves its -inf seed when a valid key is seen, so
finalization can mask rows whose softmax was empty.  The jnp reference
(`ref.attention_ref`) implements the same convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels

NEG_INF = -1e30


def _kernel(scale: float, block_k: int, len_ref, *refs) -> None:
    """Online-softmax body of the dense kernel, one ``(b, h)`` row per
    grid step along the cache.

    ``len_ref`` is the scalar-prefetched ``(B,)`` lengths vector in SMEM.
    The masking index is the logical position ``j * block_k + lane``."""
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tiles are upcast in VMEM: a bf16 query against a float32 cache (or
    # the reverse) costs no HBM copy of either
    q = q_ref[0, 0].astype(jnp.float32)             # (1, d)
    k = k_ref[0, 0].astype(jnp.float32)             # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    k_idx = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_idx < len_ref[pl.program_id(0)], s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        # m never left its NEG_INF seed <=> every key was masked (length 0).
        # The l/acc state is then exp(0)-polluted garbage; emit zeros.
        valid = m_ref[...] > NEG_INF * 0.5
        acc = jnp.where(valid, acc_ref[...], 0.0)
        o_ref[0, 0] = (acc /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _paged_kernel(scale: float, block_size: int, tbl_ref, len_ref, q_ref,
                  k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref) -> None:
    """Online softmax over one slot's pool blocks, all heads per step.

    Grid ``(B, MB)``: step ``(b, j)`` holds slot ``b``'s logical block
    ``j``, the whole ``(G, bs, D)`` pool block, and the slot's queries
    viewed as ``(G, rep, D)``; score and value products are batched over
    the ``G`` KV heads.  ``tbl_ref`` is consumed by the index maps only.
    Steps past the slot's last live block compute nothing (their index
    maps name that block again, so nothing is fetched either); positions
    ``>= length`` inside the last live block are masked."""
    del tbl_ref
    b, j = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block_size < length)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # (G, rep, D)
        k = k_ref[0].astype(jnp.float32)            # (G, bs, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        k_idx = j * block_size + jax.lax.broadcasted_iota(jnp.int32,
                                                          s.shape, 2)
        s = jnp.where(k_idx < length, s, NEG_INF)   # (G, rep, bs)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        # m never left its NEG_INF seed <=> the slot has no key (length 0)
        valid = m_ref[...] > NEG_INF * 0.5
        acc = jnp.where(valid, acc_ref[...], 0.0)
        o_ref[0] = (acc /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _scratch(d: int) -> list:
    return [pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32)]


def paged_flash_decode(q: jnp.ndarray, k_pool: jnp.ndarray,
                       v_pool: jnp.ndarray, table: jnp.ndarray,
                       lengths: jnp.ndarray, *, scale: float | None = None
                       ) -> jnp.ndarray:
    """Flash decode through a block table: one query position against a
    block-mapped KV pool.

    q: (B, H, 1, D); k_pool/v_pool: (N, G, block_size, D) physical blocks;
    table: (B, MB) int32 — slot ``b``'s logical block ``j`` lives in
    physical block ``table[b, j]``; lengths: (B,) int32 valid positions.

    The table and the lengths ride in as scalar-prefetch (SMEM) operands
    (``PrefetchScalarGridSpec``) so the KV BlockSpec index maps can gather
    ``pool[table[b, j]]`` per grid step.  A pool block is contiguous
    across its ``G`` heads, so one step fetches it whole and serves every
    query head of the slot: the grid is ``(B, MB)``, one step per table
    entry.  The index maps clamp ``j`` to the slot's last live entry
    (``ceil(length / block_size) - 1``), so steps past it name the block
    already in VMEM and the pipeline starts no copy; their bodies are
    skipped.  Unmapped table entries may alias any pool block.
    """
    b, h, _one, d = q.shape
    n, g, bs, _ = k_pool.shape
    mb = table.shape[1]
    rep = h // g
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    # Entries past a slot's live range never reach the body, but the
    # clamped index map can still name one (length 0 names entry 0) —
    # clamp into the pool so an allocator sentinel (e.g. ``n`` for "no
    # block") can never index out of bounds.  This is what lets the
    # serving engine pass its table operand through unfiltered.
    table = jnp.clip(table.astype(jnp.int32), 0, n - 1)

    def kv_index(b_, j, tbl, lens):
        last = jnp.maximum((lens[b_] + bs - 1) // bs - 1, 0)
        return (tbl[b_, jnp.minimum(j, last)], 0, 0, 0)

    def q_index(b_, j, tbl, lens):
        return (b_, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mb),
        in_specs=[pl.BlockSpec((1, g, rep, d), q_index),
                  pl.BlockSpec((1, g, bs, d), kv_index),
                  pl.BlockSpec((1, g, bs, d), kv_index)],
        out_specs=pl.BlockSpec((1, g, rep, d), q_index),
        scratch_shapes=[pltpu.VMEM((g, rep, 1), jnp.float32),
                        pltpu.VMEM((g, rep, 1), jnp.float32),
                        pltpu.VMEM((g, rep, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale, bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, rep, d), q.dtype),
        name="paged_decode_kernel",
        interpret=kernels.pallas_interpret(),
    )(table, lengths.astype(jnp.int32), q.reshape(b, g, rep, d), k_pool,
      v_pool)
    return out.reshape(b, h, 1, d)


def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 lengths: jnp.ndarray, *, scale: float | None = None,
                 block_k: int = 512) -> jnp.ndarray:
    """q: (B, H, 1, D); k, v: (B, G, S, D); lengths: (B,) int32, a
    scalar-prefetch (SMEM) operand."""
    b, h, one, d = q.shape
    _, g, s, _ = k.shape
    rep = h // g
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    block_k = min(block_k, s)
    pk = (-s) % block_k
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v

    def kv_index(b_, h_, j, lens, rep=rep):
        return (b_, h_ // rep, j, 0)

    def q_index(b_, h_, j, lens):
        return (b_, h_, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, (s + pk) // block_k),
        in_specs=[pl.BlockSpec((1, 1, 1, d), q_index),
                  pl.BlockSpec((1, 1, block_k, d), kv_index),
                  pl.BlockSpec((1, 1, block_k, d), kv_index)],
        out_specs=pl.BlockSpec((1, 1, 1, d), q_index),
        scratch_shapes=_scratch(d),
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale, block_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        name="flash_decode_kernel",
        interpret=kernels.pallas_interpret(),
    )(lengths.astype(jnp.int32), q, kp, vp)
