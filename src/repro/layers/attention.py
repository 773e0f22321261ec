"""GQA attention with RoPE (or none: ``cfg.use_rope``): train (flash /
chunked / full) + decode paths.  Scores are scaled by
``cfg.attention_multiplier``, or ``1/sqrt(head_dim)`` when it is unset.

Schedules:
* ``brainslug``  — the depth-first Pallas flash kernel (scores never hit HBM)
* ``xla``        — a lax.scan online-softmax at the JAX level for long
                   sequences (memory-bounded, GSPMD-shardable), full scores
                   for short ones
* ``barrier``    — full scores with materialization barriers between the
                   score/softmax/weight stages (the paper's breadth-first
                   framework baseline)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.core import ir
from repro.kernels.attention import ops as attn_ops
from repro.kernels.attention import ref as attn_ref
from repro.layers import base

FULL_SCORE_MAX_SEQ = 2048          # above this, xla mode uses the chunked scan


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, h, g, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": base.boxed(ks[0], (d, h * hd), ("fsdp", "heads"), dtype=dtype),
        "wk": base.boxed(ks[1], (d, g * hd), ("fsdp", "kv_heads"),
                         dtype=dtype),
        "wv": base.boxed(ks[2], (d, g * hd), ("fsdp", "kv_heads"),
                         dtype=dtype),
        "wo": base.boxed(ks[3], (h * hd, d), ("heads", "fsdp"),
                         dtype=dtype, scale=1.0 / (h * hd) ** 0.5),
    }
    if cfg.qkv_bias:
        p["bq"] = base.boxed(key, (h * hd,), ("heads",), init="zeros",
                             dtype=dtype)
        p["bk"] = base.boxed(key, (g * hd,), ("kv_heads",), init="zeros",
                             dtype=dtype)
        p["bv"] = base.boxed(key, (g * hd,), ("kv_heads",), init="zeros",
                             dtype=dtype)
    return p


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, H, S, D_h); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,S,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Core attention math (xla / barrier paths)
# ---------------------------------------------------------------------------

def _full_attention(q, k, v, causal: bool, barrier: bool,
                    scale: float | None = None) -> jnp.ndarray:
    """GQA without kv expansion: q heads grouped against their kv head in
    the einsum — no (H/G)x repeated copy of K/V is materialized."""
    b, h, sq, hd = q.shape
    g, sk = k.shape[1], k.shape[2]
    rep = h // g
    if scale is None:
        scale = 1.0 / hd ** 0.5
    qg = q.reshape(b, g, rep, sq, hd)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    if barrier:
        s = ir.opt_barrier(s)
    if causal:
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if barrier:
        p = ir.opt_barrier(p)
    # p stays f32 (casting the largest tensor costs a materialized copy;
    # the MXU consumes f32 LHS fine — v is promoted, a far smaller tensor)
    o = jnp.einsum("bgrqk,bgkd->bgrqd", p, v.astype(jnp.float32))
    return o.reshape(b, h, sq, hd).astype(q.dtype)


def _chunked_attention(q, k, v, causal: bool, block_k: int = 512,
                       unroll: bool = False,
                       scale: float | None = None) -> jnp.ndarray:
    """Online-softmax over KV chunks at the JAX level (lax.scan).  Bounded
    memory for long sequences without a custom kernel — the xla-mode path.

    Traffic posture (mirrors the flash kernel): matmul operands stay in the
    model dtype (bf16 in production) with f32 accumulation via
    ``preferred_element_type``; only the online-softmax statistics (m, l,
    acc) are f32.  GQA is grouped, not repeated."""
    b, h, sq, hd = q.shape
    g, sk = k.shape[1], k.shape[2]
    rep = h // g
    if scale is None:
        scale = 1.0 / hd ** 0.5
    pad = (-sk) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = (sk + pad) // block_k
    kc = k.reshape(b, g, nk, block_k, hd)
    vc = v.reshape(b, g, nk, block_k, hd)
    qg = q.reshape(b, g, rep, sq, hd)
    q_idx = jnp.arange(sq)[None, None, None, :, None]

    def step(carry, j):
        m, l, acc = carry
        kj = kc[:, :, j]                                 # (b, g, bk, hd)
        vj = vc[:, :, j]
        s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kj,
                       preferred_element_type=jnp.float32) * scale
        k_idx = j * block_k + jnp.arange(block_k)[None, None, None, None, :]
        valid = k_idx < sk
        if causal:
            valid = valid & (k_idx <= q_idx + (sk - sq))
        s = jnp.where(valid, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        # p stays f32: casting the (sq x bk) tile would materialize a copy
        # of the largest tensor per chunk; vj (bk x hd) promotes instead
        acc = acc * corr + jnp.einsum(
            "bgrqk,bgkd->bgrqd", p, vj.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, g, rep, sq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, g, rep, sq, 1), jnp.float32)
    a0 = jnp.zeros((b, g, rep, sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(nk),
                                  unroll=nk if unroll else 1)
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(b, h, sq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Layer entry points
# ---------------------------------------------------------------------------

def _project(params, x, cfg: ModelConfig):
    b, s, d = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = jnp.einsum("bsd,dk->bsk", x, params["wq"])
    k = jnp.einsum("bsd,dk->bsk", x, params["wk"])
    v = jnp.einsum("bsd,dk->bsk", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, g, hd).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, g, hd).transpose(0, 2, 1, 3)
    return q, k, v


def apply(params, x: jnp.ndarray, cfg: ModelConfig, rt: RuntimeConfig,
          *, positions: jnp.ndarray | None = None) -> jnp.ndarray:
    """Full-sequence attention (train / prefill)."""
    b, s, _ = x.shape
    causal = not cfg.is_encoder
    scale = cfg.attention_multiplier
    q, k, v = _project(params, x, cfg)
    if cfg.use_rope:
        if positions is None:
            positions = jnp.arange(s)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)

    if rt.attn_impl == "skip_core":
        # cost-probe mode: the quadratic core is bypassed (o = q + 0*v so
        # every projection stays live); used to measure the attention
        # share of a block's cost by differencing two lowerings
        o = q + 0.0 * jnp.mean(v) + 0.0 * jnp.mean(k)
    elif rt.mode == "brainslug":
        o = attn_ops.flash_attention(q, k, v, causal, rt.attn_block_q,
                                     rt.attn_block_k, scale)
    elif rt.mode == "barrier":
        o = _full_attention(q, k, v, causal, barrier=True, scale=scale)
    elif s > FULL_SCORE_MAX_SEQ:
        o = _chunked_attention(q, k, v, causal, unroll=rt.scan_unroll,
                               scale=scale)
    else:
        o = _full_attention(q, k, v, causal, barrier=False, scale=scale)

    o = o.transpose(0, 2, 1, 3).reshape(b, s, cfg.n_heads * cfg.head_dim)
    return jnp.einsum("bsk,kd->bsd", o, params["wo"])


@dataclasses.dataclass
class KVCache:
    k: jnp.ndarray          # (B, G, S_max, hd)
    v: jnp.ndarray
    length: jnp.ndarray     # (B,) int32

    #: Decode-cache sharding declaration consumed by
    #: ``repro.core.partition.plan_decode_cache``: per field, which
    #: *negative* dim index carries the batch-slot extent ("slot") and
    #: which carries the KV-head extent ("model").  Negative indexing is
    #: what keeps one declaration valid for both a bare per-layer node and
    #: the engine's (L, ...)-stacked cache leaves.
    CACHE_AXES = {"k": {"slot": -4, "model": -3},
                  "v": {"slot": -4, "model": -3},
                  "length": {"slot": -1}}


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> KVCache:
    g, hd = cfg.n_kv_heads, cfg.head_dim
    return KVCache(
        k=jnp.zeros((batch, g, max_len, hd), dtype),
        v=jnp.zeros((batch, g, max_len, hd), dtype),
        length=jnp.zeros((batch,), jnp.int32))


@dataclasses.dataclass
class PagedKVCache:
    """Block-mapped KV state: a fixed pool of ``(block_size,)``-token
    physical blocks shared by every slot, addressed through a per-dispatch
    block table.  The table itself is *not* cache state — it only changes
    at host events (admission, on-demand append, copy-on-write fork), so
    the engine threads it into each dispatch as an ordinary operand and
    the jitted step stays table-shape-polymorphic over engine instances.
    """
    k_pool: jnp.ndarray     # (N, G, block_size, hd) physical blocks
    v_pool: jnp.ndarray
    length: jnp.ndarray     # (B,) int32 logical positions per slot

    #: Like :attr:`KVCache.CACHE_AXES`, but the pools have *no* slot dim —
    #: every slot scatters into one shared physical pool.  ``pool: True``
    #: tells the planner the leaf must never shard over the batch axis:
    #: data-sharding slots while each shard holds a full pool replica
    #: would let the per-shard scatter writes diverge between replicas.
    CACHE_AXES = {"k_pool": {"model": -3, "pool": True},
                  "v_pool": {"model": -3, "pool": True},
                  "length": {"slot": -1}}


def init_paged_cache(cfg: ModelConfig, batch: int, num_blocks: int,
                     block_size: int, dtype=jnp.bfloat16) -> PagedKVCache:
    g, hd = cfg.n_kv_heads, cfg.head_dim
    return PagedKVCache(
        k_pool=jnp.zeros((num_blocks, g, block_size, hd), dtype),
        v_pool=jnp.zeros((num_blocks, g, block_size, hd), dtype),
        length=jnp.zeros((batch,), jnp.int32))


def _decode_paged(params, x_t: jnp.ndarray, cache: PagedKVCache,
                  cfg: ModelConfig, rt: RuntimeConfig,
                  table: jnp.ndarray, active: jnp.ndarray | None
                  ) -> tuple[jnp.ndarray, PagedKVCache]:
    b = x_t.shape[0]
    h, hd = cfg.n_heads, cfg.head_dim
    n, _, bs, _ = cache.k_pool.shape
    q, k_new, v_new = _project(params, x_t, cfg)          # (B,*,1,hd)
    pos = cache.length                                     # (B,)
    if cfg.use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k_new = rope(k_new, pos[:, None], cfg.rope_theta)

    # Scatter write through the table: position `pos` lands in physical
    # block table[b, pos // bs] at offset pos % bs.  The host pre-maps
    # (and COW-forks) every block a dispatch will write, so the target is
    # always private (refcount 1) — inactive slots are routed to the
    # out-of-range id `n` and dropped.  Unlike the dense layout, a
    # where-select over the pool is not expressible (the written row is
    # per-slot dynamic), but the scatter touches one (G, hd) row per slot
    # against a pool-sized operand, and with donation it stays in place.
    phys = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    if active is not None:
        phys = jnp.where(active, phys, n)
    off = pos % bs
    k_pool = cache.k_pool.at[phys, :, off].set(
        k_new[:, :, 0].astype(cache.k_pool.dtype), mode="drop")
    v_pool = cache.v_pool.at[phys, :, off].set(
        v_new[:, :, 0].astype(cache.v_pool.dtype), mode="drop")
    adv = 1 if active is None else active.astype(jnp.int32)
    lengths = cache.length + adv
    new_cache = PagedKVCache(k_pool=k_pool, v_pool=v_pool, length=lengths)
    if rt.mode == "brainslug":
        attn_ops.STATS.record("paged_decode_pallas")
        o = attn_ops.paged_flash_decode(
            q, k_pool.astype(q.dtype), v_pool.astype(q.dtype), table,
            lengths, scale=cfg.attention_multiplier)
    else:
        attn_ops.STATS.record("paged_decode_ref")
        o = attn_ref.paged_decode_ref(
            q, k_pool.astype(q.dtype), v_pool.astype(q.dtype), table,
            lengths, scale=cfg.attention_multiplier)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * hd)
    out = jnp.einsum("bsk,kd->bsd", o, params["wo"])
    if rt.tp_axis:
        # heads are tensor-sharded: each shard computed a partial row-slice
        # product against its wo rows; the sum over shards is the output
        out = jax.lax.psum(out, rt.tp_axis)
    return out, new_cache


def decode(params, x_t: jnp.ndarray, cache, cfg: ModelConfig,
           rt: RuntimeConfig, *, active: jnp.ndarray | None = None,
           block_table: jnp.ndarray | None = None
           ) -> tuple[jnp.ndarray, KVCache]:
    """One decode step.  x_t: (B, 1, D).

    ``active`` is an optional (B,) bool slot mask (continuous-batching
    engine): inactive slots neither write their K/V into the cache nor
    advance their length — their cache state is frozen while other slots
    in the same dispatch prefill or decode.  ``None`` means all active.

    A :class:`PagedKVCache` dispatches the block-mapped path and requires
    ``block_table`` (the engine threads it per dispatch).
    """
    if isinstance(cache, PagedKVCache):
        if block_table is None:
            raise ValueError(
                "paged KV cache requires a block_table operand (the "
                "engine threads it through lm.decode_step)")
        return _decode_paged(params, x_t, cache, cfg, rt, block_table,
                             active)
    b = x_t.shape[0]
    h, g, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q, k_new, v_new = _project(params, x_t, cfg)          # (B,*,1,hd)
    pos = cache.length                                     # (B,)
    if cfg.use_rope:
        q = rope(q, pos[:, None], cfg.rope_theta)
        k_new = rope(k_new, pos[:, None], cfg.rope_theta)

    # where-select write at position `length`.  A per-batch scatter was
    # measured 5x worse in XLA's byte accounting (scatter is charged ~10x
    # the cache size vs 2x for the fused select); with buffer donation the
    # select lowers to an in-place masked update.
    idx = cache.length[:, None, None, None]
    barange = jnp.arange(cache.k.shape[2])[None, None, :, None]
    write = barange == idx
    if active is not None:
        write = write & active[:, None, None, None]
    k = jnp.where(write, k_new.astype(cache.k.dtype), cache.k)
    v = jnp.where(write, v_new.astype(cache.v.dtype), cache.v)
    adv = 1 if active is None else active.astype(jnp.int32)
    lengths = cache.length + adv
    new_cache = KVCache(k=k, v=v, length=lengths)
    if rt.mode == "brainslug":
        attn_ops.STATS.record("decode_pallas")
        o = attn_ops.flash_decode(q, k.astype(q.dtype), v.astype(q.dtype),
                                  lengths, block_k=rt.decode_block_k,
                                  scale=cfg.attention_multiplier)
    else:
        attn_ops.STATS.record("decode_ref")
        o = attn_ref.decode_ref(q, k.astype(q.dtype), v.astype(q.dtype),
                                lengths, scale=cfg.attention_multiplier)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * hd)
    out = jnp.einsum("bsk,kd->bsd", o, params["wo"])
    if rt.tp_axis:
        out = jax.lax.psum(out, rt.tp_axis)
    return out, new_cache


jax.tree_util.register_dataclass(
    KVCache, data_fields=["k", "v", "length"], meta_fields=[])
jax.tree_util.register_dataclass(
    PagedKVCache, data_fields=["k_pool", "v_pool", "length"], meta_fields=[])
