"""Differentiable dispatch for the attention kernels."""
from __future__ import annotations

import functools

import jax

from repro.kernels.attention import decode as decode_mod
from repro.kernels.attention import flash as flash_mod
from repro.kernels.attention import ref as ref_mod
from repro.kernels.fused_stack.ops import DispatchStats

#: Trace-time decode-dispatch counters (same snapshot/delta protocol as
#: the fused-stack STATS): which decode path a compilation took — the
#: pallas flash kernels or the jnp reference.  Recorded at the dispatch
#: sites in :mod:`repro.layers.attention`; the serve engine diffs these
#: around a run so report() can prove ``mode="brainslug"`` serving
#: actually compiled ``paged_flash_decode`` (and name the fallback
#: otherwise).
STATS = DispatchStats(keys=("decode_pallas", "decode_ref",
                            "paged_decode_pallas", "paged_decode_ref"))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, scale: float | None = None):
    """Flash forward + reference-recompute backward.  ``scale`` overrides
    the default ``1/sqrt(head_dim)`` score scaling (the kernel-registry
    path passes the scale it matched out of the traced graph)."""
    return flash_mod.flash_attention_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k)


def _fwd(q, k, v, causal, block_q, block_k, scale):
    return flash_attention(q, k, v, causal, block_q, block_k,
                           scale), (q, k, v)


def _bwd(causal, block_q, block_k, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref_mod.attention_ref(q_, k_, v_, causal=causal,
                                                 scale=scale),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fwd, _bwd)


def flash_decode(q, k, v, lengths, *, block_k: int = 512):
    """Inference-only (no vjp needed on the decode path)."""
    return decode_mod.flash_decode(q, k, v, lengths, block_k=block_k)


def paged_flash_decode(q, k_pool, v_pool, table, lengths):
    """Block-mapped flash decode (inference-only, like ``flash_decode``):
    the (B, MB) block table routes each grid step to its physical pool
    block via scalar prefetch."""
    return decode_mod.paged_flash_decode(q, k_pool, v_pool, table, lengths)
