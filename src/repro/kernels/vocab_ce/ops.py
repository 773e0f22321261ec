"""Differentiable dispatch for the fused vocab cross-entropy.

Forward runs the depth-first kernel; backward recomputes through a
V-chunked reference (same pattern as the other kernels: fused forward,
recompute backward — the (T, V) logits are never stored)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.vocab_ce import ce as kernel_mod


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_nll(h, w, labels, block_rows: int = 128, block_v: int = 512,
              block_d: int = 512):
    """Mean masked NLL over (T, D) hidden states against a (D, V) head."""
    lse, gold = kernel_mod.fused_ce_fwd(
        h, w, labels, block_rows=block_rows, block_v=block_v,
        block_d=block_d)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _fwd(h, w, labels, block_rows, block_v, block_d):
    lse, gold = kernel_mod.fused_ce_fwd(
        h, w, labels, block_rows=block_rows, block_v=block_v,
        block_d=block_d)
    mask = (labels >= 0).astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    nll = jnp.sum((lse - gold) * mask) / denom
    return nll, (h, w, labels, lse, mask, denom)


def _bwd(block_rows, block_v, block_d, res, g):
    """d nll / dh = (softmax - onehot) W^T * mask / denom, computed in
    V-chunks against the saved logsumexp — O(T*D + chunk) memory."""
    h, w, labels, lse, mask, denom = res
    t, d = h.shape
    v = w.shape[1]
    scale = (g * mask / denom).astype(jnp.float32)          # (T,)
    safe = jnp.maximum(labels, 0)

    nv = -(-v // block_v)
    wpad = (-v) % block_v
    w_p = jnp.pad(w, ((0, 0), (0, wpad))) if wpad else w

    def chunk(carry, j):
        dh, dw = carry
        lo = j * block_v
        wc = jax.lax.dynamic_slice_in_dim(w_p, lo, block_v, axis=1)
        logits = h.astype(jnp.float32) @ wc.astype(jnp.float32)
        col = lo + jnp.arange(block_v)[None, :]
        p = jnp.exp(logits - lse[:, None])
        p = jnp.where(col < v, p, 0.0)
        onehot = (col == safe[:, None]) & (labels >= 0)[:, None]
        dlogits = (p - onehot.astype(jnp.float32)) * scale[:, None]
        dh = dh + dlogits @ wc.astype(jnp.float32).T
        dw = jax.lax.dynamic_update_slice_in_dim(
            dw, (h.astype(jnp.float32).T @ dlogits).astype(dw.dtype),
            lo, axis=1)
        return (dh, dw), None

    dh0 = jnp.zeros((t, d), jnp.float32)
    dw0 = jnp.zeros_like(w_p, jnp.float32)
    (dh, dw), _ = jax.lax.scan(
        functools.partial(chunk), (dh0, dw0), jnp.arange(nv))
    if wpad:
        dw = dw[:, :v]
    return dh.astype(h.dtype), dw.astype(w.dtype), None


fused_nll.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# Per-token gold log-probability — the kernel-registry entry point.
#
# The registry matches the *value* form of the loss tail
# (``take_along_axis(log_softmax(h @ w), labels)``), whose output is one
# gold log-prob per row, not the reduced mean — the user's own mask /
# mean ops stay in the graph downstream.  Forward runs the same fused
# (lse, gold) kernel; backward recomputes in V-chunks with a *per-token*
# cotangent instead of fused_nll's mask/denom scale.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_gold_logp(h, w, labels, block_rows: int = 128, block_v: int = 512,
                    block_d: int = 512):
    """Per-token ``log_softmax(h @ w)[t, labels[t]]`` (T,) f32; negative
    labels wrap python-style, matching ``jnp.take_along_axis``."""
    v = w.shape[1]
    wrapped = jnp.where(labels < 0, labels + v, labels).astype(jnp.int32)
    lse, gold = kernel_mod.fused_ce_fwd(
        h, w, wrapped, block_rows=block_rows, block_v=block_v,
        block_d=block_d)
    return gold - lse


def _glp_fwd(h, w, labels, block_rows, block_v, block_d):
    v = w.shape[1]
    wrapped = jnp.where(labels < 0, labels + v, labels).astype(jnp.int32)
    lse, gold = kernel_mod.fused_ce_fwd(
        h, w, wrapped, block_rows=block_rows, block_v=block_v,
        block_d=block_d)
    return gold - lse, (h, w, wrapped, lse)


def _glp_bwd(block_rows, block_v, block_d, res, g):
    """d logp / dlogits = onehot - softmax, scaled per token by ``g`` —
    computed in V-chunks against the saved logsumexp, O(T*D + chunk)."""
    h, w, wrapped, lse = res
    t, d = h.shape
    v = w.shape[1]
    scale = g.astype(jnp.float32)                           # (T,)

    nv = -(-v // block_v)
    wpad = (-v) % block_v
    w_p = jnp.pad(w, ((0, 0), (0, wpad))) if wpad else w

    def chunk(carry, j):
        dh, dw = carry
        lo = j * block_v
        wc = jax.lax.dynamic_slice_in_dim(w_p, lo, block_v, axis=1)
        logits = h.astype(jnp.float32) @ wc.astype(jnp.float32)
        col = lo + jnp.arange(block_v)[None, :]
        p = jnp.exp(logits - lse[:, None])
        p = jnp.where(col < v, p, 0.0)
        onehot = (col == wrapped[:, None]).astype(jnp.float32)
        dlogits = (onehot - p) * scale[:, None]
        dh = dh + dlogits @ wc.astype(jnp.float32).T
        dw = jax.lax.dynamic_update_slice_in_dim(
            dw, (h.astype(jnp.float32).T @ dlogits).astype(dw.dtype),
            lo, axis=1)
        return (dh, dw), None

    dh0 = jnp.zeros((t, d), jnp.float32)
    dw0 = jnp.zeros_like(w_p, jnp.float32)
    (dh, dw), _ = jax.lax.scan(chunk, (dh0, dw0), jnp.arange(nv))
    if wpad:
        dw = dw[:, :v]
    return dh.astype(h.dtype), dw.astype(w.dtype), None


fused_gold_logp.defvjp(_glp_fwd, _glp_bwd)
