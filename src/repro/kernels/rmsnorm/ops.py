"""Differentiable dispatch for fused residual+RMSNorm."""
from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm import ref as ref_mod
from repro.kernels.rmsnorm import rmsnorm as kernel_mod


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def rmsnorm(x, scale, residual=None, eps: float = 1e-6,
            block_rows: int = 256):
    return kernel_mod.rmsnorm_fwd(x, scale, residual, eps=eps,
                                  block_rows=block_rows)


def _fwd(x, scale, residual, eps, block_rows):
    out = rmsnorm(x, scale, residual, eps, block_rows)
    return out, (x, scale, residual)


def _bwd(eps, block_rows, res, g):
    x, scale, residual = res
    if residual is None:
        def f(x_, s_):
            return ref_mod.rmsnorm_ref(x_, s_, None, eps=eps)
        _, vjp = jax.vjp(f, x, scale)
        dx, ds = vjp(g)
        return dx, ds, None
    def f(x_, s_, r_):
        return ref_mod.rmsnorm_ref(x_, s_, r_, eps=eps)
    _, vjp = jax.vjp(f, x, scale, residual)
    return vjp(g)


rmsnorm.defvjp(_fwd, _bwd)


def rmsnorm_value(x, scale, *, eps: float = 1e-6, block_rows: int = 256):
    """Normalized value only (no residual stream) — the kernel-registry
    entry point for the traced ``x * rsqrt(mean(x^2) + eps) * g`` idiom."""
    y, _ = rmsnorm(x, scale, None, eps, block_rows)
    return y
