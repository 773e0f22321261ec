"""Differentiable dispatch for the SSD mixer."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd import chunked as chunked_mod
from repro.kernels.ssd import ssd as kernel_mod


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd(x, dt, A, B, C, D, chunk: int = 64):
    """Pallas-accelerated SSD: intra-chunk work in the kernel, inter-chunk
    state scan at the JAX level.  Matches ``chunked.ssd_chunked`` /
    ``ref.ssd_ref`` bitwise up to f32 reassociation."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = x.shape[1]
    nc = sp // chunk

    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    Bf = B.astype(jnp.float32).reshape(b, nc, chunk, n)
    Cf = C.astype(jnp.float32).reshape(b, nc, chunk, n)
    Af = A.astype(jnp.float32)

    dtx = jnp.moveaxis(dtf[..., None] * xf, 3, 1)        # (b,h,nc,L,p)
    dta = dtf * Af[None, None, None, :]
    a = jnp.cumsum(dta, axis=2)                          # (b,nc,L,h)
    a_bh = jnp.moveaxis(a, 3, 1)[..., None]              # (b,h,nc,L,1)

    y_intra, S = kernel_mod.ssd_intra_chunk(dtx, a_bh, Bf, Cf)

    # inter-chunk state recurrence (tiny)
    lam = jnp.exp(jnp.moveaxis(a[:, :, -1], 2, 1))       # (b,h,nc)

    def step(hprev, inputs):
        lam_c, S_c = inputs
        return hprev * lam_c[..., None, None] + S_c, hprev

    h0 = jnp.zeros((b, h, n, p), jnp.float32)
    _, hprevs = jax.lax.scan(
        step, h0, (jnp.moveaxis(lam, 2, 0), jnp.moveaxis(S, 2, 0)))
    hprevs = jnp.moveaxis(hprevs, 0, 2)                  # (b,h,nc,n,p)

    y_inter = jnp.einsum("bcln,bhcl,bhcnp->bhclp",
                         Cf, jnp.exp(a_bh[..., 0]), hprevs)
    y = jnp.moveaxis(y_intra + y_inter, 1, 3).reshape(b, sp, h, p)[:, :s]
    if D is not None:
        y = y + D.astype(jnp.float32)[None, None, :, None] * \
            x.astype(jnp.float32).reshape(b, sp, h, p)[:, :s]
    return y.astype(x.dtype)


def _fwd(x, dt, A, B, C, D, chunk):
    return ssd(x, dt, A, B, C, D, chunk), (x, dt, A, B, C, D)


def _bwd(chunk, res, g):
    x, dt, A, B, C, D = res
    has_d = D is not None

    def f(x_, dt_, A_, B_, C_, D_):
        return chunked_mod.ssd_chunked(x_, dt_, A_, B_, C_,
                                       D_ if has_d else None, chunk=chunk)

    _, vjp = jax.vjp(f, x, dt, A, B, C,
                     D if has_d else jnp.zeros_like(A))
    dx, ddt, dA, dB, dC, dD = vjp(g)
    return dx, ddt, dA, dB, dC, (dD if has_d else None)


ssd.defvjp(_fwd, _bwd)
