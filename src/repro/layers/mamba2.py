"""Mamba2 (SSD) mixer layer: projections, causal depthwise conv, SSD scan,
gated RMSNorm, out-projection.

The gated-norm epilogue ``y = rmsnorm(y * silu(z)) * scale`` is a BrainSlug
stack (silu → mul → row-norm) and runs through the fused dispatcher; the SSD
scan itself goes to the chunked Pallas kernel in ``brainslug`` mode and the
pure-JAX chunked path in ``xla`` mode.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.core import ir
from repro.kernels.fused_stack import ops as fused_ops
from repro.kernels.ssd import chunked as ssd_chunked
from repro.kernels.ssd import ops as ssd_ops
from repro.layers import base


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, di, n, h, cw = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                       cfg.ssm_heads, cfg.ssm_conv_width)
    ks = jax.random.split(key, 8)
    return {
        "wz": base.boxed(ks[0], (d, di), ("fsdp", "ffn"), dtype=dtype),
        "wx": base.boxed(ks[1], (d, di), ("fsdp", "ffn"), dtype=dtype),
        "wB": base.boxed(ks[2], (d, n), ("fsdp", None), dtype=dtype),
        "wC": base.boxed(ks[3], (d, n), ("fsdp", None), dtype=dtype),
        "wdt": base.boxed(ks[4], (d, h), ("fsdp", "heads"), dtype=dtype),
        "dt_bias": base.boxed(ks[4], (h,), ("heads",), init="zeros",
                              dtype=dtype),
        "conv_x": base.boxed(ks[5], (cw, di), (None, "ffn"),
                             scale=1.0 / cw ** 0.5, dtype=dtype),
        "conv_B": base.boxed(ks[5], (cw, n), (None, None),
                             scale=1.0 / cw ** 0.5, dtype=dtype),
        "conv_C": base.boxed(ks[6], (cw, n), (None, None),
                             scale=1.0 / cw ** 0.5, dtype=dtype),
        "A_log": base.boxed(ks[6], (h,), ("heads",), init="zeros",
                            dtype=jnp.float32),
        "D": base.boxed(ks[7], (h,), ("heads",), init="ones",
                        dtype=jnp.float32),
        "norm_scale": base.boxed(ks[7], (di,), ("ffn",), init="ones",
                                 dtype=dtype),
        "wo": base.boxed(ks[0], (di, d), ("ffn", "fsdp"),
                         scale=1.0 / di ** 0.5, dtype=dtype),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Depthwise causal conv.  x: (B, S, C); w: (cw, C)."""
    cw = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (cw - 1, 0), (0, 0)))
    y = jnp.zeros_like(x)
    for i in range(cw):
        y = y + xp[:, i: i + x.shape[1], :] * w[i]
    return y


@functools.lru_cache(maxsize=None)
def _gated_norm_program(eps: float) -> ir.StackProgram:
    return ir.StackProgram(
        name="gated_rmsnorm", inputs=("y", "z"), outputs=("o",),
        layout="rows",
        ops=(
            ir.OpNode(ir.OpKind.EW_UNARY, "gate_act", ("z",), "g", fn="silu"),
            ir.OpNode(ir.OpKind.EW_BINARY, "gate_mul", ("y", "g"), "m",
                      fn="mul"),
            ir.OpNode(ir.OpKind.ROW_NORM, "norm", ("m",), "o",
                      params=("scale",), attrs={"norm": "rms", "eps": eps}),
        ))


def _ssd_dispatch(xs, dt, A, B, C, D, rt: RuntimeConfig):
    if rt.mode == "brainslug":
        return ssd_ops.ssd(xs, dt, A, B, C, D, rt.ssd_chunk)
    return ssd_chunked.ssd_chunked(xs, dt, A, B, C, D, chunk=rt.ssd_chunk)


def apply(params, x: jnp.ndarray, cfg: ModelConfig, rt: RuntimeConfig
          ) -> jnp.ndarray:
    """Full-sequence mixer.  x: (B, S, D)."""
    b, s, _ = x.shape
    h, p = cfg.ssm_heads, cfg.ssm_head_dim
    z = jnp.einsum("bsd,de->bse", x, params["wz"])
    xs = jnp.einsum("bsd,de->bse", x, params["wx"])
    Bc = jnp.einsum("bsd,dn->bsn", x, params["wB"])
    Cc = jnp.einsum("bsd,dn->bsn", x, params["wC"])
    dt = jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", x, params["wdt"]).astype(jnp.float32)
        + params["dt_bias"].astype(jnp.float32))

    xs = jax.nn.silu(_causal_conv(xs, params["conv_x"]))
    Bc = jax.nn.silu(_causal_conv(Bc, params["conv_B"]))
    Cc = jax.nn.silu(_causal_conv(Cc, params["conv_C"]))

    A = -jnp.exp(params["A_log"])
    y = _ssd_dispatch(xs.reshape(b, s, h, p), dt, A, Bc, Cc, params["D"], rt)
    y = y.reshape(b, s, cfg.d_inner)

    out = fused_ops.fused_stack_apply(
        _gated_norm_program(1e-6), {"y": y, "z": z},
        {"scale": params["norm_scale"]}, mode=rt.mode)["o"]
    return jnp.einsum("bse,ed->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# Decode (recurrent) path
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class MambaCache:
    conv: jnp.ndarray       # (B, cw-1, di + 2n): rolling pre-conv inputs
    state: jnp.ndarray      # (B, H, N, P) f32 SSM state

    #: Decode-cache sharding declaration (see ``KVCache.CACHE_AXES``):
    #: recurrent state is purely per-slot, so only the slot dim shards.
    #: No "model" entry on purpose — the mixer's gated RMSNorm reduces
    #: over the full d_inner, so head-sharding the state would put a
    #: collective inside the norm (the dist.collective-placement fence).
    CACHE_AXES = {"conv": {"slot": -3}, "state": {"slot": -4}}


def init_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16
               ) -> MambaCache:
    return MambaCache(
        conv=jnp.zeros((batch, cfg.ssm_conv_width - 1,
                        cfg.d_inner + 2 * cfg.ssm_state), dtype),
        state=jnp.zeros((batch, cfg.ssm_heads, cfg.ssm_state,
                         cfg.ssm_head_dim), jnp.float32))


def decode(params, x_t: jnp.ndarray, cache: MambaCache, cfg: ModelConfig,
           rt: RuntimeConfig, *, active: jnp.ndarray | None = None
           ) -> tuple[jnp.ndarray, MambaCache]:
    """One recurrent step.  x_t: (B, 1, D).

    ``active`` (B,) bool freezes inactive slots' recurrent state (conv
    window + SSM state) — the mamba analogue of not advancing a KV cache.
    """
    b = x_t.shape[0]
    h, p, n, di = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                   cfg.d_inner)
    xt = x_t[:, 0]
    z = xt @ params["wz"]
    xs = xt @ params["wx"]
    Bc = xt @ params["wB"]
    Cc = xt @ params["wC"]
    dt = jax.nn.softplus((xt @ params["wdt"]).astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))

    new_in = jnp.concatenate([xs, Bc, Cc], axis=-1)          # (B, di+2n)
    window = jnp.concatenate(
        [cache.conv.astype(new_in.dtype), new_in[:, None]], axis=1)
    w_all = jnp.concatenate(
        [params["conv_x"], params["conv_B"], params["conv_C"]], axis=-1)
    conv_out = jnp.einsum("bwc,wc->bc", window, w_all)
    conv_out = jax.nn.silu(conv_out)
    xs_c, B_c, C_c = jnp.split(conv_out, [di, di + n], axis=-1)

    A = -jnp.exp(params["A_log"])
    state, y = ssd_chunked.ssd_decode_step(
        cache.state, xs_c.reshape(b, h, p), dt, A, B_c, C_c, params["D"])
    y = y.reshape(b, di)

    out = fused_ops.fused_stack_apply(
        _gated_norm_program(1e-6), {"y": y[:, None], "z": z[:, None]},
        {"scale": params["norm_scale"]}, mode=rt.mode)["o"]
    new_conv = window[:, 1:].astype(cache.conv.dtype)
    if active is not None:
        new_conv = jnp.where(active[:, None, None], new_conv, cache.conv)
        state = jnp.where(active[:, None, None, None], state, cache.state)
    new_cache = MambaCache(conv=new_conv, state=state)
    return (out[:, 0] @ params["wo"])[:, None], new_cache


jax.tree_util.register_dataclass(
    MambaCache, data_fields=["conv", "state"], meta_fields=[])
