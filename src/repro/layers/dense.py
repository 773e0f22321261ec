"""Dense MLP blocks (gated and plain) and norm parameter helpers."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.layers import base, stacks

GATED_ACTS = ("silu", "gelu")


def is_gated(cfg: ModelConfig) -> bool:
    # llama/qwen/deepseek/gemma use gated MLPs; hubert (encoder) and
    # minitron (squared-relu) use plain two-matmul MLPs.
    return cfg.act in GATED_ACTS and not cfg.is_encoder


def init(key, cfg: ModelConfig, d_ff: int | None = None,
         dtype=jnp.float32) -> dict:
    d = cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if is_gated(cfg):
        return {
            "wg": base.boxed(ks[0], (d, f), ("fsdp", "ffn"), dtype=dtype),
            "wu": base.boxed(ks[1], (d, f), ("fsdp", "ffn"), dtype=dtype),
            "wd": base.boxed(ks[2], (f, d), ("ffn", "fsdp"), dtype=dtype,
                             scale=1.0 / f ** 0.5),
        }
    return {
        "wu": base.boxed(ks[0], (d, f), ("fsdp", "ffn"), dtype=dtype),
        "bu": base.boxed(ks[1], (f,), ("ffn",), init="zeros", dtype=dtype),
        "wd": base.boxed(ks[2], (f, d), ("ffn", "fsdp"), dtype=dtype,
                         scale=1.0 / f ** 0.5),
        "bd": base.boxed(ks[1], (d,), (None,), init="zeros", dtype=dtype),
    }


def apply(params, x: jnp.ndarray, cfg: ModelConfig, rt: RuntimeConfig
          ) -> jnp.ndarray:
    if "wg" in params:
        gate = jnp.einsum("bsd,df->bsf", x, params["wg"])
        up = jnp.einsum("bsd,df->bsf", x, params["wu"])
        h = stacks.glu(gate, up, act=cfg.act, mode=rt.mode)
        return jnp.einsum("bsf,fd->bsd", h, params["wd"])
    h = jnp.einsum("bsd,df->bsf", x, params["wu"]) + params["bu"]
    h = stacks.activation(h, act=cfg.act, mode=rt.mode)
    return jnp.einsum("bsf,fd->bsd", h, params["wd"]) + params["bd"]


def norm_init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    p = {"scale": base.boxed(key, (cfg.d_model,), (None,), init="ones",
                             dtype=dtype)}
    if cfg.norm == "layer":
        p["bias"] = base.boxed(key, (cfg.d_model,), (None,), init="zeros",
                               dtype=dtype)
    return p
