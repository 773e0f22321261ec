"""Plain reference of deepseek-llm-7b (arXiv:2401.02954; a LLaMA-style
dense decoder, ``LlamaForCausalLM`` in its published ``config.json``),
written from the published description in ``jax.numpy`` alone.

Pre-norm blocks: ``h += attn(rmsnorm(h))``, ``h += mlp(rmsnorm(h))``;
multi-head attention (32 query and 32 key/value heads of 128) with
rotate-half RoPE (theta 10000) and a causal softmax; SwiGLU MLP
``down(silu(gate(x)) * up(x))``; a final RMSNorm (eps 1e-6) and an untied
head.  Every product runs in float32 at ``HIGHEST`` precision from the
bfloat16 weights, one layer at a time, so that 8 layers fit beside the
weights.  Departures: none in the mathematics; depth and the position
budget are cut as ``deepseek-7b.json`` records.

``quant="fp8"`` is the control: every linear layer (the attention
projections, the MLP and the head) takes its input and its weight rounded
to float8 e4m3, per token and per output channel, before a float32
product.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def init(cfg: dict, key) -> dict:
    """Weights in ``cfg["torch_dtype"]``, layers stacked on axis 0; call
    under ``jax.jit`` so one device program makes them all."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    v, n = cfg["vocab_size"], cfg["num_hidden_layers"]
    h = cfg["num_attention_heads"] * (d // cfg["num_attention_heads"])
    g = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    std = cfg["initializer_range"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    shapes = {"embed": (v, d), "wq": (n, d, h), "wk": (n, d, g),
              "wv": (n, d, g), "wo": (n, h, d), "wg": (n, d, f),
              "wu": (n, d, f), "wd": (n, f, d), "head": (d, v)}
    keys = dict(zip(shapes, jax.random.split(key, len(shapes))))
    w = {k: (std * jax.random.normal(keys[k], s, jnp.float32)).astype(dtype)
         for k, s in shapes.items()}
    w["attn_norm"] = jnp.ones((n, d), dtype)
    w["mlp_norm"] = jnp.ones((n, d), dtype)
    w["final_norm"] = jnp.ones((d,), dtype)
    return w


def _round_f8(x, axis):
    """``x`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (the slice's absolute maximum maps to e4m3's largest)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _round_f8(x, -1), _round_f8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rmsnorm(x, g, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, H, hd); rotate-half RoPE at positions 0..S-1."""
    s, _, hd = x.shape
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "theta",
                                             "quant"))
def _layer(x, w, i, *, heads, eps, theta, quant):
    s, d = x.shape
    hd = d // heads
    h = _rmsnorm(x, w["attn_norm"][i], eps)
    q = _rope(_linear(h, w["wq"][i], quant).reshape(s, heads, hd), theta)
    k = _rope(_linear(h, w["wk"][i], quant).reshape(s, -1, hd), theta)
    v = _linear(h, w["wv"][i], quant).reshape(s, -1, hd)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / hd ** 0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST)
    x = x + _linear(o.reshape(s, heads * hd), w["wo"][i], quant)
    h = _rmsnorm(x, w["mlp_norm"][i], eps)
    mlp = jax.nn.silu(_linear(h, w["wg"][i], quant)) * _linear(
        h, w["wu"][i], quant)
    return x + _linear(mlp, w["wd"][i], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, w, *, eps, quant):
    return _linear(_rmsnorm(x, w["final_norm"], eps), w["head"], quant)


def logits(w: dict, tokens, cfg: dict, quant: str | None = None):
    """(S,) token ids -> (S, vocab) float32 logits of every position."""
    layer_w = {k: w[k] for k in ("attn_norm", "wq", "wk", "wv", "wo",
                                 "mlp_norm", "wg", "wu", "wd")}
    x = w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_w, i, heads=cfg["num_attention_heads"], eps=eps,
                   theta=cfg["rope_theta"], quant=quant)
    return _head(x, {"final_norm": w["final_norm"], "head": w["head"]},
                 eps=eps, quant=quant)
