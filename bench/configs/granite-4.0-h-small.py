"""Plain reference of IBM Granite 4.0-H Small (``granitemoehybrid``; its
published ``config.json``), written from the published description in
``jax.numpy`` alone.

Layers follow ``layer_types``: a Mamba-2 or an attention mixer, then a
mixture of experts beside a shared expert, each joining the residual
stream scaled by ``residual_multiplier``:

    h = embed[tok] * embedding_multiplier
    h = h + residual_multiplier * mixer(rmsnorm(h))
    h = h + residual_multiplier * (moe(x) + shared(x)),  x = rmsnorm(h)
    logits = rmsnorm(h) @ embed.T / logits_scaling

* Mamba-2, one token at a time (a ``lax.scan`` of the recurrence, not the
  chunked form): ``[z, x, B, C, dt] = x @ W_in``; ``[x, B, C]`` through a
  causal depthwise conv of width ``mamba_d_conv`` with a bias (the last
  tap on the current token), then SiLU; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; per head ``S = exp(dt A) S + dt (B outer x)``,
  ``y = C S + D x``; ``y = rmsnorm(y * silu(z)) * w`` over all of
  ``d_inner`` (one group); ``out = y @ W_out``.
* Attention: causal grouped-query attention (query head ``i`` reads
  key/value head ``i // (heads / kv_heads)``), no positional encoding
  (``position_embedding_type: nope``), scores scaled by
  ``attention_multiplier``, no biases.
* Experts: router logits over every expert (``router_experts``), the
  ``num_experts_per_tok`` largest, a softmax over those; each expert is
  ``down(silu(gate x) * up x)``, as is the shared expert.

Every product runs in float32 at ``HIGHEST`` precision from the bfloat16
weights, one layer at a time.  Departure from the source: only the
experts this chip holds (``first_held_expert`` onwards,
``num_local_experts`` of them) are computed, as a masked sum whose gates
are still normalised over all ten choices; what the other chips' experts
would add is left out, as in the program.  Depth and positions are cut as
``granite-4.0-h-small.json`` records.

Weights are stored by position in the period of ``layer_types`` (the
shortest that repeats it), each leaf stacked over the periods on axis 0.

``quant="fp8"`` is the control: every linear layer (the Mamba and
attention projections, the router, the experts, the shared expert and the
head) takes its input and its weight rounded to float8 e4m3, per token and
per output channel, before a float32 product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def period(cfg: dict) -> int:
    """The shortest period of ``layer_types``."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and all(kinds[i] == kinds[i % p]
                                      for i in range(n)))


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return {"d": d, "hd": d // heads, "heads": heads,
            "kv": cfg["num_key_value_heads"],
            "di": cfg["mamba_n_heads"] * cfg["mamba_d_head"],
            "mh": cfg["mamba_n_heads"], "mp": cfg["mamba_d_head"],
            "n": cfg["mamba_d_state"], "cw": cfg["mamba_d_conv"],
            "e": cfg["num_local_experts"], "f": cfg["intermediate_size"],
            "fs": cfg["shared_intermediate_size"],
            "router": cfg["router_experts"], "v": cfg["vocab_size"]}


def _layer_shapes(kind: str, s: dict) -> dict:
    d = s["d"]
    out = {"input_norm": (d,), "post_norm": (d,),
           "router": (d, s["router"]),
           "wg": (s["e"], d, s["f"]), "wu": (s["e"], d, s["f"]),
           "wd": (s["e"], s["f"], d),
           "shared_wg": (d, s["fs"]), "shared_wu": (d, s["fs"]),
           "shared_wd": (s["fs"], d)}
    if kind == "attention":
        out.update(wq=(d, s["heads"] * s["hd"]), wk=(d, s["kv"] * s["hd"]),
                   wv=(d, s["kv"] * s["hd"]), wo=(s["heads"] * s["hd"], d))
    else:
        di, n, mh = s["di"], s["n"], s["mh"]
        out.update(wz=(d, di), wx=(d, di), wB=(d, n), wC=(d, n),
                   wdt=(d, mh), dt_bias=(mh,), conv_x=(s["cw"], di),
                   conv_B=(s["cw"], n), conv_C=(s["cw"], n),
                   conv_bias=(di + 2 * n,), A_log=(mh,), D=(mh,),
                   gate_norm=(di,), w_out=(di, d))
    return out


def _init_leaf(name: str, shape: tuple, key, std: float, dtype):
    """Matrices (the embedding among them) N(0, std); norms 1;
    Mamba-2's own initialisation for ``A_log`` (A uniform in [1, 16]),
    ``dt_bias`` (softplus^-1 of dt log-uniform in [0.001, 0.1]) and ``D``
    (1), kept in float32."""
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1, 16))
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init(cfg: dict, key) -> dict:
    """Weights in ``cfg["torch_dtype"]``; call under ``jax.jit`` so one
    device program makes them all."""
    s = sizes(cfg)
    p = period(cfg)
    periods = cfg["num_hidden_layers"] // p
    std = cfg["initializer_range"]
    dtype = jnp.dtype(cfg["torch_dtype"])
    k_embed, k_layers = jax.random.split(key)
    layers = []
    for j, lk in enumerate(jax.random.split(k_layers, p)):
        shapes = _layer_shapes(cfg["layer_types"][j], s)
        keys = dict(zip(shapes, jax.random.split(lk, len(shapes))))
        layers.append({
            name: _init_leaf(name, (periods,) + shape, keys[name], std, dtype)
            for name, shape in shapes.items()})
    return {"embed": _init_leaf("embed", (s["v"], s["d"]), k_embed, std,
                                dtype),
            "final_norm": jnp.ones((s["d"],), dtype), "layers": layers}


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


def _mamba(x, w, s, eps, quant):
    """x: (S, d) -> (S, d), one token at a time."""
    n_tok = x.shape[0]
    di, n, mh, mp, cw = s["di"], s["n"], s["mh"], s["mp"], s["cw"]
    z = _linear(x, w["wz"], quant)
    xbc = jnp.concatenate([_linear(x, w["wx"], quant),
                           _linear(x, w["wB"], quant),
                           _linear(x, w["wC"], quant)], axis=-1)
    dt = jax.nn.softplus(_linear(x, w["wdt"], quant)
                         + w["dt_bias"].astype(jnp.float32))
    conv_w = jnp.concatenate([w["conv_x"], w["conv_B"], w["conv_C"]],
                             axis=-1).astype(jnp.float32)   # (cw, di+2n)
    padded = jnp.concatenate([jnp.zeros((cw - 1, xbc.shape[1])), xbc])
    conv = sum(padded[i:i + n_tok] * conv_w[i] for i in range(cw))
    xbc = jax.nn.silu(conv + w["conv_bias"].astype(jnp.float32))
    xs, b, c = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
    a = -jnp.exp(w["A_log"].astype(jnp.float32))
    d_skip = w["D"].astype(jnp.float32)

    def step(state, inp):                       # state: (mh, n, mp)
        x_t, b_t, c_t, dt_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None])
        y = jnp.einsum("n,hnp->hp", c_t, state, precision=HIGHEST)
        return state, y + d_skip[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((mh, n, mp), jnp.float32),
                        (xs.reshape(n_tok, mh, mp), b, c, dt))
    y = y.reshape(n_tok, di) * jax.nn.silu(z)
    return _linear(_rmsnorm(y, w["gate_norm"], eps), w["w_out"], quant)


def _attention(x, w, s, multiplier, quant):
    """Causal GQA without positional encoding; one key/value group at a
    time, so that the scores of 4096 positions fit."""
    n_tok = x.shape[0]
    heads, kv, hd = s["heads"], s["kv"], s["hd"]
    rep = heads // kv
    q = _linear(x, w["wq"], quant).reshape(n_tok, kv, rep, hd)
    k = _linear(x, w["wk"], quant).reshape(n_tok, kv, hd)
    v = _linear(x, w["wv"], quant).reshape(n_tok, kv, hd)
    causal = jnp.arange(n_tok)[:, None] >= jnp.arange(n_tok)[None, :]

    def group(args):
        qg, kg, vg = args                   # (S, rep, hd), (S, hd), (S, hd)
        scores = jnp.einsum("qrd,kd->rqk", qg, kg,
                            precision=HIGHEST) * multiplier
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->qrd", probs, vg, precision=HIGHEST)

    o = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                            v.transpose(1, 0, 2)))     # (kv, S, rep, hd)
    o = o.transpose(1, 0, 2, 3).reshape(n_tok, heads * hd)
    return _linear(o, w["wo"], quant)


def _glu(x, wg, wu, wd, quant):
    return _linear(jax.nn.silu(_linear(x, wg, quant))
                   * _linear(x, wu, quant), wd, quant)


def _experts(x, w, s, first, top_k, quant):
    """The held experts' part of the routed sum: each token's gates are
    the softmax over its ``top_k`` router logits (of all experts); an
    expert held here adds its output times its gate where chosen."""
    logits = _linear(x, w["router"], quant)             # (S, router)
    top, idx = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(x)
    for e in range(s["e"]):
        g = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        out = out + g[:, None] * _glu(x, w["wg"][e], w["wu"][e],
                                      w["wd"][e], quant)
    return out


@functools.partial(jax.jit, static_argnames=("kind", "cfg_items",
                                             "quant"))
def _layer(h, w, p, *, kind, cfg_items, quant):
    cfg = dict(cfg_items)
    s = sizes(cfg)
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    w = {k: v[p] for k, v in w.items()}
    x = _rmsnorm(h, w["input_norm"], eps)
    if kind == "attention":
        mixed = _attention(x, w, s, cfg["attention_multiplier"], quant)
    else:
        mixed = _mamba(x, w, s, eps, quant)
    h = h + res * mixed
    x = _rmsnorm(h, w["post_norm"], eps)
    ffn = _experts(x, w, s, cfg["first_held_expert"],
                   cfg["num_experts_per_tok"], quant)
    ffn = ffn + _glu(x, w["shared_wg"], w["shared_wu"], w["shared_wd"],
                     quant)
    return h + res * ffn


@functools.partial(jax.jit, static_argnames=("eps", "scaling", "quant"))
def _head(h, w, *, eps, scaling, quant):
    x = _rmsnorm(h, w["final_norm"], eps)
    return _linear(x, w["embed"].T, quant) / scaling


_SCALARS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "mamba_n_heads", "mamba_d_head", "mamba_d_state",
            "mamba_d_conv", "num_local_experts", "intermediate_size",
            "shared_intermediate_size", "router_experts", "vocab_size",
            "rms_norm_eps", "residual_multiplier", "attention_multiplier",
            "first_held_expert", "num_experts_per_tok")


def logits(w: dict, tokens, cfg: dict, quant: str | None = None):
    """(S,) token ids -> (S, vocab) float32 logits of every position."""
    items = tuple((k, cfg[k]) for k in _SCALARS)
    p = period(cfg)
    h = (w["embed"][jnp.asarray(tokens)].astype(jnp.float32)
         * cfg["embedding_multiplier"])
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, w["layers"][i % p], i // p,
                   kind=cfg["layer_types"][i], cfg_items=items, quant=quant)
    return _head(h, {"final_norm": w["final_norm"], "embed": w["embed"]},
                 eps=cfg["rms_norm_eps"], scaling=cfg["logits_scaling"],
                 quant=quant)
