"""Unified language-model assembly for every assigned architecture family.

One module covers dense / MoE / SSM / hybrid / audio-encoder / VLM because
they share the substrate: embedding (or modality-stub projection), a scanned
stack of blocks, fused BrainSlug norm/act chains, final norm, vocab head,
loss.  Family differences are *data*, not code paths:

* ``layer_plan(cfg)`` describes the repeating super-block (e.g. llama4:
  ``("attn_dense", "attn_moe")``; zamba2: 13 mamba + 1 shared-attn;
  granite-4.0-h: the config's ``layer_pattern`` of ten distinct layers,
  each a Mamba or attention mixer followed by an MoE FFN) and the
  heterogeneous tail.
* Blocks are scanned (``jax.lax.scan``) over stacked per-layer params —
  compile time and HLO size stay bounded for 64-81-layer models.
* The residual stream uses a (resid, pending) carry so every residual add
  fuses with the next norm (maximal BrainSlug stack coverage).

Decode mirrors the same plan with per-layer caches (KV or Mamba state)
stacked along the scan axis.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.layers import attention, base, dense, mamba2, moe, stacks


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerPlan:
    superblock: tuple[str, ...]     # kinds within one scanned super-block
    n_super: int
    tail: tuple[str, ...]           # unscanned remainder (hybrid only)

    @property
    def uses_shared_attn(self) -> bool:
        return "shared_attn" in self.superblock or "shared_attn" in self.tail


#: sub-layer kinds whose mixer is Mamba-2 (with recurrent decode state)
MAMBA_KINDS = ("mamba", "mamba_moe")


def layer_plan(cfg: ModelConfig) -> LayerPlan:
    if cfg.layer_pattern:
        p = len(cfg.layer_pattern)
        if cfg.n_layers % p:
            raise ValueError(f"{cfg.name}: n_layers % len(layer_pattern) "
                             f"!= 0")
        return LayerPlan(tuple(cfg.layer_pattern), cfg.n_layers // p, ())
    if cfg.family == "ssm":
        return LayerPlan(("mamba",), cfg.n_layers, ())
    if cfg.family == "hybrid":
        q = cfg.attn_layer_period
        n_super = cfg.n_layers // q
        tail = ("mamba",) * (cfg.n_layers % q)
        return LayerPlan(("mamba",) * (q - 1) + ("shared_attn",),
                         n_super, tail)
    if cfg.n_experts:
        p = cfg.moe_layer_period
        if cfg.n_layers % p:
            raise ValueError(f"{cfg.name}: n_layers % moe_layer_period != 0")
        return LayerPlan(("attn_dense",) * (p - 1) + ("attn_moe",),
                         cfg.n_layers // p, ())
    return LayerPlan(("attn_dense",), cfg.n_layers, ())


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_sub(key, kind: str, cfg: ModelConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    if kind == "mamba":
        return {"norm1": dense.norm_init(ks[0], cfg, dtype),
                "mixer": mamba2.init(ks[1], cfg, dtype)}
    p = {"norm1": dense.norm_init(ks[0], cfg, dtype),
         "norm2": dense.norm_init(ks[2], cfg, dtype)}
    if kind == "mamba_moe":
        p["mixer"] = mamba2.init(ks[1], cfg, dtype)
    else:
        p["attn"] = attention.init(ks[1], cfg, dtype)
    if kind.endswith("_moe"):
        p["moe"] = moe.init(ks[3], cfg, dtype)
    else:                                   # attn_dense / shared_attn
        p["mlp"] = dense.init(ks[3], cfg, dtype=dtype)
    return p


def init(key, cfg: ModelConfig) -> tuple[Any, Any]:
    """Returns (params, logical_axes) trees."""
    dtype = jnp.dtype(cfg.dtype)
    plan = layer_plan(cfg)
    keys = jax.random.split(key, 8)

    tree: dict[str, Any] = {}
    tree["embed"] = base.boxed(keys[0], (cfg.vocab_size, cfg.d_model),
                               ("vocab", None), dtype=dtype,
                               scale=0.02 if cfg.tie_embeddings else None)
    if not cfg.tie_embeddings:
        tree["out_head"] = base.boxed(
            keys[1], (cfg.d_model, cfg.vocab_size), (None, "vocab"),
            dtype=dtype)
    if cfg.frontend:
        tree["frontend_proj"] = base.boxed(
            keys[2], (cfg.frontend_dim, cfg.d_model), (None, None),
            dtype=dtype)
    tree["final_norm"] = dense.norm_init(keys[3], cfg, dtype)

    # scanned super-blocks
    blk_keys = jax.random.split(keys[4], max(plan.n_super, 1))
    blocks = []
    for i in range(plan.n_super):
        sub_keys = jax.random.split(blk_keys[i], len(plan.superblock))
        blk = {}
        for j, kind in enumerate(plan.superblock):
            if kind == "shared_attn":
                continue                    # params shared, stored once
            blk[f"sub{j}"] = _init_sub(sub_keys[j], kind, cfg, dtype)
        blocks.append(blk)
    if blocks and blocks[0]:
        tree["blocks"] = base.stack_layer_trees(blocks)
    if plan.uses_shared_attn:
        tree["shared_attn"] = _init_sub(keys[5], "shared_attn", cfg, dtype)
    if plan.tail:
        # tail kinds are all 'mamba' (hybrid remainder layers)
        tail = [{"sub0": _init_sub(k, "mamba", cfg, dtype)}
                for k in jax.random.split(keys[6], len(plan.tail))]
        tree["tail"] = base.stack_layer_trees(tail)
    return base.split(tree)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _add_norm(x, resid, p, cfg: ModelConfig, rt: RuntimeConfig):
    return stacks.add_norm(x, resid, p["scale"], p.get("bias"),
                           norm=cfg.norm, eps=cfg.norm_eps, mode=rt.mode)


def _final_norm(params, h, cfg: ModelConfig, rt: RuntimeConfig):
    p = params["final_norm"]
    return stacks.apply_norm(h, p["scale"], p.get("bias"), norm=cfg.norm,
                             eps=cfg.norm_eps, mode=rt.mode)


def _residual(x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """A sub-layer's output as it joins the residual stream (scaled in
    float32: 0.22 is not a bfloat16 number)."""
    if cfg.residual_multiplier == 1.0:
        return x
    return (x.astype(jnp.float32) * cfg.residual_multiplier).astype(x.dtype)


def _ffn(p, h, cfg: ModelConfig, rt: RuntimeConfig, *,
         dropless: bool = False):
    """The FFN after a mixer: (output, MoE aux or None)."""
    if "moe" not in p:
        return dense.apply(p["mlp"], h, cfg, rt), None
    # the scopes ("moe", "shared", "mamba", "attn") reach the compiled
    # step's op metadata, where device time is found by part
    with jax.named_scope("moe"):
        out, aux = moe.apply(p["moe"], h, cfg, rt, dropless=dropless)
    if cfg.shared_expert_ff:
        with jax.named_scope("shared"):
            out = out + moe.shared(p["moe"], h, cfg, rt)
    return out, aux


def _apply_sub(kind: str, p, carry, cfg: ModelConfig, rt: RuntimeConfig,
               shared_params=None):
    resid, pending, aux = carry
    if kind == "shared_attn":
        p = shared_params
    h1, resid = _add_norm(pending, resid, p["norm1"], cfg, rt)
    if kind in MAMBA_KINDS:
        with jax.named_scope("mamba"):
            mixed = _residual(mamba2.apply(p["mixer"], h1, cfg, rt), cfg)
        if kind == "mamba":
            return (resid, mixed, aux)
    else:
        with jax.named_scope("attn"):
            mixed = _residual(attention.apply(p["attn"], h1, cfg, rt), cfg)
    h2, resid = _add_norm(mixed, resid, p["norm2"], cfg, rt)
    out, moe_aux = _ffn(p, h2, cfg, rt)
    if moe_aux is not None:
        aux = {k: aux[k] + moe_aux[k] for k in aux}
    return (resid, _residual(out, cfg), aux)


def _remat(fn, rt: RuntimeConfig):
    if rt.remat == "full":
        return jax.checkpoint(fn)
    if rt.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def _embed(params, tokens, cfg: ModelConfig) -> jnp.ndarray:
    """Token embeddings times ``cfg.embedding_multiplier`` (unset:
    sqrt(d_model) for a tied table, else none)."""
    x = params["embed"][tokens]
    m = cfg.embedding_multiplier
    if m is None and cfg.tie_embeddings:
        m = cfg.d_model ** 0.5
    if m is not None:
        x = x * jnp.asarray(m, x.dtype)
    return x


def embed_inputs(params, batch: dict, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.frontend == "audio_frames":
        return batch["frames"] @ params["frontend_proj"]
    x = _embed(params, batch["tokens"], cfg)
    if cfg.frontend == "vision_patches":
        pre = batch["patches"] @ params["frontend_proj"]
        x = jnp.concatenate([pre.astype(x.dtype), x], axis=1)
    return x


def hidden(params, batch: dict, cfg: ModelConfig, rt: RuntimeConfig
           ) -> tuple[jnp.ndarray, dict]:
    """Backbone only: returns (final-normed hidden states, aux)."""
    plan = layer_plan(cfg)
    x = embed_inputs(params, batch, cfg)
    aux0 = {"router_aux_loss": jnp.zeros((), jnp.float32),
            "drop_fraction": jnp.zeros((), jnp.float32)}
    shared = params.get("shared_attn")

    def block_body(carry, blk_params):
        resid, pending, aux = carry
        for j, kind in enumerate(plan.superblock):
            p = blk_params.get(f"sub{j}")
            resid, pending, aux = _apply_sub(
                kind, p, (resid, pending, aux), cfg, rt, shared)
        return (resid, pending, aux), None

    body = _remat(block_body, rt)
    carry = (x, jnp.zeros_like(x), aux0)
    if "blocks" in params:
        carry, _ = jax.lax.scan(body, carry, params["blocks"])
    if "tail" in params:
        def tail_body(c, p):
            return (_apply_sub("mamba", p["sub0"], c, cfg, rt), None)
        carry, _ = jax.lax.scan(_remat(tail_body, rt), carry, params["tail"])
    resid, pending, aux = carry
    h = _final_norm(params, resid + pending, cfg, rt)
    return h, aux


def forward(params, batch: dict, cfg: ModelConfig, rt: RuntimeConfig
            ) -> tuple[jnp.ndarray, dict]:
    """Returns (logits, aux)."""
    h, aux = hidden(params, batch, cfg, rt)
    return _logits(params, h, cfg), aux


def prefill(params, batch: dict, cfg: ModelConfig, rt: RuntimeConfig
            ) -> jnp.ndarray:
    """Inference prefill: run the backbone over the full prompt, project
    only the last position (full-sequence logits are never materialized)."""
    h, _ = hidden(params, batch, cfg, rt)
    return _logits(params, h[:, -1:], cfg)


def _logits(params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", h, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["out_head"])
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return logits


def _nll_from_hidden(params, h, labels, cfg: ModelConfig,
                     chunk: int, unroll: bool = False) -> jnp.ndarray:
    """Masked next-token NLL.  ``chunk > 0`` computes the vocab projection
    and log-sum-exp in sequence chunks under jax.checkpoint, bounding the
    (B, S, V) f32 logits working set — the memory-roofline lever for the
    256k-vocab archs."""
    def chunk_nll(h_c, labels_c):
        lf = _logits(params, h_c, cfg).astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(
            lf, jnp.maximum(labels_c, 0)[..., None], axis=-1)[..., 0]
        mask = (labels_c >= 0).astype(jnp.float32)
        return jnp.sum((logz - gold) * mask), jnp.sum(mask)

    s = h.shape[1]
    if chunk <= 0 or s <= chunk or s % chunk:
        total, count = chunk_nll(h, labels)
        return total / jnp.maximum(count, 1.0)
    nc = s // chunk
    hc = h.reshape(h.shape[0], nc, chunk, h.shape[-1]).swapaxes(0, 1)
    lc = labels.reshape(labels.shape[0], nc, chunk).swapaxes(0, 1)
    body = jax.checkpoint(chunk_nll)

    def scan_body(carry, xs):
        t, c = body(*xs)
        return (carry[0] + t, carry[1] + c), None

    (total, count), _ = jax.lax.scan(
        scan_body, (jnp.zeros(()), jnp.zeros(())), (hc, lc),
        unroll=nc if unroll else 1)
    return total / jnp.maximum(count, 1.0)


def loss_fn(params, batch: dict, cfg: ModelConfig, rt: RuntimeConfig
            ) -> tuple[jnp.ndarray, dict]:
    """Next-token (or frame-label) cross entropy; labels < 0 are masked."""
    h, aux = hidden(params, batch, cfg, rt)
    labels = batch["labels"]
    if cfg.frontend == "vision_patches":
        h = h[:, -labels.shape[1]:]                 # text positions only
    if rt.mode == "brainslug" and not cfg.tie_embeddings:
        # depth-first fused CE kernel: the (T, V) logits never hit HBM
        from repro.kernels.vocab_ce import ops as ce_ops
        nll = ce_ops.fused_nll(
            h.reshape(-1, h.shape[-1]), params["out_head"],
            labels.reshape(-1), 128, 512, 512)
    else:
        nll = _nll_from_hidden(params, h, labels, cfg, rt.fused_loss_chunk,
                               unroll=rt.loss_unroll)
    loss = nll + cfg.router_aux_weight * aux["router_aux_loss"]
    metrics = {"loss": loss, "nll": nll, **aux}
    return loss, metrics


# ---------------------------------------------------------------------------
# Single-super-block entry points (roofline trip-count correction).
#
# XLA's cost_analysis counts a while-loop body ONCE.  The dry-run therefore
# lowers one scanned super-block straight-line (inner chunk scans unrolled via
# rt.scan_unroll) and adds (n_super - 1) x its cost to the full-step cost.
# ---------------------------------------------------------------------------

def superblock_fwd(blk_params, shared, x, cfg: ModelConfig,
                   rt: RuntimeConfig):
    """One super-block application on hidden states x (B, S, D)."""
    plan = layer_plan(cfg)
    aux = {"router_aux_loss": jnp.zeros((), jnp.float32),
           "drop_fraction": jnp.zeros((), jnp.float32)}
    carry = (x, jnp.zeros_like(x), aux)
    for j, kind in enumerate(plan.superblock):
        p = blk_params.get(f"sub{j}") if blk_params else None
        carry = _apply_sub(kind, p, carry, cfg, rt, shared)
    resid, pending, aux = carry
    return resid + pending, aux


def tail_fwd(tail_params, x, cfg: ModelConfig, rt: RuntimeConfig):
    """One tail (mamba) layer application (hybrid remainder)."""
    aux = {"router_aux_loss": jnp.zeros((), jnp.float32),
           "drop_fraction": jnp.zeros((), jnp.float32)}
    carry = _apply_sub("mamba", tail_params["sub0"], (x, jnp.zeros_like(x),
                                                      aux), cfg, rt)
    resid, pending, _ = carry
    return resid + pending


def superblock_decode(blk_params, shared, blk_cache, x, cfg: ModelConfig,
                      rt: RuntimeConfig):
    """One super-block decode step on x (B, 1, D) with this block's cache."""
    plan = layer_plan(cfg)
    carry = (x, jnp.zeros_like(x))
    new_cache = {}
    for j, kind in enumerate(plan.superblock):
        p = blk_params.get(f"sub{j}") if blk_params else None
        carry, new_cache[f"sub{j}"] = _decode_sub(
            kind, p, blk_cache[f"sub{j}"], carry, cfg, rt, shared)
    resid, pending = carry
    return resid + pending, new_cache


def tail_decode(tail_params, tail_cache, x, cfg: ModelConfig,
                rt: RuntimeConfig):
    carry, new_cache = _decode_sub(
        "mamba", tail_params["sub0"], tail_cache["sub0"],
        (x, jnp.zeros_like(x)), cfg, rt)
    resid, pending = carry
    return resid + pending, {"sub0": new_cache}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_decode_cache(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=None, *, kv_layout: str = "dense",
                      kv_num_blocks: int = 0,
                      kv_block_size: int = 16) -> dict:
    """Decode cache for every layer, stacked along the scan axis.

    ``dtype`` (default: the model's, ``cfg.dtype``, which ``init`` makes
    the weights in) is that of the K/V and the Mamba conv window; every
    value written there is already of the model's dtype, so a wider cache
    only moves more bytes.  The Mamba SSM state is float32 regardless.

    ``kv_layout="paged"`` swaps each attention layer's dense (B, G,
    max_len, hd) reservation for a pool of ``kv_num_blocks`` physical
    blocks of ``kv_block_size`` tokens; one logical block id addresses
    the same pool row in every layer (the pools are layer-stacked), so a
    single host-side block table serves the whole model.  Mamba layers
    keep their dense per-slot state either way — a recurrent state has no
    block structure to share."""
    plan = layer_plan(cfg)
    if kv_layout not in ("dense", "paged"):
        raise ValueError(f"unknown kv_layout {kv_layout!r}; "
                         f"allowed: 'dense' | 'paged'")
    if kv_layout == "paged" and kv_num_blocks < 1:
        raise ValueError("paged kv_layout requires kv_num_blocks >= 1")

    if dtype is None:
        dtype = jnp.dtype(cfg.dtype)

    def sub_cache(kind: str):
        if kind in MAMBA_KINDS:
            return mamba2.init_cache(cfg, batch, dtype)
        if kv_layout == "paged":
            return attention.init_paged_cache(cfg, batch, kv_num_blocks,
                                              kv_block_size, dtype)
        return attention.init_cache(cfg, batch, max_len, dtype)

    cache: dict[str, Any] = {}
    if plan.n_super:
        per_layer = [{f"sub{j}": sub_cache(kind)
                      for j, kind in enumerate(plan.superblock)}
                     for _ in range(plan.n_super)]
        cache["blocks"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per_layer)
    if plan.tail:
        per_tail = [{"sub0": sub_cache("mamba")} for _ in plan.tail]
        cache["tail"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per_tail)
    return cache


def reset_slots(cache: dict, mask: jnp.ndarray,
                lengths: jnp.ndarray | None = None) -> dict:
    """Reset the decode state of the batch slots where ``mask`` is True.

    Slot admission primitive for the continuous-batching engine.  Dense
    leaves (KV contents, per-slot length, mamba conv window and SSM state)
    are zeroed so a new request can prefill from position 0; every such
    leaf is layer-stacked, so batch is axis 1: (L, B, ...).

    Paged KV state is block-mapped: the pool is shared, so a freed slot
    returns its blocks on the *host* (allocator free list) and only its
    logical ``length`` is rewritten here — to 0, or to ``lengths[b]``
    when prefix sharing admits the slot mid-prompt (the shared blocks
    already hold its first ``lengths[b]`` positions)."""
    def leaf(x):
        m = mask.reshape((1, -1) + (1,) * (x.ndim - 2))
        return jnp.where(m, jnp.zeros((), x.dtype), x)

    def node(c):
        if isinstance(c, attention.PagedKVCache):
            new_len = (jnp.zeros_like(c.length) if lengths is None
                       else lengths.astype(c.length.dtype))
            return attention.PagedKVCache(
                k_pool=c.k_pool, v_pool=c.v_pool,
                length=jnp.where(mask[None, :], new_len, c.length))
        return leaf(c)

    return jax.tree_util.tree_map(
        node, cache,
        is_leaf=lambda x: isinstance(x, attention.PagedKVCache))


def copy_blocks(cache: dict, src: jnp.ndarray, dst: jnp.ndarray) -> dict:
    """Copy physical KV block ``src`` -> ``dst`` in every paged layer pool
    (the copy-on-write fork primitive: the engine allocates ``dst``,
    copies, and remaps the writing slot's table before the dispatch that
    would have written into the shared ``src``).  Non-paged leaves are
    untouched; ``src``/``dst`` are int32 scalars so one jitted trace
    serves every fork."""
    def node(c):
        if isinstance(c, attention.PagedKVCache):
            return attention.PagedKVCache(
                k_pool=c.k_pool.at[:, dst].set(c.k_pool[:, src]),
                v_pool=c.v_pool.at[:, dst].set(c.v_pool[:, src]),
                length=c.length)
        return c

    return jax.tree_util.tree_map(
        node, cache,
        is_leaf=lambda x: isinstance(x, attention.PagedKVCache))


def _decode_sub(kind: str, p, cache, carry, cfg, rt, shared_params=None,
                active=None, block_table=None):
    """One sub-layer of a decode step.  ``carry`` is ``(resid, pending)``,
    or ``(resid, pending, held)`` where ``held`` counts the expert
    assignments of active lanes that landed on the experts held here."""
    resid, pending = carry[:2]
    if kind == "shared_attn":
        p = shared_params
    h1, resid = _add_norm(pending, resid, p["norm1"], cfg, rt)
    if kind in MAMBA_KINDS:
        with jax.named_scope("mamba"):
            mixed, cache = mamba2.decode(p["mixer"], h1, cache, cfg, rt,
                                         active=active)
    else:
        with jax.named_scope("attn"):
            mixed, cache = attention.decode(p["attn"], h1, cache, cfg, rt,
                                            active=active,
                                            block_table=block_table)
    mixed = _residual(mixed, cfg)
    if kind == "mamba":
        return (resid, mixed) + carry[2:], cache
    h2, resid = _add_norm(mixed, resid, p["norm2"], cfg, rt)
    # serving is dropless: dropping a live request's token to a capacity
    # limit is a training-only trade-off
    out, moe_aux = _ffn(p, h2, cfg, rt, dropless=True)
    rest = carry[2:]
    if rest and moe_aux is not None:
        held = moe_aux["held"][:, 0]
        if active is not None:
            held = jnp.where(active, held, 0)
        rest = (rest[0] + jnp.sum(held),)
    return (resid, _residual(out, cfg)) + rest, cache


# ---------------------------------------------------------------------------
# Plain-jnp twins for the traced frontend (repro.api.optimize) — the LM
# analogue of models/cnn.py's vgg_fn: ordinary tensor code whose traced
# graph the kernel registry must rewrite onto the dedicated kernels
# (attention softmax·V -> flash, rmsnorm·g -> fused rmsnorm, the GLU gate
# -> fused swiglu, the log_softmax/gather loss tail -> fused vocab-CE).
# ---------------------------------------------------------------------------

def transformer_block_params(key, d_model: int, n_heads: int, d_ff: int,
                             dtype=jnp.float32) -> dict:
    """Parameter dict for :func:`transformer_block_fn` (pre-norm attention
    + SwiGLU MLP; rms scales initialized near 1)."""
    del n_heads                     # the layout is head-count agnostic
    ks = jax.random.split(key, 8)
    dk = lambda k, i, o: jax.random.normal(k, (i, o), dtype) / (i ** 0.5)
    return {
        "norm1_g": 1.0 + 0.1 * jax.random.normal(ks[0], (d_model,), dtype),
        "wq": dk(ks[1], d_model, d_model),
        "wk": dk(ks[2], d_model, d_model),
        "wv": dk(ks[3], d_model, d_model),
        "wo": dk(ks[4], d_model, d_model),
        "norm2_g": 1.0 + 0.1 * jax.random.normal(ks[5], (d_model,), dtype),
        "w_gate": dk(ks[6], d_model, d_ff),
        "w_up": dk(ks[7], d_model, d_ff),
        "w_down": dk(jax.random.fold_in(key, 99), d_ff, d_model),
    }


def transformer_block_fn(x: jnp.ndarray, params: dict, *, n_heads: int = 4,
                         causal: bool = True,
                         eps: float = 1e-6) -> jnp.ndarray:
    """Plain-jnp pre-norm transformer block: what a user would write.

    ``x`` is (B, S, D).  Attention is multi-head with an additive causal
    mask; the MLP is SwiGLU.  ``repro.api.optimize`` of this function must
    dispatch attention, both rmsnorms and the swiglu gate through the
    kernel registry and match this raw function to 2e-4.
    """
    b, s, d = x.shape
    dh = d // n_heads

    def rms(v, g):
        var = jnp.mean(jnp.square(v), axis=-1, keepdims=True)
        return v * jax.lax.rsqrt(var + eps) * g

    def heads(t):                               # (B,S,D) -> (B,H,S,dh)
        return t.reshape(b, s, n_heads, dh).transpose(0, 2, 1, 3)

    h = rms(x, params["norm1_g"])
    q, k, v = (heads(h @ params[w]) for w in ("wq", "wk", "wv"))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (1.0 / (dh ** 0.5))
    if causal:
        mask = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :],
                         0.0, -1e30)
        scores = scores + mask
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + o @ params["wo"]

    h2 = rms(x, params["norm2_g"])
    y = jax.nn.silu(h2 @ params["w_gate"]) * (h2 @ params["w_up"])
    return x + y @ params["w_down"]


def ce_loss_fn(h: jnp.ndarray, w: jnp.ndarray,
               labels: jnp.ndarray) -> jnp.ndarray:
    """Plain-jnp masked-mean CE tail over (T, D) hiddens and a (D, V)
    head — the registry rewrites the logits -> log_softmax -> gather core
    onto the fused vocab-CE kernel (the (T, V) logits never materialize);
    the mask / mean stay ordinary traced ops."""
    logits = h @ w
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                               axis=-1)[:, 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(-gold * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def tp_local_config(cfg: ModelConfig, model_extent: int) -> ModelConfig:
    """Region-local config for an attention-tensor-parallel shard_map body.

    Inside the region every shard holds ``n_heads / m`` query heads and
    ``n_kv_heads / m`` KV heads, and the attention projections reshape by
    ``cfg.n_heads`` — so the body must run against a localized config.
    ``d_head`` is pinned to the global head size first: for configs that
    derive it as ``d_model // n_heads``, halving ``n_heads`` must not
    change the per-head width.
    """
    if model_extent <= 1:
        return cfg
    if cfg.n_heads % model_extent or cfg.n_kv_heads % model_extent:
        raise ValueError(
            f"{cfg.name}: heads ({cfg.n_heads}, kv {cfg.n_kv_heads}) not "
            f"divisible by model={model_extent}")
    return dataclasses.replace(
        cfg, d_head=cfg.head_dim,
        n_heads=cfg.n_heads // model_extent,
        n_kv_heads=cfg.n_kv_heads // model_extent)


#: Attention projection leaves and the dim "model" shards when the serve
#: plan tensor-parallelizes heads: q/k/v projections (and their biases)
#: split their *output* columns per head-group; wo splits its input rows,
#: closed by one psum after the out-projection (see layers.attention).
_TP_COL_LEAVES = frozenset({"wq", "wk", "wv", "bq", "bk", "bv"})
_TP_ROW_LEAVES = frozenset({"wo"})


def tp_param_specs(params: Any, model_extent: int) -> Any:
    """PartitionSpec tree (congruent with ``params``) for attention-only
    tensor parallelism: projection leaves under an ``"attn"`` subtree
    shard over "model"; everything else — norms, MLP/MoE, mamba mixers,
    embeddings, the vocab head — replicates (the mamba gated norm reduces
    over the full d_inner, so its state must stay whole per shard)."""
    from jax.sharding import PartitionSpec as P

    def walk(node: Any, in_attn: bool, name: str) -> Any:
        if isinstance(node, dict):
            return {k: walk(v, in_attn or k == "attn", k)
                    for k, v in node.items()}
        parts: list = [None] * node.ndim
        if in_attn and model_extent > 1:
            if name in _TP_COL_LEAVES:
                parts[-1] = "model"
            elif name in _TP_ROW_LEAVES:
                parts[-2] = "model"
        return P(*parts)

    return walk(params, False, "")


def decode_step(params, cache: dict, tokens_t: jnp.ndarray,
                cfg: ModelConfig, rt: RuntimeConfig,
                active: jnp.ndarray | None = None,
                block_tables: jnp.ndarray | None = None, *,
                count_experts: bool = False) -> tuple:
    """One serving step: tokens_t (B, 1) -> (logits (B, 1, V), new cache).

    ``active`` is an optional (B,) bool slot mask for mixed continuous-
    batching dispatches: inactive slots compute (the batch shape is static)
    but their per-slot cache state — KV write/length, mamba conv window and
    SSM state — is frozen, so one compiled step serves any mix of
    prefilling, decoding and idle slots.

    ``block_tables`` (B, MB) int32 is required for (and only for) a paged
    KV cache: one table addresses every layer's pool, closed over as a
    scan constant.

    ``count_experts=True`` returns a third value: the int32 count of the
    active lanes' expert assignments that landed on the experts held here
    (``cfg.expert_share``), over every MoE layer.
    """
    plan = layer_plan(cfg)
    x = _embed(params, tokens_t, cfg)
    shared = params.get("shared_attn")
    new_cache: dict[str, Any] = {}

    def block_body(carry, scanned):
        blk_params, blk_cache = scanned
        out_cache = {}
        for j, kind in enumerate(plan.superblock):
            p = blk_params.get(f"sub{j}")
            carry, out_cache[f"sub{j}"] = _decode_sub(
                kind, p, blk_cache[f"sub{j}"], carry, cfg, rt, shared,
                active, block_tables)
        return carry, out_cache

    carry = (x, jnp.zeros_like(x))
    if count_experts:
        carry += (jnp.zeros((), jnp.int32),)
    if "blocks" in params:
        carry, new_cache["blocks"] = jax.lax.scan(
            block_body, carry, (params["blocks"], cache["blocks"]))
    if "tail" in params:
        def tail_body(c, scanned):
            p, cc = scanned
            c, out = _decode_sub("mamba", p["sub0"], cc["sub0"], c, cfg, rt,
                                 active=active)
            return c, {"sub0": out}
        carry, new_cache["tail"] = jax.lax.scan(
            tail_body, carry, (params["tail"], cache["tail"]))
    h = _final_norm(params, carry[0] + carry[1], cfg, rt)
    if count_experts:
        return _logits(params, h, cfg), new_cache, carry[2]
    return _logits(params, h, cfg), new_cache
