"""Token-choice top-k MoE with static capacity (sort-based dispatch).

Dispatch is the sort-based static-capacity scheme: flatten (token, choice)
assignments, rank each within its expert via one argsort, drop ranks beyond
the capacity ``C = ceil(T·k/E · capacity_factor)``, and gather tokens into
an ``(E, C, D)`` expert batch.  Memory is O(T·k + E·C·D) — no (T, E, C)
one-hot dispatch tensor — which keeps the roofline memory term sane for
128-expert llama4.

Expert weights carry logical axes ("experts", None, "ffn"): experts shard
over the *data* axis (expert parallelism), the expert-FFN hidden dim over
the *model* axis (TP within experts).  The router aux (load-balance) loss
and drop fraction are returned for logging.

Expert share (``cfg.expert_share``): a layer told that it holds experts
``[first, first + count)`` of ``n_experts`` routes over all of them (the
router keeps every output and ``top_k``, and the gates are normalised over
all ``top_k`` choices) and computes only its own experts' terms; an
assignment to an absent expert contributes nothing here.  That partial
sum is the layer's output: nothing stands in for the other chips.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RuntimeConfig
from repro.layers import base, dense, stacks


def init(key, cfg: ModelConfig, dtype=jnp.float32) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.held_experts[1]
    ks = jax.random.split(key, 5)
    p = {
        "router": base.boxed(ks[0], (d, cfg.n_experts), ("fsdp", None),
                             dtype=dtype),
        "wg": base.boxed(ks[1], (e, d, f), ("experts", None, "ffn"),
                         dtype=dtype, scale=1.0 / d ** 0.5),
        "wu": base.boxed(ks[2], (e, d, f), ("experts", None, "ffn"),
                         dtype=dtype, scale=1.0 / d ** 0.5),
        "wd": base.boxed(ks[3], (e, f, d), ("experts", "ffn", None),
                         dtype=dtype, scale=1.0 / f ** 0.5),
    }
    if cfg.shared_expert_ff:
        p["shared"] = dense.init(ks[4], cfg, d_ff=cfg.shared_expert_ff,
                                 dtype=dtype)
    return p


def _constrain(t: jnp.ndarray, rt: RuntimeConfig) -> jnp.ndarray:
    """Pin the layout of a (G, E, C, ...) dispatch tensor.  Left alone,
    GSPMD replicates the batched token gather over every device (measured:
    2x 60 GiB per layer on granite prefill).  'tokens' keeps slots sharded
    by group on the data axis (expert weights replicated over data);
    'experts' reshards slot tensors expert-major (expert parallelism: one
    all-to-all in, one out — right when n_experts divides the data axis)."""
    P = jax.sharding.PartitionSpec
    if rt.moe_constraint == "tokens":
        spec = P("data", *([None] * (t.ndim - 1)))
    elif rt.moe_constraint == "experts":
        spec = P(None, "data", *([None] * (t.ndim - 2)))
    else:
        return t
    return jax.lax.with_sharding_constraint(t, spec)


def capacity(cfg: ModelConfig, n_tokens: int, *,
             dropless: bool = False) -> int:
    """Static expert capacity.  ``dropless=True`` sizes slots for the worst
    case (every token on one expert) — the decode/serving semantic, where
    dropping a live request's token is not acceptable."""
    if dropless:
        c = n_tokens
    else:
        c = int(n_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(8, -(-c // 8) * 8)          # round up to 8 (sublane)


def apply(params, x: jnp.ndarray, cfg: ModelConfig, rt: RuntimeConfig,
          *, dropless: bool = False) -> tuple[jnp.ndarray, dict]:
    """Dispatch selector over the routed experts; the shared expert, where
    the config has one, is :func:`shared`, added by the caller.

    * ``grouped`` (default) — per-batch-row dispatch: every routing tensor
      keeps a leading group dim that GSPMD shards over the data axis, so
      the sort/gather/scatter partition instead of replicating, and the
      expert einsum reshards via one all-to-all.  Capacity is enforced per
      group (the GShard "group" semantic).
    * ``global``  — the single flat sort over all T·k assignments (exact
      global capacity, but the sort and gathers do not partition — kept as
      the measured §Perf baseline).
    """
    b, s, _ = x.shape
    n_groups = 1 if rt.moe_dispatch == "global" or s == 1 else b
    return _apply_dispatch(params, x, cfg, rt, dropless=dropless,
                           n_groups=n_groups)


def shared(params, x: jnp.ndarray, cfg: ModelConfig, rt: RuntimeConfig
           ) -> jnp.ndarray:
    """The always-on shared expert, which every chip computes alike."""
    return dense.apply(params["shared"], x, cfg, rt)


def _apply_dispatch(params, x: jnp.ndarray, cfg: ModelConfig,
                    rt: RuntimeConfig, *, dropless: bool,
                    n_groups: int) -> tuple[jnp.ndarray, dict]:
    b, s, d = x.shape
    t = b * s
    k = cfg.top_k
    first, e = cfg.held_experts                            # e: experts held
    share = cfg.expert_share is not None
    g = n_groups
    tg = t // g                                            # tokens per group
    c = capacity(cfg, tg, dropless=dropless)
    xf = x.reshape(g, tg, d)

    # ---- routing (f32), over all n_experts ---------------------------------
    with jax.named_scope("router"):
        logits = jnp.einsum("gtd,de->gte", xf.astype(jnp.float32),
                            params["router"].astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, expert_idx = jax.lax.top_k(probs, k)       # (G, Tg, k)
        gate_w = gate_w / jnp.maximum(
            jnp.sum(gate_w, axis=-1, keepdims=True), 1e-9)

    # ---- rank-in-expert via one argsort per group ---------------------------
    with jax.named_scope("dispatch"):
        flat_global = expert_idx.reshape(g, tg * k)
        flat_e = flat_global
        n_buckets = e
        if share:
            # local expert ids; every absent expert shares the bucket ``e``,
            # whose assignments are never kept
            held = (flat_global >= first) & (flat_global < first + e)
            flat_e = jnp.where(held, flat_global - first, e)
            n_buckets = e + 1
        sort_idx = jnp.argsort(flat_e, axis=-1, stable=True)
        sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=-1)
        # group_start[g, e] = #assignments with expert < e in group g
        group_start = jax.vmap(
            lambda se: jnp.searchsorted(se, jnp.arange(n_buckets),
                                        side="left"))(sorted_e)
        ranks_sorted = (jnp.arange(tg * k)[None, :]
                        - jnp.take_along_axis(group_start, sorted_e, axis=-1))
        ranks = jnp.zeros((g, tg * k), jnp.int32).at[
            jnp.arange(g)[:, None], sort_idx].set(
                ranks_sorted.astype(jnp.int32))
        keep = ranks < c
        if share:
            keep = keep & held
        slot = jnp.where(keep, flat_e * c + ranks, e * c)  # sentinel slot

        # ---- gather expert batches (G, E, C, D) ---------------------------
        token_of_flat = jnp.broadcast_to(
            (jnp.arange(tg * k, dtype=jnp.int32) // k)[None, :], (g, tg * k))
        garange = jnp.arange(g)[:, None]
        table = jnp.full((g, e * c + 1), tg, jnp.int32).at[
            garange, slot].set(token_of_flat)
        gates = jnp.zeros((g, e * c + 1), jnp.float32).at[
            garange, slot].set(gate_w.reshape(g, tg * k))
        table, gates = table[:, :-1], gates[:, :-1]
        xpad = jnp.concatenate([xf, jnp.zeros((g, 1, d), xf.dtype)], axis=1)
        xe = jnp.take_along_axis(xpad, table[..., None], axis=1) \
            .reshape(g, e, c, d)
        xe = _constrain(xe, rt)

    # ---- expert FFN (gated), the held experts only -------------------------
    with jax.named_scope("experts"):
        ge = jnp.einsum("gecd,edf->gecf", xe, params["wg"])
        ue = jnp.einsum("gecd,edf->gecf", xe, params["wu"])
        he = stacks.glu(ge, ue, act=cfg.act, mode=rt.mode)
        ye = _constrain(jnp.einsum("gecf,efd->gecd", he, params["wd"]), rt)

    # ---- weighted combine back to tokens -------------------------------------
    with jax.named_scope("combine"):
        ye_flat = ye.reshape(g, e * c, d) * gates[..., None].astype(
            ye.dtype)
        y = jnp.zeros((g, tg + 1, d), ye.dtype).at[garange, table].add(
            ye_flat)[:, :tg]
        if rt.moe_constraint in ("tokens", "experts"):
            y = jax.lax.with_sharding_constraint(
                y, jax.sharding.PartitionSpec("data", None, None))
        y = y.reshape(b, s, d).astype(x.dtype)

    # ---- aux: switch load-balance loss + drop stats ---------------------------
    n_all = cfg.n_experts
    me = jnp.mean(probs, axis=(0, 1))                      # mean router prob
    ce_frac = jnp.zeros((n_all,), jnp.float32).at[flat_global].add(
        keep.astype(jnp.float32)) / jnp.maximum(jnp.sum(keep), 1.0)
    if share:
        # a token's assignments that landed here, and of those the share
        # the capacity dropped
        held_per_token = jnp.sum(held.reshape(g, tg, k), axis=-1)
        drop = 1.0 - jnp.sum(keep) / jnp.maximum(jnp.sum(held), 1)
    else:
        held_per_token = jnp.full((g, tg), k, jnp.int32)
        drop = 1.0 - jnp.mean(keep.astype(jnp.float32))
    aux = {
        "router_aux_loss": n_all * jnp.sum(me * ce_frac),
        "drop_fraction": drop,
        "held": held_per_token.reshape(b, s).astype(jnp.int32),
    }
    return y, aux
