"""Step builders + abstract input specs for every (arch x shape) cell.

``input_specs`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, zero allocation) for model inputs; ``abstract_state`` does the
same for params/optimizer state.  The dry-run lowers
``jax.jit(step, in_shardings=..., out_shardings=...)`` against these — the
same functions the real train/serve drivers execute.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig, RuntimeConfig, ShapeConfig
from repro.distributed import sharding as shd
from repro.models import lm
from repro.optim import adamw, schedule


# ---------------------------------------------------------------------------
# Abstract inputs
# ---------------------------------------------------------------------------

def _batch_dims(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, tuple]:
    b, s = shape.global_batch, shape.seq_len
    if cfg.frontend == "audio_frames":
        return {"frames": (b, s, cfg.frontend_dim), "labels": (b, s)}
    dims: dict[str, tuple] = {}
    if cfg.frontend == "vision_patches":
        s_text = s - cfg.n_prefix_tokens
        dims["tokens"] = (b, s_text)
        dims["patches"] = (b, cfg.n_prefix_tokens, cfg.frontend_dim)
        dims["labels"] = (b, s_text)
    else:
        dims["tokens"] = (b, s)
        dims["labels"] = (b, s)
    return dims


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    """ShapeDtypeStructs for one training/prefill batch."""
    act_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    out = {}
    for name, dims in _batch_dims(cfg, shape).items():
        dt = jnp.int32 if name in ("tokens", "labels") else act_dtype
        out[name] = jax.ShapeDtypeStruct(dims, dt)
    if shape.kind != "train":
        out.pop("labels", None)
    return out


def decode_input_specs(cfg: ModelConfig, shape: ShapeConfig
                       ) -> tuple[dict, Any]:
    """(tokens_t spec, cache spec tree) for one decode step with a KV/state
    cache sized for ``shape.seq_len``."""
    b = shape.global_batch
    cache = jax.eval_shape(
        lambda: lm.init_decode_cache(cfg, b, shape.seq_len))
    tokens = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    return tokens, cache


@functools.lru_cache(maxsize=None)
def _abstract_params(cfg: ModelConfig):
    """(params ShapeDtypeStruct tree, logical-axes tree), zero allocation.
    The axes tree is static metadata, captured as a side output while
    tracing init under eval_shape."""
    closure: list = []

    def capture(key):
        p, a = lm.init(key, cfg)
        closure.append(a)
        return p

    params = jax.eval_shape(capture, jax.random.PRNGKey(0))
    return params, closure[0]


def abstract_state(cfg: ModelConfig, *, with_opt: bool = True):
    """(params shapes, axes tree, opt-state shapes) with zero allocation."""
    params, axes = _abstract_params(cfg)
    opt = jax.eval_shape(adamw.init, params) if with_opt else None
    return params, axes, opt


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------

def default_opt_config(total_steps: int = 1000) -> adamw.AdamWConfig:
    return adamw.AdamWConfig(
        lr=schedule.warmup_cosine(3e-4, min(100, total_steps // 10 + 1),
                                  total_steps))


def make_train_step(cfg: ModelConfig, rt: RuntimeConfig,
                    opt_cfg: adamw.AdamWConfig) -> Callable:
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lm.loss_fn, has_aux=True)(params, batch, cfg, rt)
        params, opt_state, opt_metrics = adamw.update(
            opt_cfg, grads, opt_state, params)
        return params, opt_state, {**metrics, **opt_metrics}
    return train_step


def make_prefill_step(cfg: ModelConfig, rt: RuntimeConfig) -> Callable:
    def prefill_step(params, batch):
        return lm.prefill(params, batch, cfg, rt)
    return prefill_step


def make_decode_step(cfg: ModelConfig, rt: RuntimeConfig) -> Callable:
    def serve_step(params, cache, batch):
        return lm.decode_step(params, cache, batch["tokens"], cfg, rt)
    return serve_step


# ---------------------------------------------------------------------------
# Sharding assembly
# ---------------------------------------------------------------------------

def _maybe_batch_spec(tree, mesh: Mesh) -> Any:
    """Shard the leading batch dim when it divides the data extent(s);
    otherwise replicate (long_500k has global_batch=1)."""
    axes = shd.batch_axes(mesh)
    flat = axes if isinstance(axes, tuple) else (axes,)
    extent = 1
    for a in flat:
        extent *= mesh.shape[a]

    def leaf(x):
        if x.shape and x.shape[0] % extent == 0 and x.shape[0] > 0:
            return P(axes, *([None] * (len(x.shape) - 1)))
        return P(*([None] * len(x.shape)))

    return jax.tree_util.tree_map(leaf, tree)


@dataclasses.dataclass(frozen=True)
class CellLowering:
    """Everything needed to lower one (arch x shape x mesh) cell."""
    step: Callable
    args: tuple                     # abstract operand trees, in order
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple = ()


# ---------------------------------------------------------------------------
# Per-super-block part lowerings (roofline trip-count correction).
# ---------------------------------------------------------------------------

def _drop_layer_dim(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), tree)


def _drop_layer_spec(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda s: P(*tuple(s)[1:]), tree,
        is_leaf=lambda x: isinstance(x, P))


def plan_part_cells(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                    rt: RuntimeConfig,
                    rules: shd.ShardingRules = shd.ShardingRules()
                    ) -> list[tuple[str, CellLowering, int]]:
    """Returns [(part_name, lowering, extra_multiplier)] where
    ``corrected_cost = full_cost + sum(extra_multiplier * part_cost)``.
    The extra multiplier is (trip_count - 1): the full lowering already
    counts each scanned body once."""
    rt = resolve_rt(cfg, mesh, rt)
    rt = dataclasses.replace(rt, scan_unroll=True, loss_unroll=True)
    params, axes, _ = abstract_state(cfg, with_opt=False)
    pspecs = shd.repair_specs(
        params, shd.param_specs(axes, rules, mesh), mesh)
    plan = lm.layer_plan(cfg)
    act_dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    b = shape.global_batch
    s = 1 if shape.kind == "decode" else shape.seq_len
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), act_dtype)
    xspec = shd.repair_spec(x.shape, P(shd.batch_axes(mesh), None, None),
                            mesh)
    shared = params.get("shared_attn")
    shared_spec = pspecs.get("shared_attn")
    use_shared = plan.uses_shared_attn

    parts: list[tuple[str, CellLowering, int]] = []

    def add(name: str, step, args, in_sh, mult: int, out_sh=None,
            donate=()):
        if mult > 0:
            parts.append((name, CellLowering(
                step=step, args=args, in_shardings=in_sh,
                out_shardings=out_sh, donate_argnums=donate), mult))

    if shape.kind == "train":
        if use_shared:
            def block_step(bp, sh, xx):
                def f(bp_, sh_, x_):
                    y, aux = lm.superblock_fwd(bp_, sh_, x_, cfg, rt)
                    return (jnp.sum(y.astype(jnp.float32))
                            + aux["router_aux_loss"])
                return jax.grad(f, argnums=(0, 1, 2))(bp, sh, xx)
            args = (_drop_layer_dim(params["blocks"]), shared, x)
            in_sh = (_drop_layer_spec(pspecs["blocks"]), shared_spec, xspec)
        else:
            def block_step(bp, xx):
                def f(bp_, x_):
                    y, aux = lm.superblock_fwd(bp_, None, x_, cfg, rt)
                    return (jnp.sum(y.astype(jnp.float32))
                            + aux["router_aux_loss"])
                return jax.grad(f, argnums=(0, 1))(bp, xx)
            args = (_drop_layer_dim(params["blocks"]), x)
            in_sh = (_drop_layer_spec(pspecs["blocks"]), xspec)
        add("block", block_step, args, in_sh, plan.n_super - 1)
        if plan.tail:
            def tail_step(tp, xx):
                def f(tp_, x_):
                    return jnp.sum(lm.tail_fwd(tp_, x_, cfg, rt)
                                   .astype(jnp.float32))
                return jax.grad(f, argnums=(0, 1))(tp, xx)
            add("tail", tail_step,
                (_drop_layer_dim(params["tail"]), x),
                (_drop_layer_spec(pspecs["tail"]), xspec),
                len(plan.tail) - 1)
        return parts

    if shape.kind == "prefill":
        if use_shared:
            def block_step(bp, sh, xx):
                return lm.superblock_fwd(bp, sh, xx, cfg, rt)[0]
            args = (_drop_layer_dim(params["blocks"]), shared, x)
            in_sh = (_drop_layer_spec(pspecs["blocks"]), shared_spec, xspec)
        else:
            def block_step(bp, xx):
                return lm.superblock_fwd(bp, None, xx, cfg, rt)[0]
            args = (_drop_layer_dim(params["blocks"]), x)
            in_sh = (_drop_layer_spec(pspecs["blocks"]), xspec)
        add("block", block_step, args, in_sh, plan.n_super - 1)
        if plan.tail:
            add("tail", lambda tp, xx: lm.tail_fwd(tp, xx, cfg, rt),
                (_drop_layer_dim(params["tail"]), x),
                (_drop_layer_spec(pspecs["tail"]), xspec),
                len(plan.tail) - 1)
        return parts

    # decode
    _, cache = decode_input_specs(cfg, shape)
    cspecs = shd.repair_specs(cache, shd.cache_spec(cache, mesh), mesh)
    blk_cache = _drop_layer_dim(cache["blocks"])
    blk_cspec = _drop_layer_spec(cspecs["blocks"])
    if use_shared:
        def block_step(bp, sh, cc, xx):
            return lm.superblock_decode(bp, sh, cc, xx, cfg, rt)
        args = (_drop_layer_dim(params["blocks"]), shared, blk_cache, x)
        in_sh = (_drop_layer_spec(pspecs["blocks"]), shared_spec,
                 blk_cspec, xspec)
        donate = (2,)
    else:
        def block_step(bp, cc, xx):
            return lm.superblock_decode(bp, None, cc, xx, cfg, rt)
        args = (_drop_layer_dim(params["blocks"]), blk_cache, x)
        in_sh = (_drop_layer_spec(pspecs["blocks"]), blk_cspec, xspec)
        donate = (1,)
    # cache donation mirrors the full decode step (the in-place scatter
    # update must not be charged a whole-cache copy)
    add("block", block_step, args, in_sh, plan.n_super - 1, donate=donate)
    if plan.tail:
        add("tail",
            lambda tp, cc, xx: lm.tail_decode(tp, cc, xx, cfg, rt),
            (_drop_layer_dim(params["tail"]), _drop_layer_dim(cache["tail"]),
             x),
            (_drop_layer_spec(pspecs["tail"]), _drop_layer_spec(cspecs["tail"]),
             xspec),
            len(plan.tail) - 1, donate=(1,))
    return parts


def resolve_rt(cfg: ModelConfig, mesh: Mesh, rt: RuntimeConfig
               ) -> RuntimeConfig:
    """Resolve launcher-decided knobs ('auto' values) from cfg x mesh."""
    if rt.moe_constraint == "auto":
        if not cfg.n_experts or rt.moe_dispatch != "grouped" \
                or "data" not in mesh.axis_names:
            choice = "none"
        elif cfg.n_experts % mesh.shape["data"] == 0:
            choice = "experts"
        else:
            choice = "tokens"
        rt = dataclasses.replace(rt, moe_constraint=choice)
    return rt


def plan_cell(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
              rt: RuntimeConfig,
              rules: shd.ShardingRules = shd.ShardingRules()
              ) -> CellLowering:
    rt = resolve_rt(cfg, mesh, rt)
    params, axes, opt = abstract_state(cfg, with_opt=shape.kind == "train")
    pspecs = shd.param_specs(axes, rules, mesh)
    pspecs = shd.repair_specs(params, pspecs, mesh)

    if shape.kind == "train":
        step = make_train_step(cfg, rt, default_opt_config())
        batch = input_specs(cfg, shape)
        ospecs = shd.opt_state_specs(pspecs, mesh)
        bspecs = _maybe_batch_spec(batch, mesh)
        metric_specs = None
        return CellLowering(
            step=step,
            args=(params, opt, batch),
            in_shardings=(pspecs, ospecs, bspecs),
            out_shardings=(pspecs, ospecs, metric_specs),
            donate_argnums=(0, 1),
        )
    if shape.kind == "prefill":
        step = make_prefill_step(cfg, rt)
        batch = input_specs(cfg, shape)
        bspecs = _maybe_batch_spec(batch, mesh)
        return CellLowering(
            step=step, args=(params, batch),
            in_shardings=(pspecs, bspecs),
            out_shardings=None)
    # decode
    step = make_decode_step(cfg, rt)
    tokens, cache = decode_input_specs(cfg, shape)
    cspecs = shd.repair_specs(cache, shd.cache_spec(cache, mesh), mesh)
    tspecs = _maybe_batch_spec(tokens, mesh)
    return CellLowering(
        step=step, args=(params, cache, tokens),
        in_shardings=(pspecs, cspecs, tspecs),
        out_shardings=(None, cspecs),
        donate_argnums=(1,),
    )
