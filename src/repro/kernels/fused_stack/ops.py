"""Jitted wrappers for the generated fused-stack kernels.

``fused_stack_apply`` dispatches one collapsed Sequence:

* mode ``brainslug``  — the generated Pallas kernels (depth-first schedule).
  Training runs depth-first end to end: the forward kernel keeps the tile
  VMEM-resident through the op chain, and the generated backward kernels
  (:mod:`repro.kernels.fused_stack.rows_bwd` for rows-layout chains,
  :mod:`repro.kernels.fused_stack.nhwc_bwd` for pooling stacks) recompute
  the chain on the resident tile and apply the per-op VJP rules of
  :mod:`repro.core.autodiff` in reverse — no reference-interpreter dispatch
  on either hot path.  nhwc stacks whose extra inputs are broadcast side
  operands (every non-channel dim 1) run generated too; only
  spatially-extended multi-input nhwc stacks keep the reference VJP
  (fusion changes the schedule, not the math, so the reference is exact).
* mode ``xla``        — jit of the interpreter (XLA fuses what it can).
* mode ``barrier``    — per-op ``optimization_barrier`` (paper's
  breadth-first baseline; every intermediate is materialized).

Executables are built once per structural signature + tile geometry and
cached (paper: "If there are multiple equivalent stacks, BRAINSLUG only
generates the code once") — one cache entry holds *both* the forward and the
backward kernel closure.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core import autodiff, ir, resource
from repro.kernels.fused_stack import nhwc, nhwc_bwd, ref, rows, rows_bwd
from repro.obs import DispatchStats

MODES = ("brainslug", "xla", "barrier")

STATS = DispatchStats()


def is_broadcast_operand(a) -> bool:
    """True when an nhwc side operand can ride along like a parameter: a
    channel vector, or any shape whose every non-channel dim is 1."""
    shape = jnp.shape(a)
    if len(shape) == 0:
        return False                    # scalars: keep the reference path
    return len(shape) == 1 or all(d == 1 for d in shape[:-1])


@dataclasses.dataclass(frozen=True)
class FusedExecutable:
    """One generated forward+backward pair for a Sequence (brainslug mode)."""

    program: ir.StackProgram
    tile_rows: int
    tile_out_h: int
    tile_out_w: int
    call: Callable[..., tuple[jnp.ndarray, ...]]   # (in_list, p_list) -> outs
    generated_bwd: bool                            # depth-first backward?


#: LRU over generated forward+backward pairs.  Bounded: a long-lived
#: serve process that keeps producing fresh shape signatures must not
#: leak one executable per signature (``set_cache_limit`` is driven by
#: ``OptimizeConfig.code_cache_size`` through the codegen layer).
_EXEC_CACHE: "OrderedDict[tuple, FusedExecutable]" = OrderedDict()
_CACHE_LIMIT = 256


def set_cache_limit(n: int) -> None:
    global _CACHE_LIMIT
    if n < 1:
        raise ValueError(f"cache limit must be >= 1, got {n}")
    _CACHE_LIMIT = n
    while len(_EXEC_CACHE) > _CACHE_LIMIT:
        _EXEC_CACHE.popitem(last=False)


def get_executable(program: ir.StackProgram, *, tile_rows: int = 256,
                   tile_out_h: int = 8, tile_out_w: int = 8
                   ) -> FusedExecutable:
    """Build (or fetch) the cached forward+backward executable for
    ``program`` at the given tile geometry, keyed on the structural
    signature so equivalent stacks share one generated pair."""
    key = (program.signature(), tile_rows, tile_out_h, tile_out_w)
    exe = _EXEC_CACHE.get(key)
    if exe is None:
        exe = _build_executable(program, tile_rows, tile_out_h, tile_out_w)
        _EXEC_CACHE[key] = exe
    _EXEC_CACHE.move_to_end(key)
    while len(_EXEC_CACHE) > _CACHE_LIMIT:
        _EXEC_CACHE.popitem(last=False)
    return exe


def clear_executable_cache() -> None:
    _EXEC_CACHE.clear()


def _build_executable(program: ir.StackProgram, tile_rows: int,
                      tile_out_h: int, tile_out_w: int) -> FusedExecutable:
    names = tuple(program.inputs)
    pnames = tuple(program.param_names)
    is_nhwc = program.layout == "nhwc"
    diffable = autodiff.supports(program)
    generated_bwd = diffable and (not is_nhwc or len(program.outputs) == 1)

    def _nhwc_generated(in_list) -> bool:
        """Can this call run the generated nhwc kernels?  Shape-dependent:
        extra inputs must be broadcast side operands."""
        return (len(program.outputs) == 1
                and all(is_broadcast_operand(a) for a in in_list[1:]))

    def _forward(in_list, p_list):
        inputs = dict(zip(names, in_list))
        params = dict(zip(pnames, p_list))
        if is_nhwc:
            if _nhwc_generated(in_list):
                STATS.record("fwd_generated")
                y = nhwc.fused_nhwc_call(
                    program, in_list[0], params,
                    extras=dict(zip(names[1:], in_list[1:])),
                    tile_out_h=tile_out_h, tile_out_w=tile_out_w)
                return (y,)
            # spatially-extended multi-input nhwc: XLA-path fallback
            STATS.record("fwd_reference")
            out = ref.fused_stack_ref(program, inputs, params)
            return tuple(out[v] for v in program.outputs)
        STATS.record("fwd_generated")
        out = rows.fused_rows_call(program, inputs, params,
                                   tile_rows=tile_rows)
        return tuple(out[v] for v in program.outputs)

    @jax.custom_vjp
    def run(in_list, p_list):
        return _forward(in_list, p_list)

    def _fwd(in_list, p_list):
        return _forward(in_list, p_list), (in_list, p_list)

    def _bwd(res, g):
        in_list, p_list = res
        # Depth-first backward: recompute the chain on the VMEM tile and
        # apply the VJP rules in reverse — one HBM read per input, one
        # write per cotangent, grid-summed parameter grads.
        if generated_bwd and is_nhwc and _nhwc_generated(in_list):
            STATS.record("bwd_generated")
            dx, dextras, dparams = nhwc_bwd.fused_nhwc_bwd_call(
                program, in_list[0], dict(zip(names[1:], in_list[1:])),
                dict(zip(pnames, p_list)), g[0],
                tile_out_h=tile_out_h, tile_out_w=tile_out_w)
            return ((dx,) + tuple(dextras[n] for n in names[1:]),
                    tuple(dparams[p] for p in pnames))
        if generated_bwd and not is_nhwc:
            STATS.record("bwd_generated")
            dins, dparams = rows_bwd.fused_rows_bwd_call(
                program, dict(zip(names, in_list)),
                dict(zip(pnames, p_list)),
                dict(zip(program.outputs, g)),
                tile_rows=tile_rows)
            return (tuple(dins[n] for n in names),
                    tuple(dparams[p] for p in pnames))

        STATS.record("bwd_reference")

        def reference(ins, ps):
            out = ref.fused_stack_ref(program, dict(zip(names, ins)),
                                      dict(zip(pnames, ps)))
            return tuple(out[v] for v in program.outputs)

        _, vjp = jax.vjp(reference, in_list, p_list)
        din, dp = vjp(tuple(g))
        return din, dp

    run.defvjp(_fwd, _bwd)
    return FusedExecutable(program=program, tile_rows=tile_rows,
                           tile_out_h=tile_out_h, tile_out_w=tile_out_w,
                           call=run,
                           generated_bwd=generated_bwd)


def plan_row_tile(program: ir.StackProgram,
                  in_list: tuple[jnp.ndarray, ...]) -> int:
    """The rows tile for one call: the planner's VMEM-budgeted extent for
    the widest feature dim, clamped to the (sublane-rounded) row count so a
    decode-sized batch is not padded up to a prefill-sized tile."""
    features = max(jnp.shape(a)[-1] for a in in_list)
    itemsize = max(jnp.dtype(a.dtype).itemsize for a in in_list)
    tile = resource.pick_row_tile(program, features, itemsize,
                                  resource.TPU_V5E, differentiable=True)
    n_rows = 1
    for d in jnp.shape(in_list[0])[:-1]:
        n_rows *= d
    return min(tile, resource.round_up(n_rows, resource.TPU_V5E.sublane))


def fused_stack_apply(program: ir.StackProgram,
                      inputs: Mapping[str, jnp.ndarray],
                      params: Mapping[str, jnp.ndarray],
                      *,
                      mode: str = "xla",
                      tile_rows: int | None = None,
                      tile_out_h: int = 8,
                      tile_out_w: int = 8) -> dict[str, jnp.ndarray]:
    """Run one stack in ``mode``.  ``tile_rows=None`` sizes the rows tile
    for these inputs against the v5e VMEM budget, as the collapse planner
    does (the joint forward+backward working set, so the same tile serves
    the generated backward)."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mode == "barrier":
        return ref.fused_stack_ref(program, inputs, params, barrier=True)
    if mode == "xla":
        return ref.fused_stack_ref(program, inputs, params)

    # mode == 'brainslug': differentiable Pallas dispatch.
    in_list = tuple(inputs[n] for n in program.inputs)
    if tile_rows is None and program.layout == "rows":
        tile_rows = plan_row_tile(program, in_list)
    exe = get_executable(program, tile_rows=tile_rows or 256,
                         tile_out_h=tile_out_h, tile_out_w=tile_out_w)
    p_list = tuple(params[p] for p in program.param_names)
    outs = exe.call(in_list, p_list)
    return dict(zip(program.outputs, outs))
