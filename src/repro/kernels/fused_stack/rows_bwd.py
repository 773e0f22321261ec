"""Generated depth-first **backward** kernel for rows-layout stacks.

The forward kernel (:mod:`repro.kernels.fused_stack.rows`) keeps a
``(tile_rows, F)`` tile VMEM-resident through a whole collapsed Sequence.
This module generates the training twin: one ``pl.pallas_call`` that

1. *recomputes* the sequence's forward ops on the resident tile (the
   depth-first analogue of activation rematerialization — intermediates are
   never written to HBM, neither in the forward nor here),
2. runs the per-op VJP rules of :mod:`repro.core.autodiff` in reverse while
   everything is still VMEM-resident,
3. writes each input cotangent tile once, and
4. accumulates per-feature parameter gradients across the grid into ``(1, F)``
   accumulator blocks (all grid cells map to the same output block; TPU grid
   iterations are sequential, so ``ref[...] +=`` is a race-free reduction —
   the grid-sum epilogue pattern).

Padded rows carry zero cotangents (the wrapper zero-pads ``g``) and are
additionally excluded from the parameter-gradient reduction by a row-validity
mask, so a NaN/inf primal recomputed on an all-zero padded row cannot poison
the accumulators.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.core import autodiff, ir
from repro.kernels.fused_stack import rows


def write_model(program: ir.StackProgram,
                shapes: Mapping[str, tuple[int, ...]],
                tile_rows: int, padded_rows: int) -> list[dict]:
    """The backward kernel's output-write geometry, as data, for the
    static verifier: one disjoint ``(tile_rows, F)`` cotangent block per
    input, plus one shared ``(1, F)`` accumulator per parameter — the
    sanctioned sequential-grid reduction idiom (``accumulate='grid-sum'``:
    every grid cell must address the *same* block)."""
    models = []
    for name in program.inputs:
        f = shapes[name][-1]
        models.append({
            "name": f"din:{name}", "block_shape": (tile_rows, f),
            "index_map": rows.row_block_index,
            "array_shape": (padded_rows, f), "accumulate": None})
    for pname in program.param_names:
        f = next((shapes[op.output][-1] for op in program.ops
                  if pname in op.params and op.output in shapes), 1)
        models.append({
            "name": f"dparam:{pname}", "block_shape": (1, f),
            "index_map": rows.shared_block_index,
            "array_shape": (1, f), "accumulate": "grid-sum"})
    return models


def _bwd_kernel(program: ir.StackProgram, n_inputs: int, n_params: int,
                n_outputs: int, tile_rows: int, valid_rows: int | None,
                *refs) -> None:
    in_refs = refs[:n_inputs]
    param_refs = refs[n_inputs:n_inputs + n_params]
    g_refs = refs[n_inputs + n_params:n_inputs + n_params + n_outputs]
    din_refs = refs[n_inputs + n_params + n_outputs:
                    n_inputs + n_params + n_outputs + n_inputs]
    dparam_refs = refs[n_inputs + n_params + n_outputs + n_inputs:]

    env = {name: rows.load_tile(ref)
           for name, ref in zip(program.inputs, in_refs)}
    params = {name: rows.load_tile(ref) for name, ref in
              zip(program.param_names, param_refs)}

    # (1) depth-first recompute: the whole op chain on the resident tile.
    for op in program.ops:
        env[op.output] = ir.apply_op(op, env, params)

    # (2) reverse sweep with the shared VJP rule table.  When the row count
    # is not a tile multiple the tail tile carries zero-padded rows; their
    # cotangents are zero, but the recomputed primal can still be NaN/inf
    # there (e.g. div on all-zero rows), so the rules get a validity mask to
    # exclude those rows from the parameter-gradient reduction.
    row_mask = None
    if valid_rows is not None:
        row0 = pl.program_id(0) * tile_rows
        ids = row0 + jax.lax.broadcasted_iota(jnp.int32, (tile_rows, 1), 0)
        row_mask = ids < valid_rows
    gouts = {name: rows.load_tile(ref)
             for name, ref in zip(program.outputs, g_refs)}
    dins, dparams = autodiff.program_vjp(program, env, params, gouts,
                                         row_mask)

    # (3) input cotangents: one write per tile.
    for name, ref in zip(program.inputs, din_refs):
        ref[...] = dins[name].astype(ref.dtype)

    # (4) parameter gradients: zero-init on the first grid cell, then
    # accumulate every tile's (1, F) partial into the shared block.
    if dparam_refs:
        @pl.when(pl.program_id(0) == 0)
        def _init():
            for ref in dparam_refs:
                ref[...] = jnp.zeros(ref.shape, ref.dtype)

        for pname, ref in zip(program.param_names, dparam_refs):
            ref[...] += dparams[pname].astype(ref.dtype)


def fused_rows_bwd_call(program: ir.StackProgram,
                        inputs: Mapping[str, jnp.ndarray],
                        params: Mapping[str, jnp.ndarray],
                        cotangents: Mapping[str, jnp.ndarray],
                        *,
                        tile_rows: int = 256
                        ) -> tuple[dict[str, jnp.ndarray],
                                   dict[str, jnp.ndarray]]:
    """Run the generated recompute-in-tile backward for one sequence.

    ``cotangents`` maps each program output name to its incoming cotangent
    (same leading shape as the inputs).  Returns ``(dinputs, dparams)`` keyed
    by input / parameter name, with shapes and dtypes matching the primals.
    """
    names = list(program.inputs)
    pnames = list(program.param_names)
    flat, lead, rows_n, pad = rows.flatten_rows(program.name, names, inputs,
                                                tile_rows)
    grid = ((rows_n + pad) // tile_rows,)

    pvals = rows.prep_params(program, params)

    gflat, glead, _, _ = rows.flatten_rows(
        program.name, list(program.outputs), cotangents, tile_rows)
    if glead != lead:
        raise ValueError(f"{program.name}: cotangent leading shape {glead} "
                         f"!= input leading shape {lead}")

    din_shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat]
    dparam_shapes = [jax.ShapeDtypeStruct(v.shape, v.dtype) for v in pvals]

    in_specs = [pl.BlockSpec((tile_rows, a.shape[-1]), rows.row_block_index)
                for a in flat]
    in_specs += [pl.BlockSpec((1, v.shape[-1]), rows.shared_block_index)
                 for v in pvals]
    in_specs += [pl.BlockSpec((tile_rows, g.shape[-1]),
                              rows.row_block_index) for g in gflat]
    out_specs = [pl.BlockSpec((tile_rows, a.shape[-1]),
                              rows.row_block_index) for a in flat]
    # Parameter-grad accumulators: every grid cell addresses block (0, 0).
    out_specs += [pl.BlockSpec((1, v.shape[-1]), rows.shared_block_index)
                  for v in pvals]

    fn = pl.pallas_call(
        functools.partial(_bwd_kernel, program, len(flat), len(pvals),
                          len(gflat), tile_rows, rows_n if pad else None),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(din_shapes + dparam_shapes),
        interpret=kernels.pallas_interpret(),
    )
    outs = fn(*flat, *pvals, *gflat)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)

    dins: dict[str, jnp.ndarray] = {}
    for name, d in zip(names, outs[: len(names)]):
        d = d[:rows_n] if pad else d
        dins[name] = d.reshape(*lead, d.shape[-1])
    dparams: dict[str, jnp.ndarray] = {}
    for pname, d in zip(pnames, outs[len(names):]):
        dparams[pname] = d.reshape(jnp.shape(params[pname]))
    return dins, dparams
