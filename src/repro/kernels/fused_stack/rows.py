"""Generated depth-first kernel for rows-layout stacks (LM chains).

One ``pl.pallas_call`` executes an entire collapsed Sequence on a
``(tile_rows, features)`` VMEM tile: the tile is read from HBM once, every
op of the sequence is applied while it is VMEM/VREG-resident, and the result
is written back once.  This is the paper's depth-first schedule with VMEM
playing the role of the L1/shared-memory cache.

The kernel *body* is the shared IR interpreter (:func:`repro.core.ir.apply_op`)
traced over the tile values — the same semantics object that defines the
reference path, so the generated kernel cannot drift from the oracle.

The backward twin lives in :mod:`repro.kernels.fused_stack.rows_bwd` and
shares this module's flatten/pad/param plumbing.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.core import ir


def row_block_index(i):
    """Output/input BlockSpec index map for ``(tile_rows, F)`` tiles: grid
    cell ``i`` owns row-block ``i``.  Module-level (not a lambda) so the
    static verifier's write model (:func:`write_model`) evaluates the
    *same* function the ``pallas_call`` BlockSpecs install — the race
    check cannot drift from the kernel."""
    return (i, 0)


def shared_block_index(i):
    """BlockSpec index map for ``(1, F)`` parameter blocks: every grid
    cell addresses the single shared block."""
    del i
    return (0, 0)


def write_model(program: ir.StackProgram,
                shapes: Mapping[str, tuple[int, ...]],
                tile_rows: int, padded_rows: int) -> list[dict]:
    """The forward kernel's output-write geometry, as data: one entry per
    program output with the grid-evaluable index map, block shape, and
    destination array shape :func:`fused_rows_call` will use.  Consumed by
    ``repro.core.verify`` to prove pairwise-disjoint writes."""
    models = []
    for name in program.outputs:
        f = shapes[name][-1]
        models.append({
            "name": name, "block_shape": (tile_rows, f),
            "index_map": row_block_index,
            "array_shape": (padded_rows, f), "accumulate": None})
    return models


def load_tile(ref) -> jnp.ndarray:
    """Read a VMEM block for the op chain, floating values as float32.

    The chain runs in float32 whatever the storage dtype: the v5e VPU has
    no bf16 arithmetic, and Mosaic refuses the float32 scalar constants
    that ``ir.apply_op`` would otherwise broadcast into a bf16 vector."""
    v = ref[...]
    return (v.astype(jnp.float32) if jnp.issubdtype(v.dtype, jnp.floating)
            else v)


def _kernel(program: ir.StackProgram, n_inputs: int, n_params: int,
            *refs) -> None:
    in_refs = refs[:n_inputs]
    param_refs = refs[n_inputs:n_inputs + n_params]
    out_refs = refs[n_inputs + n_params:]

    env = {name: load_tile(ref) for name, ref in zip(program.inputs, in_refs)}
    # Params keep their (1, F) block shape; broadcasting against the
    # (tile_rows, F) tiles is free and avoids 1-D operands on TPU.
    params = {name: load_tile(ref) for name, ref in
              zip(program.param_names, param_refs)}
    for op in program.ops:
        env[op.output] = ir.apply_op(op, env, params)
    for name, ref in zip(program.outputs, out_refs):
        ref[...] = env[name].astype(ref.dtype)


def flatten_rows(prog_name: str, names: list[str],
                 values: Mapping[str, jnp.ndarray], tile_rows: int
                 ) -> tuple[list[jnp.ndarray], tuple[int, ...], int, int]:
    """Flatten the named values to ``(rows, F)`` and zero-pad the row
    dimension to a ``tile_rows`` multiple.  Returns
    (flat arrays, lead shape, rows, pad)."""
    arrays = [values[n] for n in names]
    lead = arrays[0].shape[:-1]
    for n, a in zip(names, arrays):
        if a.shape[:-1] != lead:
            raise ValueError(f"{prog_name}: value {n} leading shape "
                             f"{a.shape[:-1]} != {lead}")
    rows = 1
    for d in lead:
        rows *= d
    flat = [a.reshape(rows, a.shape[-1]) for a in arrays]
    pad = (-rows) % tile_rows
    if pad:
        flat = [jnp.pad(a, ((0, pad), (0, 0))) for a in flat]
    return flat, lead, rows, pad


def prep_params(program: ir.StackProgram,
                params: Mapping[str, jnp.ndarray]) -> list[jnp.ndarray]:
    """Reshape per-feature parameter vectors to (1, F) 2-D operands."""
    pvals = []
    for p in program.param_names:
        v = jnp.asarray(params[p])
        pvals.append(v.reshape(1, -1) if v.ndim <= 1
                     else v.reshape(1, v.shape[-1]))
    return pvals


def fused_rows_call(program: ir.StackProgram,
                    inputs: Mapping[str, jnp.ndarray],
                    params: Mapping[str, jnp.ndarray],
                    *,
                    tile_rows: int = 256) -> dict[str, jnp.ndarray]:
    """Run a rows-layout sequence as one fused Pallas kernel.

    Every input must share the same leading shape ``(..., F_i)``; leading
    dims are flattened to a row dimension that is tiled by ``tile_rows``.
    Parameters are per-feature vectors (or scalars) held fully in VMEM.
    """
    names = list(program.inputs)
    flat, lead, rows, pad = flatten_rows(program.name, names, inputs,
                                         tile_rows)
    padded_rows = rows + pad
    grid = (padded_rows // tile_rows,)

    pnames = list(program.param_names)
    pvals = prep_params(program, params)

    # Infer output shapes/dtypes from the interpreter on ShapeDtypeStructs.
    out_shapes = _infer_outputs(program, flat, names, pnames, pvals)

    in_specs = [pl.BlockSpec((tile_rows, a.shape[-1]), row_block_index)
                for a in flat]
    in_specs += [pl.BlockSpec((1, v.shape[-1]), shared_block_index)
                 for v in pvals]
    out_specs = [pl.BlockSpec((tile_rows, s.shape[-1]), row_block_index)
                 for s in out_shapes]

    fn = pl.pallas_call(
        functools.partial(_kernel, program, len(flat), len(pvals)),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        interpret=kernels.pallas_interpret(),
    )
    outs = fn(*flat, *pvals)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    result = {}
    for name, o in zip(program.outputs, outs):
        o = o[:rows] if pad else o
        result[name] = o.reshape(*lead, o.shape[-1])
    return result


def _infer_outputs(program: ir.StackProgram, flat, names, pnames, pvals):
    def run(*args):
        env = dict(zip(names, args[: len(names)]))
        ps = dict(zip(pnames, args[len(names):]))
        out = ir.run_program(program, env, ps)
        return tuple(out[v] for v in program.outputs)

    shapes = jax.eval_shape(run, *flat, *pvals)
    return [jax.ShapeDtypeStruct(s.shape, s.dtype) for s in shapes]
