"""Generated depth-first **backward** kernel for nhwc-layout stacks.

The forward kernel (:mod:`repro.kernels.fused_stack.nhwc`) produces one
``(tile_out_h, tile_out_w, C)`` output patch per grid cell from a halo-grown
input region held in VMEM.  This module generates the training twin: one
``pl.pallas_call`` over the same ``(N, tiles_h, tiles_w)`` grid that

1. *recomputes* the op chain on the halo-grown patch (via the forward's own
   :func:`~repro.kernels.fused_stack.nhwc.run_tile` — one halo/mask
   semantics for both kernels),
2. runs the per-op VJP rules of :mod:`repro.core.autodiff` in reverse while
   every level is still VMEM-resident — max-pool cotangents routed to the
   first maximal window position (the jax/XLA tie convention), avg-pool
   cotangents scattered uniformly,
3. applies the *masking dual* of the forward's −inf/0 neutral elements:
   the cotangent of each op output is zeroed outside the true image at its
   level, and each pool's input cotangent is zeroed where the forward
   substituted the neutral element — so out-of-image halo positions
   contribute exactly zero gradient, and
4. writes one halo-extent input-cotangent patch per grid cell, while
   accumulating parameter (and broadcast-extra) gradients across the grid
   into shared ``(1, C)`` blocks (sequential TPU grid ⇒ race-free
   grid-sum, the rows_bwd epilogue pattern).

Overlap-add
-----------
Neighbouring tiles read *overlapping* halo regions in the forward, so their
input-cotangent patches overlap too and must be **summed**.  The kernel
writes each tile's patch to its own slot; the wrapper performs the
overlap-add with a ``fori_loop`` of dynamic-slice accumulates (tile origins
are affine in the grid index, and the trace stays O(1) in tile count) and
then crops the pre-padding — which also drops any garbage cotangent the
recompute produced at out-of-image positions of the input level.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import kernels
from repro.core import autodiff, ir
from repro.kernels.fused_stack import nhwc


def patch_block_index(i, j, k):
    """Input-cotangent BlockSpec index map: grid cell ``(n, i, j)`` owns
    its private ``(1, 1, 1, eh, ew, C)`` patch slot.  Module-level so the
    static verifier's write model evaluates the same function the
    ``pallas_call`` BlockSpec installs."""
    return (i, j, k, 0, 0, 0)


def write_model(program: ir.StackProgram, grid: tuple[int, int, int],
                eh: int, ew: int, c: int) -> list[dict]:
    """The backward kernel's output-write geometry, as data, for the
    static verifier: per-cell private patch slots (the halo overlap-add
    idiom, ``accumulate='overlap-slot'`` — disjoint slot writes; the
    wrapper sums the logical overlaps outside the kernel) plus shared
    ``(1, C)`` grid-sum accumulators for broadcast extras and params."""
    n, gh, gw = grid
    models = [{
        "name": "dx_patches", "block_shape": (1, 1, 1, eh, ew, c),
        "index_map": patch_block_index,
        "array_shape": (n, gh, gw, eh, ew, c),
        "accumulate": "overlap-slot"}]
    for name in (*program.inputs[1:], *program.param_names):
        models.append({
            "name": f"acc:{name}", "block_shape": (1, c),
            "index_map": nhwc.shared_block_index,
            "array_shape": (1, c), "accumulate": "grid-sum"})
    return models


def _pool_patch_vjp(op: ir.OpNode, x_ref, y: jnp.ndarray, g: jnp.ndarray,
                    dx_ref) -> jnp.ndarray:
    """In-kernel twin of :func:`repro.core.autodiff.pool2d_patch_vjp` (the
    oracle's rule, same max tie convention): the window reads are strided
    loads from ``x_ref`` (the pool's masked input left by the recompute),
    and the scatter back to input positions is a strided accumulate into
    the VMEM scratch ``dx_ref`` — Mosaic has neither strided value slices
    nor interior padding."""
    kh, kw = op.attrs["window"]
    sh, sw = op.attrs["stride"]
    oh, ow = g.shape[0], g.shape[1]
    lanes = dx_ref.shape[-1]
    dx_ref[...] = jnp.zeros(dx_ref.shape, dx_ref.dtype)
    c = (g / float(kh * kw)).astype(dx_ref.dtype)        # avg: uniform
    taken = jnp.zeros(g.shape, jnp.bool_)
    for di in range(kh):
        for dj in range(kw):
            if op.fn == "max":
                # route g to the first window position attaining the max
                part = nhwc.pool_window(x_ref, op, di, dj, oh, ow)
                sel = (part == y) & ~taken
                taken = taken | sel
                c = jnp.where(sel, g, 0).astype(dx_ref.dtype)
            rows, cols = nhwc.window(op, di, dj, oh, ow)
            for j in range(dx_ref.shape[0]):
                dx_ref[j, rows, cols, :] += c[..., j * lanes:(j + 1) * lanes]
    return nhwc.load_lanes(dx_ref)


def _bwd_kernel(program: ir.StackProgram, levels, pad_off_h: int,
                pad_off_w: int, n_extra: int, n_params: int, *refs) -> None:
    src_ref = refs[0]
    extra_refs = refs[1: 1 + n_extra]
    param_refs = refs[1 + n_extra: 1 + n_extra + n_params]
    g_ref = refs[1 + n_extra + n_params]
    dx_ref = refs[2 + n_extra + n_params]
    dextra_refs = refs[3 + n_extra + n_params: 3 + 2 * n_extra + n_params]
    n_out = 3 + 2 * n_extra + 2 * n_params
    dparam_refs = refs[3 + 2 * n_extra + n_params: n_out]
    buf_ref, sem = refs[n_out: n_out + 2]
    pools = nhwc.pool_ops(program)
    pool_refs = {op.name: ref for (_, op), ref in
                 zip(pools, refs[n_out + 2: n_out + 2 + len(pools)])}
    pool_dx_refs = {op.name: ref for (_, op), ref in
                    zip(pools, refs[n_out + 2 + len(pools):])}

    n = pl.program_id(0)
    pi = pl.program_id(1)
    pj = pl.program_id(2)

    lv0 = levels[0]
    out_lv = levels[-1]
    g0h = pi * out_lv.extent_h * lv0.mul_h - lv0.off_h
    g0w = pj * out_lv.extent_w * lv0.mul_w - lv0.off_w
    buf = nhwc.load_halo(src_ref, buf_ref, sem, n, g0h + pad_off_h,
                         g0w + pad_off_w, lv0.extent_w)

    extra_names = list(program.inputs[1:])
    extras = {name: ref[...][None] for name, ref in
              zip(extra_names, extra_refs)}
    params = {name: ref[...] for name, ref in
              zip(program.param_names, param_refs)}

    # (1) depth-first recompute — the forward kernel's own tile function.
    env, origins, valids = nhwc.run_tile(
        program, levels, buf, extras, params, g0h, g0w, pool_refs)

    # (2) reverse sweep.  The incoming cotangent tile is zero on grid-padded
    # output rows/cols (the wrapper zero-pads g), and every op's output
    # cotangent is re-zeroed against that level's validity mask before use:
    # positions outside the true image recompute garbage primals, and a
    # 0 * inf slipping through an elementwise rule would otherwise scatter
    # NaNs into valid input positions via the pool routing.
    cot: dict[str, jnp.ndarray] = {program.outputs[0]: g_ref[0]}
    dparams: dict[str, jnp.ndarray] = {}
    for i in reversed(range(len(program.ops))):
        op = program.ops[i]
        g = cot.pop(op.output, None)
        if g is None:                       # output never used downstream
            continue
        valid_out = nhwc.tile_valid(g.shape, origins[op.output],
                                    levels[i + 1])
        g = jnp.where(valid_out, g, 0)
        if op.kind == ir.OpKind.POOL2D:
            dx = _pool_patch_vjp(op, pool_refs[op.name], env[op.output], g,
                                 pool_dx_refs[op.name])
            # masking dual: the forward replaced out-of-image positions with
            # the neutral element, so their cotangent is exactly zero.
            dx = jnp.where(valids[op.name], dx, 0)
            v = op.inputs[0]
            cot[v] = cot[v] + dx if v in cot else dx
            continue
        din, dp = autodiff.op_vjp(op, env, params, g, row_mask=valid_out)
        for v, d in din.items():
            cot[v] = cot[v] + d if v in cot else d
        for p, d in dp.items():
            dparams[p] = dparams[p] + d if p in dparams else d

    # (3) input cotangent: one halo-extent patch per grid cell; the wrapper
    # overlap-adds across tiles.
    primary = program.inputs[0]
    dx0 = cot.get(primary)
    if dx0 is None:
        dx0 = jnp.zeros(buf.shape, buf.dtype)
    dx_ref[...] = dx0.astype(buf.dtype)[None, None, None]

    # (4) parameter / broadcast-extra gradients: zero-init on the first grid
    # cell, then every cell accumulates its (1, C) partial into the shared
    # block (sequential grid ⇒ race-free reduction).
    if dextra_refs or dparam_refs:
        @pl.when((n == 0) & (pi == 0) & (pj == 0))
        def _init():
            for ref in (*dextra_refs, *dparam_refs):
                ref[...] = jnp.zeros(ref.shape, ref.dtype)

        for name, ref in zip(extra_names, dextra_refs):
            d = cot.get(name)
            if d is None:
                continue
            ref[...] += d.reshape(1, -1).astype(ref.dtype)
        for pname, ref in zip(program.param_names, dparam_refs):
            d = dparams.get(pname)
            if d is None:
                continue
            ref[...] += d.reshape(1, -1).astype(ref.dtype)


def fused_nhwc_bwd_call(program: ir.StackProgram,
                        x: jnp.ndarray,
                        extras: Mapping[str, jnp.ndarray],
                        params: Mapping[str, jnp.ndarray],
                        g: jnp.ndarray,
                        *,
                        tile_out_h: int = 8,
                        tile_out_w: int = 8
                        ) -> tuple[jnp.ndarray, dict[str, jnp.ndarray],
                                   dict[str, jnp.ndarray]]:
    """Run the generated recompute-in-tile backward for one nhwc sequence.

    ``g`` is the cotangent of the single program output; ``extras`` the
    broadcast side operands (``program.inputs[1:]``).  Returns
    ``(dx, dextras, dparams)`` with shapes/dtypes matching the primals.
    """
    extras = dict(extras or {})
    n, h, w, c = x.shape
    (levels, grid, xp, (left_h, left_w), (oh, ow), (pad_oh, pad_ow),
     (th, tw)) = nhwc.plan_geometry(program, x, extras, tile_out_h,
                                    tile_out_w)
    lv0 = levels[0]
    eh, ew = lv0.extent_h, lv0.extent_w

    cp = xp.shape[-1]
    # zero-pad the cotangent over the grid-padding region (and the padded
    # channels): padded output positions contribute no gradient.
    gp = jnp.pad(g, ((0, 0), (0, pad_oh), (0, pad_ow), (0, cp - c)))

    evals = nhwc.prep_extras(program, extras, cp)
    pnames = list(program.param_names)
    pvals = [nhwc.lane_row(params[p], cp) for p in pnames]

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    in_specs += [pl.BlockSpec((1, cp), nhwc.shared_block_index)
                 for _ in evals + pvals]
    in_specs += [pl.BlockSpec((1, th, tw, cp), nhwc.out_block_index)]

    out_shapes = [jax.ShapeDtypeStruct((n, grid[1], grid[2], eh, ew, cp),
                                       x.dtype)]
    out_specs = [pl.BlockSpec((1, 1, 1, eh, ew, cp), patch_block_index)]
    # grid-summed accumulators: every cell addresses block (0, 0)
    for v in evals + pvals:
        out_shapes.append(jax.ShapeDtypeStruct(v.shape, v.dtype))
        out_specs.append(pl.BlockSpec((1, v.shape[-1]),
                                      nhwc.shared_block_index))

    fn = pl.pallas_call(
        functools.partial(_bwd_kernel, program, levels, left_h, left_w,
                          len(evals), len(pvals)),
        grid=grid,
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
        scratch_shapes=nhwc.tile_scratch(program, levels, cp, x.dtype,
                                         backward=True),
        interpret=kernels.pallas_interpret(),
    )
    outs = fn(xp, *evals, *pvals, gp)
    patches = outs[0]

    # Overlap-add: neighbouring tiles' halo patches overlap and must be
    # summed.  Tile origins are affine in the grid index, so a fori_loop
    # keeps the trace O(1) in tile count (a statically unrolled chain would
    # bake tiles_h * tiles_w update ops into every backward jaxpr).
    gh, gw = grid[1], grid[2]

    def _accumulate(t, acc):
        pi = t // gw
        pj = t % gw
        h0 = pi * th * lv0.mul_h - lv0.off_h + left_h
        w0 = pj * tw * lv0.mul_w - lv0.off_w + left_w
        patch = jax.lax.dynamic_slice(
            patches, (0, pi, pj, 0, 0, 0), (n, 1, 1, eh, ew, cp))[:, 0, 0]
        cur = jax.lax.dynamic_slice(acc, (0, h0, w0, 0), (n, eh, ew, cp))
        return jax.lax.dynamic_update_slice(acc, cur + patch,
                                            (0, h0, w0, 0))

    dxp = jax.lax.fori_loop(0, gh * gw, _accumulate, jnp.zeros_like(xp))
    dx = dxp[:, left_h: left_h + h, left_w: left_w + w, :c]

    dextras: dict[str, jnp.ndarray] = {}
    for name, d in zip(program.inputs[1:], outs[1: 1 + len(evals)]):
        dextras[name] = d[:, :c].reshape(jnp.shape(extras[name])).astype(
            jnp.asarray(extras[name]).dtype)
    dparams: dict[str, jnp.ndarray] = {}
    for pname, d in zip(pnames, outs[1 + len(evals):]):
        dparams[pname] = d[:, :c].reshape(jnp.shape(params[pname]))
    return dx, dextras, dparams
