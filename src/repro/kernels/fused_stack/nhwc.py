"""Generated depth-first kernel for nhwc-layout stacks (pooling chains).

This is the faithful TPU port of the paper's collapsed CNN kernel
(paper Listing 2): a grid cell produces one ``(tile_out_h, tile_out_w, C)``
output patch by loading the receptive-field-grown input region (halo) into
VMEM and pushing it through every op of the sequence depth-first.

Halo mechanics
--------------
BlockSpec partitions are non-overlapping, but stacked stride-1 pooling needs
overlapping input regions.  The TPU-idiomatic answer is to keep the input in
``ANY`` (HBM) memory space and issue an explicit windowed async DMA per grid
cell into a VMEM scratch buffer (:func:`load_halo`).  The wrapper pre-pads
the input so window origins are always in-bounds, and per-pool *validity
masks* — computed from global coordinates with ``broadcasted_iota`` —
replace out-of-image positions with the pool's neutral element (−inf for
max, 0 for avg), reproducing each pooling layer's own padding semantics
exactly.  See ``ref.py`` for the oracle.

Pooling inside the kernel is expressed as a static unrolled max/add over
``window`` shifted strided loads from a VMEM scratch copy of the pool's
masked input — ``reduce_window`` does not exist inside Mosaic, and a strided
slice of a value lowers to a gather it refuses, while a strided ``pl.ds``
load from a ref is a plain vector load.

Beyond the single-input chain, the kernel carries *broadcast side operands*
(extra stack inputs whose every non-channel dim is 1, e.g. a saved
channelwise bias consumed by a residual ``EW_BINARY``): they ride along like
parameters in ``(1, C)`` blocks, which lifts the multi-input-nhwc fallback
for that family.  Spatially-extended extra inputs still fall back.

The tile recompute (:func:`run_tile`) is shared with the generated backward
(:mod:`repro.kernels.fused_stack.nhwc_bwd`) — one halo/mask semantics, two
kernels.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import kernels
from repro.core import autodiff
from repro.core import ir

#: Lane width of a vector register: the channel (minor) dim is padded to it.
LANES = 128


@dataclasses.dataclass(frozen=True)
class _Level:
    """Static spatial geometry of one value level inside the sequence."""
    extent_h: int            # tile extent at this level
    extent_w: int
    image_h: int             # full (unpadded) image extent at this level
    image_w: int
    # origin of the tile at this level = out_patch_origin * prod(strides) - off
    mul_h: int
    off_h: int
    mul_w: int
    off_w: int


def _plan_levels(ops: tuple[ir.OpNode, ...], out_h: int, out_w: int,
                 image_hw: list[tuple[int, int]]) -> list[_Level]:
    """Walk backwards from the output patch to compute, per op, the tile
    extent and origin transform of its *input* level.  Per-level *image*
    extents come from forward shape inference (``image_hw``, one entry per
    value level): reconstructing them backwards via pool_in_extent
    under-counts whenever a stride does not tile the image exactly, which
    mis-masks real border columns."""
    levels: list[_Level] = []
    eh, ew = out_h, out_w
    mul_h = mul_w = 1
    off_h = off_w = 0
    # level after the last op (the output level)
    ih, iw = image_hw[len(ops)]
    levels.append(_Level(eh, ew, ih, iw, mul_h, off_h, mul_w, off_w))
    for i, op in enumerate(reversed(ops)):
        if op.kind == ir.OpKind.POOL2D:
            kh, kw = op.attrs["window"]
            sh, sw = op.attrs["stride"]
            ph, pw = op.attrs["padding"]
            eh = ir.pool_in_extent(eh, kh, sh)
            ew = ir.pool_in_extent(ew, kw, sw)
            off_h = off_h * sh + ph
            off_w = off_w * sw + pw
            mul_h *= sh
            mul_w *= sw
        ih, iw = image_hw[len(ops) - 1 - i]
        levels.append(_Level(eh, ew, ih, iw, mul_h, off_h, mul_w, off_w))
    levels.reverse()           # levels[i] = input level of ops[i]
    return levels


def out_block_index(i, j, k):
    """Output BlockSpec index map: grid cell ``(n, i, j)`` owns output
    patch ``(n, i, j)``.  Module-level (not a lambda) so the static
    verifier's write model (:func:`write_model`) evaluates the same
    function the ``pallas_call`` BlockSpec installs."""
    return (i, j, k, 0)


def shared_block_index(i, j, k):
    """BlockSpec index map for ``(1, C)`` param / broadcast-extra blocks:
    every grid cell addresses the single shared block."""
    del i, j, k
    return (0, 0)


def write_model(n: int, oh: int, ow: int, c: int,
                th: int, tw: int) -> list[dict]:
    """The forward kernel's output-write geometry, as data, for the static
    verifier: one ``(1, th, tw, C)`` patch per grid cell into the
    grid-padded output array (pairwise disjoint by construction — proved,
    not assumed, by ``repro.core.verify``)."""
    pad_oh = (-oh) % th
    pad_ow = (-ow) % tw
    return [{
        "name": "out", "block_shape": (1, th, tw, c),
        "index_map": out_block_index,
        "array_shape": (n, oh + pad_oh, ow + pad_ow, c),
        "accumulate": None}]


def store_lanes(ref, x: jnp.ndarray) -> None:
    """Write an ``(h, w, C)`` value into a lane-chunked ``(C // 128, h, w,
    128)`` VMEM ref — the layout Mosaic's strided loads and stores take
    (their base memref must be exactly one 128-lane tile wide)."""
    for j in range(ref.shape[0]):
        ref[j] = x[..., j * LANES:(j + 1) * LANES]


def load_lanes(ref, rows=slice(None), cols=slice(None)) -> jnp.ndarray:
    """Read ``ref[:, rows, cols, :]`` of a lane-chunked ref back as one
    ``(h, w, C)`` value."""
    parts = [ref[j, rows, cols, :] for j in range(ref.shape[0])]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)


def window(op: ir.OpNode, di: int, dj: int, out_h: int, out_w: int):
    """The strided row/column index of window offset ``(di, dj)``."""
    sh, sw = op.attrs["stride"]
    return pl.ds(di, out_h, stride=sh), pl.ds(dj, out_w, stride=sw)


def pool_window(x_ref, op: ir.OpNode, di: int, dj: int, out_h: int,
                out_w: int) -> jnp.ndarray:
    """The ``(out_h, out_w, C)`` inputs that window offset ``(di, dj)``
    reads: strided loads from the VMEM ref holding the pool input."""
    return load_lanes(x_ref, *window(op, di, dj, out_h, out_w))


def _pool_tile(x_ref, op: ir.OpNode, out_h: int, out_w: int
               ) -> jnp.ndarray:
    kh, kw = op.attrs["window"]
    acc = None
    for di in range(kh):
        for dj in range(kw):
            part = pool_window(x_ref, op, di, dj, out_h, out_w)
            if acc is None:
                acc = part
            elif op.fn == "max":
                acc = jnp.maximum(acc, part)
            else:
                acc = acc + part
    if op.fn == "avg":
        acc = acc / float(kh * kw)
    return acc


def tile_valid(shape: tuple[int, int, int], origin: tuple, level: _Level
               ) -> jnp.ndarray:
    """``(h, w, C)`` bool mask: which tile positions lie inside the true
    (unpadded) image at ``level``, given the tile's global ``origin``.
    Built at full tile shape: Mosaic cannot expand an ``(h, w)`` mask."""
    rh = origin[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    rw = origin[1] + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return ((rh >= 0) & (rh < level.image_h)
            & (rw >= 0) & (rw < level.image_w))


def pool_ops(program: ir.StackProgram) -> list[tuple[int, ir.OpNode]]:
    """``(index, op)`` of every POOL2D op, in program order."""
    return [(i, op) for i, op in enumerate(program.ops)
            if op.kind == ir.OpKind.POOL2D]


def tile_scratch(program: ir.StackProgram, levels: list[_Level], c: int,
                 dtype, *, backward: bool = False) -> list:
    """VMEM scratch of one kernel: the halo-grown input patch and the DMA
    semaphore :func:`load_halo` fills it with, then one buffer per pool op
    holding its masked input (:func:`run_tile`), and for the backward one
    more per pool op accumulating its input cotangent."""
    lv0 = levels[0]
    pools = [pltpu.VMEM((c // LANES, levels[i].extent_h,
                         levels[i].extent_w, LANES), dtype)
             for i, _ in pool_ops(program)]
    return ([pltpu.VMEM((lv0.extent_h, dma_width(lv0.extent_w, dtype), c),
                        dtype),
             pltpu.SemaphoreType.DMA(())]
            + pools + (pools if backward else []))


def run_tile(program: ir.StackProgram, levels: list[_Level],
             buf: jnp.ndarray, extras: Mapping[str, jnp.ndarray],
             params: Mapping[str, jnp.ndarray], g0h, g0w,
             pool_refs: Mapping[str, object]
             ) -> tuple[dict, dict, dict]:
    """Depth-first forward of the whole op chain on one resident tile.

    ``buf`` is the halo-grown input patch with global origin ``(g0h, g0w)``
    (unpadded image coordinates); ``extras`` are broadcast side operands as
    ``(1, 1, C)`` values.  Each pool writes its neutral-masked input into
    ``pool_refs[op.name]`` (VMEM scratch) and reads its windows back from
    there.  Returns ``(env, origins, valids)`` where ``valids[op.name]`` is
    each pool's validity mask — with the masked inputs left in
    ``pool_refs``, exactly what the backward's reverse sweep needs.  Shared
    by the forward and backward kernels so the recompute cannot drift from
    the forward.
    """
    env: dict[str, jnp.ndarray] = {program.inputs[0]: buf}
    env.update(extras)
    origins: dict[str, tuple] = {name: (0, 0) for name in extras}
    origins[program.inputs[0]] = (g0h, g0w)
    valids: dict[str, jnp.ndarray] = {}

    for i, op in enumerate(program.ops):
        lv_in = levels[i]
        lv_out = levels[i + 1]
        if op.kind == ir.OpKind.POOL2D:
            x = env[op.inputs[0]]
            oh, ow = origins[op.inputs[0]]
            # mask positions outside the true image at this level; fill with
            # the pool's neutral element = that pool's padding semantics.
            valid = tile_valid(x.shape, (oh, ow), lv_in)
            x_ref = pool_refs[op.name]
            store_lanes(x_ref, jnp.where(
                valid, x, autodiff.pool_neutral(x.dtype, op.fn)))
            valids[op.name] = valid
            y = _pool_tile(x_ref, op, lv_out.extent_h, lv_out.extent_w)
            sh, sw = op.attrs["stride"]
            ph, pw = op.attrs["padding"]
            # exact by construction: origin_in = origin_out * s - p
            origins[op.output] = ((oh + ph) // sh, (ow + pw) // sw)
            env[op.output] = y
        else:
            env[op.output] = ir.apply_op(op, env, params)
            # anchor the origin on a spatial operand (broadcast extras carry
            # no coordinates of their own)
            anchor = next((v for v in op.inputs if v not in extras),
                          op.inputs[0])
            origins[op.output] = origins[anchor]
    return env, origins, valids


def sublanes(dtype) -> int:
    """Rows of one (sublane, 128) vector tile for ``dtype``: 8 for 32-bit
    values, 16 for 16-bit ones."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def dma_width(extent_w: int, dtype) -> int:
    """Columns one halo DMA moves: the halo width rounded up to the sublane
    tile, since the copy may only cut whole tiles out of the source."""
    return -(-extent_w // sublanes(dtype)) * sublanes(dtype)


def load_halo(src_ref, buf_ref, sem, n, h0, w0, extent_w: int
              ) -> jnp.ndarray:
    """Copy the halo-grown patch of image ``n`` at padded origin
    ``(h0, w0)`` from the HBM (``pl.ANY``) input into VMEM, wait for it,
    and return its first ``extent_w`` columns.  Overlapping patches cannot
    be expressed as BlockSpec tiles, and a ``pl.ANY`` ref can only be read
    by DMA.  Compiled, ``w0`` must be a sublane-tile multiple
    (:func:`plan_geometry` checks it) and the copy is :func:`dma_width`
    columns wide."""
    eh, ew = buf_ref.shape[:2]
    w0 = pl.multiple_of(w0, sublanes(buf_ref.dtype))
    copy = pltpu.make_async_copy(
        src_ref.at[n, pl.ds(h0, eh), pl.ds(w0, ew), :], buf_ref, sem)
    copy.start()
    copy.wait()
    return buf_ref[:, :extent_w, :]


def _kernel(program: ir.StackProgram, levels: list[_Level],
            pad_off_h: int, pad_off_w: int, n_extra: int, n_params: int,
            *refs) -> None:
    src_ref = refs[0]
    extra_refs = refs[1: 1 + n_extra]
    param_refs = refs[1 + n_extra: 1 + n_extra + n_params]
    out_ref = refs[1 + n_extra + n_params]
    buf_ref, sem = refs[2 + n_extra + n_params: 4 + n_extra + n_params]
    pool_refs = {op.name: ref for (_, op), ref in
                 zip(pool_ops(program), refs[4 + n_extra + n_params:])}

    n = pl.program_id(0)
    pi = pl.program_id(1)
    pj = pl.program_id(2)

    lv0 = levels[0]
    out_lv = levels[-1]
    # tile origin at the input level, in *unpadded* image coordinates
    g0h = pi * out_lv.extent_h * lv0.mul_h - lv0.off_h
    g0w = pj * out_lv.extent_w * lv0.mul_w - lv0.off_w
    # load from the pre-padded array (always in-bounds)
    buf = load_halo(src_ref, buf_ref, sem, n, g0h + pad_off_h,
                    g0w + pad_off_w, lv0.extent_w)

    # (1, C) param / broadcast-extra blocks against (h, w, C) tiles.
    extras = {name: ref[...][None] for name, ref in
              zip(program.inputs[1:], extra_refs)}
    params = {name: ref[...] for name, ref in
              zip(program.param_names, param_refs)}

    env, _, _ = run_tile(program, levels, buf, extras, params, g0h, g0w,
                         pool_refs)
    out_ref[...] = env[program.outputs[0]][None]


def plan_geometry(program: ir.StackProgram, x: jnp.ndarray,
                  extras: Mapping[str, jnp.ndarray],
                  tile_out_h: int, tile_out_w: int):
    """Shared forward/backward geometry: levels, grid, clamped tile extents,
    and the pre-padded input (every halo load in-bounds, channels padded to
    a lane multiple: the halo DMA cannot slice a part of the 128-lane
    tile).  Returns
    ``(levels, grid, xp, (left_h, left_w), (oh, ow), (pad_oh, pad_ow),
    (th, tw))``."""
    n, h, w, c = x.shape
    in_shapes = {program.inputs[0]: x.shape}
    in_shapes.update({k: jnp.shape(v) for k, v in extras.items()})
    shapes = ir.infer_shapes(program, in_shapes)
    _, oh, ow, _ = shapes[program.outputs[0]]

    th = min(tile_out_h, oh)
    tw = min(tile_out_w, ow)
    pad_oh = (-oh) % th
    pad_ow = (-ow) % tw
    grid = (n, (oh + pad_oh) // th, (ow + pad_ow) // tw)

    image_hw = [(h, w)]
    for op in program.ops:
        s_ = shapes[op.output]
        image_hw.append((s_[1], s_[2]))
    levels = _plan_levels(program.ops, th, tw, image_hw)
    lv0 = levels[0]

    # Pre-pad the input so every halo load is in-bounds.  Left pad covers the
    # most negative origin (off); right pad covers the last tile's reach.
    left_h, left_w = lv0.off_h, lv0.off_w
    last_g0h = (grid[1] - 1) * th * lv0.mul_h - lv0.off_h
    last_g0w = (grid[2] - 1) * tw * lv0.mul_w - lv0.off_w
    right_h = max(0, last_g0h + lv0.extent_h - h)
    right_w = max(0, last_g0w + dma_width(lv0.extent_w, x.dtype) - w)
    # compiled, the halo DMA cuts whole sublane tiles: every patch's padded
    # column origin (pj * tw * mul_w) must start one
    if (grid[2] > 1 and (tw * lv0.mul_w) % sublanes(x.dtype)
            and not kernels.pallas_interpret()):
        raise ValueError(
            f"{program.name}: tile_out_w={tw} x stride {lv0.mul_w} puts "
            f"halo origins off the {sublanes(x.dtype)}-column tile")
    xp = jnp.pad(x, ((0, 0), (left_h, right_h), (left_w, right_w),
                     (0, (-c) % LANES)))
    return (levels, grid, xp, (left_h, left_w), (oh, ow), (pad_oh, pad_ow),
            (th, tw))


def lane_row(v, cp: int) -> jnp.ndarray:
    """A per-channel vector as a ``(1, cp)`` block, zero-padded to the
    padded channel count ``cp`` (the param / broadcast-extra convention)."""
    v = jnp.asarray(v).reshape(1, -1)
    return jnp.pad(v, ((0, 0), (0, cp - v.shape[-1])))


def prep_extras(program: ir.StackProgram,
                extras: Mapping[str, jnp.ndarray], cp: int
                ) -> list[jnp.ndarray]:
    """Broadcast side operands as (1, cp) blocks."""
    return [lane_row(extras[name], cp) for name in program.inputs[1:]]


def fused_nhwc_call(program: ir.StackProgram,
                    x: jnp.ndarray,
                    params: Mapping[str, jnp.ndarray],
                    *,
                    extras: Mapping[str, jnp.ndarray] | None = None,
                    tile_out_h: int = 8,
                    tile_out_w: int = 8) -> jnp.ndarray:
    """Run an nhwc sequence as one fused Pallas kernel.

    ``x`` is the spatial input (``program.inputs[0]``); ``extras`` maps any
    remaining program inputs to broadcast side operands (every non-channel
    dim 1).  Spatially-extended extra inputs are not supported here — the
    dispatcher falls back to the reference path for those.
    """
    extras = dict(extras or {})
    missing = [v for v in program.inputs[1:] if v not in extras]
    if missing:
        raise ValueError(f"{program.name}: missing extra inputs {missing}; "
                         "spatially-extended multi-input stacks fall back "
                         "to the XLA path")
    n, h, w, c = x.shape
    (levels, grid, xp, (left_h, left_w), (oh, ow), (pad_oh, pad_ow),
     (th, tw)) = plan_geometry(program, x, extras, tile_out_h, tile_out_w)

    cp = xp.shape[-1]
    evals = prep_extras(program, extras, cp)
    pvals = [lane_row(params[p], cp) for p in program.param_names]

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    in_specs += [pl.BlockSpec((1, cp), shared_block_index)
                 for _ in evals + pvals]
    out_spec = pl.BlockSpec((1, th, tw, cp), out_block_index)
    out_shape = jax.ShapeDtypeStruct((n, oh + pad_oh, ow + pad_ow, cp),
                                     x.dtype)

    fn = pl.pallas_call(
        functools.partial(_kernel, program, levels, left_h, left_w,
                          len(evals), len(pvals)),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=tile_scratch(program, levels, cp, x.dtype),
        name="nhwc_fwd_kernel",
        interpret=kernels.pallas_interpret(),
    )
    out = fn(xp, *evals, *pvals)
    return out[:, :oh, :ow, :c]
