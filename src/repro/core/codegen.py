"""Code Generator — paper compile-phase step 5.

Turns a :class:`~repro.core.collapse.CollapsePlan` into an executable.
Sequences run serially, communicating through materialized boundary values
(paper §4.2); within a sequence the configured mode decides the schedule:

* ``brainslug`` — the generated Pallas kernels (depth-first, VMEM-tiled).
  Compilation builds *both* halves of each sequence up front: the forward
  kernel and the generated recompute-in-tile backward (one
  :class:`~repro.kernels.fused_stack.ops.FusedExecutable` per sequence), so
  ``jax.grad`` through the executor never constructs kernels on the hot
  path.
* ``xla``       — fused jnp closure (XLA's fusion = breadth-first compiler
  fusion; the beyond-paper comparison point),
* ``barrier``   — per-op materialization (the paper's framework baseline).

KERNEL ops (registry-matched backbone regions, :mod:`repro.core.registry`)
compile here too: :func:`compile_kernel_op` decides the backend (pallas
kernel vs jnp ref twin) once at compile time and returns an executor that
participates in the same structural-signature cache, keyed on kernel id +
operand shapes + static attrs — names are deliberately excluded so two
traced graphs with identical kernel shapes share one compiled closure.

Generated executables are cached on the program's structural signature —
the paper generates code once per equivalent stack and reuses it.  Both
caches are **LRU-bounded** (size from ``OptimizeConfig.code_cache_size``):
a long-lived serve process that keeps seeing new shape signatures must not
leak an executor per signature.  ``clear_cache()`` resets the dispatch
STATS counters alongside, so back-to-back benchmark runs cannot read
stale counts.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core import autodiff
from repro.core import collapse as collapse_mod
from repro.core import ir
from repro.core import registry as registry_mod
from repro.core import verify as verify_mod
from repro.distributed import collectives
from repro.kernels.fused_stack import ops as fused_ops

Executor = Callable[[Mapping[str, jnp.ndarray], Mapping[str, jnp.ndarray]],
                    dict[str, jnp.ndarray]]


def _spec_axis_names(spec) -> set:
    """Mesh axis names a PartitionSpec actually shards over."""
    names: set = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        for axis in (entry if isinstance(entry, tuple) else (entry,)):
            names.add(axis)
    return names


def _sharded_call(mesh, body, in_specs, out_specs):
    """Differentiable shard_map wrapper for bodies built on custom_vjp ops.

    Transposing a shard_map region through a custom_vjp op trips jax's
    spec check whenever a replicated operand is *not* among the
    differentiated inputs: the partial-eval path still emits a cotangent
    for it, and with the replication checker off (required — pallas calls
    inside the region have no replication rule) the transpose cannot
    prove that cotangent replicated, so it raises ``_SpecError``.  Fused
    stacks always carry such operands (scalar constants from the trace).

    So the region is never transposed.  The sharded call is itself a
    custom_vjp: forward runs one shard_map region; backward runs a
    *second forward* shard_map region that recomputes the local vjp
    (recompute-in-backward, same policy as the fused kernels) and psums
    each cotangent over the output-sharded mesh axes its operand does not
    shard — partial products on replicated operands become total, while
    cotangents of sharded operands stay shard-local.

    ``body(*arrays)`` must return a tuple of outputs; every output spec
    must shard the same axis set (the partition planner derives uniform
    row sharding per segment, so this holds by construction).
    """
    in_specs = tuple(in_specs)
    out_specs = tuple(out_specs)
    n_in = len(in_specs)
    out_axes: set = set()
    for s in out_specs:
        out_axes |= _spec_axis_names(s)
    fwd_sm = collectives.shard_map(body, mesh, in_specs=in_specs,
                                   out_specs=out_specs)

    def bwd_region(*arrays):
        prim, gouts = arrays[:n_in], arrays[n_in:]
        _, pull = jax.vjp(body, *prim)
        cts = pull(tuple(gouts))
        fixed = []
        for ct, spec in zip(cts, in_specs):
            reduce_over = tuple(sorted(out_axes - _spec_axis_names(spec)))
            fixed.append(jax.lax.psum(ct, reduce_over) if reduce_over else ct)
        return tuple(fixed)

    bwd_sm = collectives.shard_map(bwd_region, mesh,
                                   in_specs=in_specs + out_specs,
                                   out_specs=in_specs)

    @jax.custom_vjp
    def call(*arrays):
        return fwd_sm(*arrays)

    def call_fwd(*arrays):
        return fwd_sm(*arrays), arrays

    def call_bwd(res, gouts):
        return bwd_sm(*res, *gouts)

    call.defvjp(call_fwd, call_bwd)
    return call


#: LRU over compiled executors (stack plans and kernel dispatches alike).
_CODE_CACHE: "OrderedDict[tuple, Executor]" = OrderedDict()
_CACHE_LIMIT = 256
_LIMIT_PINNED = False          # an explicit set_cache_limit() wins over
#                                per-config floors until the next one


def set_cache_limit(n: int) -> None:
    """Bound both executor caches (this module's code cache and the fused
    forward+backward pair cache behind it) to ``n`` entries, evicting
    least-recently-used entries beyond the bound.  An explicit call pins
    the limit: later config-driven sizing will not silently undo it."""
    global _CACHE_LIMIT, _LIMIT_PINNED
    if n < 1:
        raise ValueError(f"cache limit must be >= 1, got {n}")
    _CACHE_LIMIT = n
    _LIMIT_PINNED = True
    while len(_CODE_CACHE) > _CACHE_LIMIT:
        _CODE_CACHE.popitem(last=False)
    fused_ops.set_cache_limit(n)


def _raise_cache_limit_to(n: int) -> None:
    """Config-driven sizing: the limit is process-global while
    ``code_cache_size`` rides per-OptimizeConfig, so a compile only ever
    *raises* the bound — otherwise a later optimize() with a smaller
    config would evict another live net's executors and trigger silent
    recompilation storms — and never overrides an explicitly pinned
    operator limit (:func:`set_cache_limit`)."""
    global _CACHE_LIMIT
    if _LIMIT_PINNED or n <= _CACHE_LIMIT:
        return
    _CACHE_LIMIT = n
    fused_ops.set_cache_limit(n)


def _cache_get(key: tuple):
    hit = _CODE_CACHE.get(key)
    if hit is not None:
        _CODE_CACHE.move_to_end(key)
    return hit


def _cache_put(key: tuple, value) -> None:
    _CODE_CACHE[key] = value
    _CODE_CACHE.move_to_end(key)
    while len(_CODE_CACHE) > _CACHE_LIMIT:
        _CODE_CACHE.popitem(last=False)


def compile_plan(plan: collapse_mod.CollapsePlan, *, mode: str = "xla",
                 cache_size: int | None = None,
                 mesh=None, part=None) -> Executor:
    """Compile a collapse plan into ``executor(inputs, params) -> outputs``.

    With ``mesh`` (a real :class:`jax.sharding.Mesh`) and an *active*
    ``part`` (:class:`repro.core.partition.SegmentPartition`), the
    executor body runs inside a shard_map region with the partition's
    boundary specs: each device executes the plan on its shard — which is
    exactly the shape the plan was collapsed against — and the outer
    executor keeps the global dict-in/dict-out contract."""
    if cache_size is not None:
        _raise_cache_limit_to(cache_size)
    wrap = mesh is not None and part is not None and part.active
    # plan.input_shapes keeps same-signature plans with identical tile
    # geometry but different image extents from sharing one executor.
    # Mesh identity + boundary specs join the key: the same plan wrapped
    # for a different mesh (or unwrapped) must not share a closure.
    dist_key = None
    if wrap:
        dist_key = (id(mesh),
                    tuple(sorted((k, s) for k, s in
                                 (*part.in_specs.items(),
                                  *part.out_specs.items(),
                                  *part.param_specs.items()))))
    key = (plan.program.signature(), mode, plan.input_shapes,
           tuple((s.tile_rows, s.tile_out_h, s.tile_out_w)
                 for s in plan.sequences), dist_key)
    cached = _cache_get(key)
    if cached is not None:
        return cached

    subprograms = [plan.subprogram(i) for i in range(len(plan.sequences))]

    if mode == "brainslug":
        # Generate-once: build the fused forward+backward pair per sequence
        # now (cached on structural signature inside fused_ops, so
        # equivalent sequences across stacks share one pair).
        for sub, seq in zip(subprograms, plan.sequences):
            fused_ops.get_executable(
                sub, tile_rows=seq.tile_rows or 256,
                tile_out_h=seq.tile_out_h or 8,
                tile_out_w=seq.tile_out_w or 8)

    def run_body(inputs: Mapping[str, jnp.ndarray],
                 params: Mapping[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        env = dict(inputs)
        for sub, seq in zip(subprograms, plan.sequences):
            out = fused_ops.fused_stack_apply(
                sub, {k: env[k] for k in sub.inputs}, params, mode=mode,
                tile_rows=seq.tile_rows or 256,
                tile_out_h=seq.tile_out_h or 8,
                tile_out_w=seq.tile_out_w or 8)
            env.update(out)
        return {v: env[v] for v in plan.program.outputs}

    if not wrap:
        _cache_put(key, run_body)
        return run_body

    in_names = tuple(plan.program.inputs)
    param_names = tuple(part.param_specs)
    out_names = tuple(plan.program.outputs)

    def positional(*arrays):
        inputs = dict(zip(in_names, arrays[:len(in_names)]))
        params = dict(zip(param_names, arrays[len(in_names):]))
        out = run_body(inputs, params)
        return tuple(out[v] for v in out_names)

    sharded = _sharded_call(
        mesh, positional,
        in_specs=(tuple(part.in_specs[v] for v in in_names)
                  + tuple(part.param_specs[p] for p in param_names)),
        out_specs=tuple(part.out_specs[v] for v in out_names))

    def executor(inputs: Mapping[str, jnp.ndarray],
                 params: Mapping[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        arrays = [inputs[v] for v in in_names]
        arrays += [jnp.asarray(params[p]) for p in param_names]
        return dict(zip(out_names, sharded(*arrays)))

    _cache_put(key, executor)
    return executor


#: KERNEL-op attr keys that are plumbing, not compiled-code parameters.
_KERNEL_PLUMBING_ATTRS = frozenset({"slots", "kernel"})


def _unknown_kernel_error(op: ir.OpNode) -> verify_mod.VerifyError:
    """A bare KeyError from deep inside codegen names nothing; raise the
    verifier's structured error with op + invariant instead."""
    return verify_mod.VerifyError([verify_mod.Finding(
        "kernel.unknown", "error", op.name,
        f"kernel id {op.attrs.get('kernel')!r} has no registry entry "
        f"(known: {sorted(registry_mod.REGISTRY)})")])


def kernel_inner(op: ir.OpNode, *, backend: registry_mod.KernelType,
                 cache_size: int | None = None) -> Callable:
    """The positional compiled closure for one KERNEL op on an explicit
    backend.  Cached on kernel id + backend + static attrs only, so
    identically-shaped kernel sites across traced graphs share one entry;
    the autotuner calls this directly to measure PALLAS against REF on
    the same operands before committing a dispatch."""
    if cache_size is not None:
        _raise_cache_limit_to(cache_size)
    try:
        entry = registry_mod.get(op.attrs["kernel"])
    except KeyError:
        raise _unknown_kernel_error(op) from None
    static = {k: v for k, v in op.attrs.items()
              if k not in _KERNEL_PLUMBING_ATTRS}
    key = ("kernel", entry.name, backend.value, ir._freeze(static))
    inner = _cache_get(key)
    if inner is not None:
        return inner
    stat_key = f"{entry.name}_{backend.value}"
    out_shape = tuple(op.attrs["out_shape"])
    out_dtype = op.attrs["out_dtype"]

    if backend is registry_mod.KernelType.PALLAS:
        call = lambda *arrays: entry.pallas(list(arrays),  # noqa: E731
                                            static)
        if entry.vjp == "ref":
            # entry declares no custom rule on its pallas path:
            # wrap it so jax.grad recomputes through the jnp twin
            call = autodiff.with_ref_vjp(
                call, lambda *arrays: entry.ref(list(arrays), static))
    else:
        # the jnp twin differentiates natively under jax.vjp
        call = lambda *arrays: entry.ref(list(arrays), static)  # noqa: E731

    def inner(*arrays):
        registry_mod.STATS.record(stat_key)
        return jnp.reshape(call(*arrays), out_shape).astype(out_dtype)

    _cache_put(key, inner)
    return inner


def compile_kernel_op(op: ir.OpNode, *, mode: str = "xla",
                      cache_size: int | None = None,
                      backend: registry_mod.KernelType | None = None,
                      reason: str | None = None,
                      mesh=None, part=None
                      ) -> tuple[Executor, registry_mod.KernelDispatch]:
    """Compile one registry KERNEL op; returns (executor, dispatch record).

    The backend decision (pallas kernel vs ref twin) is made here, once,
    from the traced operand shapes — and returned so ``report()`` can
    surface a constraint-driven fallback instead of hiding it.  An
    explicit ``backend`` (with its ``reason``) overrides the static
    planner — the autotuner's measured dispatch arrives through it.

    With ``mesh`` + an active ``part``, the positional kernel closure is
    wrapped in a shard_map region with the partition's per-slot specs
    (batch/rows over "data", heads/features over "model") — *outside*
    the shared ``kernel_inner`` cache, so the unwrapped closure stays
    shareable with single-device compiles and the autotuner."""
    if backend is None:
        try:
            dispatch = registry_mod.plan_dispatch(op, mode)
        except KeyError:
            raise _unknown_kernel_error(op) from None
    else:
        dispatch = registry_mod.KernelDispatch(op.attrs["kernel"], backend,
                                               reason)
    slots = op.attrs["slots"]
    out_name = op.output

    if mesh is not None and part is not None and part.active:
        # Inside the shard_map region the kernel sees per-shard operands:
        # compile the inner closure against the per-shard shapes (its
        # reshape target and any shape-derived grid must be shard-local).
        shard_op = dataclasses.replace(op, attrs={
            **op.attrs,
            "arg_shapes": tuple(tuple(part.shard_shapes[f"arg{i}"])
                                for i in range(len(slots))),
            "out_shape": tuple(part.shard_shapes[out_name])})
        shard_inner = kernel_inner(shard_op, backend=dispatch.backend,
                                   cache_size=cache_size)
        tupled = _sharded_call(
            mesh, lambda *arrays: (shard_inner(*arrays),),
            in_specs=tuple(part.in_specs[f"arg{i}"]
                           for i in range(len(slots))),
            out_specs=(part.out_specs[out_name],))
        inner = lambda *arrays: tupled(*arrays)[0]  # noqa: E731
    else:
        inner = kernel_inner(op, backend=dispatch.backend,
                             cache_size=cache_size)

    def executor(inputs: Mapping[str, jnp.ndarray],
                 params: Mapping[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        arrays = []
        for slot in slots:
            if slot[0] == "in":
                arrays.append(inputs[slot[1]])
            else:
                v = params[slot[1]]
                if len(slot) > 2 and slot[2] is not None:
                    shape, dtype = slot[2]     # broadcast-alias view spec
                    v = jnp.broadcast_to(jnp.asarray(v), shape).astype(dtype)
                arrays.append(v)
        return {out_name: inner(*arrays)}

    return executor, dispatch


def clear_cache() -> None:
    """Drop every compiled executor *and* zero the dispatch counters —
    back-to-back benchmark runs must not read stale counts."""
    _CODE_CACHE.clear()
    fused_ops.clear_executable_cache()
    fused_ops.STATS.reset()
    registry_mod.STATS.reset()
