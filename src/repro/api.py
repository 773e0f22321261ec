"""Public BrainSlug API — ``repro.api``.

The paper's promise is *transparency*: ``brainslug.optimize(model)`` on an
unmodified network (Listing 3).  This facade delivers the JAX version of
that promise:

    from repro import api

    net = api.optimize(fn, *example_args,
                       config=api.OptimizeConfig(mode="brainslug"))
    y = net(*args)          # same signature / pytree structure as fn
    print(net.explain())    # ops captured vs. left opaque, HBM traffic

``optimize`` traces the plain JAX callable into the BrainSlug IR
(:mod:`repro.core.trace`), partitions it into opaque segments and
optimizable stacks, collapses each stack against the device budget, and
returns a drop-in callable.  The result is jit-compatible, and — with
``config.differentiable=True`` — grad-compatible through the generated
depth-first backward kernels (:mod:`repro.core.autodiff`).

The IR-level entry points remain available for code that already builds
graphs by hand, but new code should not: :func:`optimize_graph` and
:func:`optimize_stack` are deprecated re-exports of
:mod:`repro.core.api` and will warn for one release before being dropped
from this namespace.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import analyzer, codegen, collapse, ir
from repro.core import api as core_api
from repro.core import autotune as autotune_mod
from repro.core import registry as registry_mod
from repro.core import trace as trace_mod
from repro.core import verify as verify_mod
from repro.obs import SPANS, span

# Canonical re-exports: the config and report types live with the core
# implementation; this module is the supported way to reach them.
OptimizeConfig = core_api.OptimizeConfig
CoverageReport = core_api.CoverageReport
StackCoverage = core_api.StackCoverage
KernelCoverage = core_api.KernelCoverage
OptimizedNet = core_api.OptimizedNet
MODES = core_api.MODES
LAYOUTS = core_api.LAYOUTS
TraceResult = trace_mod.TraceResult
KernelType = registry_mod.KernelType
KernelDispatch = registry_mod.KernelDispatch

__all__ = [
    "optimize", "OptimizedFn", "OptimizeConfig", "CoverageReport",
    "StackCoverage", "KernelCoverage", "KernelType", "KernelDispatch",
    "TraceResult", "MODES", "LAYOUTS",
    "optimize_graph", "optimize_stack",
]


@dataclasses.dataclass(eq=False)        # identity hash: jax.jit(net) works
class OptimizedFn:
    """A traced-and-rewritten callable (the paper's optimized model).

    Drop-in for the original function: same positional signature, same
    output pytree.  Collapsed stacks run under ``config.mode``; everything
    else executes breadth-first exactly as traced.
    """

    trace_result: trace_mod.TraceResult
    segments: list
    executors: dict[int, codegen.Executor]
    plans: dict[int, collapse.CollapsePlan]
    config: OptimizeConfig
    shapes: dict[str, tuple[int, ...]] = dataclasses.field(
        default_factory=dict)          # value name -> shape
    param_shapes: dict[str, tuple[int, ...]] = dataclasses.field(
        default_factory=dict)          # param name -> shape
    kernel_dispatches: dict[int, registry_mod.KernelDispatch] = \
        dataclasses.field(default_factory=dict)
    kernel_matches: tuple = ()         # registry KernelMatch records
    #: Committed autotune decisions by segment index; -1 is the
    #: function-level floor (optimized vs the raw traced callable).
    autotune_decisions: dict[int, autotune_mod.Decision] = \
        dataclasses.field(default_factory=dict)
    #: Set when the function-level floor measured the whole rewrite
    #: slower than the raw function: __call__ delegates to the raw
    #: callable (still validated) — never-slower, end to end.
    passthrough: Callable | None = None
    #: Static-verifier findings recorded at compile time
    #: (:mod:`repro.core.verify`).  Under ``verify='warn'`` error findings
    #: are waived but kept here and re-emitted by :meth:`report`, so a
    #: long-lived serving process can read back what was waived long
    #: after the compile-time warning scrolled away.
    verify_findings: tuple = ()
    #: Mesh partition plan (None for single-device compiles).  When set,
    #: stack/kernel executors run inside shard_map regions and
    #: :meth:`__call__` places concrete input leaves on the mesh
    #: batch-sharded, so data never round-trips through one device.
    partitions: Any = None
    #: Where the ``optimize()`` call spent its host time: count and
    #: seconds of each ``optimize.*`` / ``trace.*`` span it opened
    #: (:mod:`repro.obs`), printed by :meth:`explain`.
    setup_spans: dict[str, dict] = dataclasses.field(default_factory=dict)

    def _place_inputs(self, leaves: list) -> list:
        """Shard concrete input leaves over the mesh's "data" axis (a
        placement hint — global-view semantics are identical; tracers
        and already-committed arrays pass through untouched)."""
        mesh = self.config.mesh
        if (self.partitions is None or mesh is None
                or not hasattr(mesh, "devices")):
            return leaves
        from jax.sharding import NamedSharding

        from repro.core import partition as partition_mod
        axes = self.partitions.axes
        placed = []
        for leaf, (shape, _dtype) in zip(leaves,
                                         self.trace_result.leaf_avals):
            if isinstance(leaf, jax.core.Tracer) or not hasattr(
                    leaf, "shape"):
                placed.append(leaf)
                continue
            spec = partition_mod.batch_leaf_spec(
                tuple(shape), self.config.partition, axes)
            try:
                placed.append(jax.device_put(leaf,
                                             NamedSharding(mesh, spec)))
            except Exception:          # committed elsewhere: leave it be
                placed.append(leaf)
        return placed

    def __call__(self, *args):
        tr = self.trace_result
        leaves, tree = jax.tree_util.tree_flatten(args)
        if tree != tr.in_tree:
            raise TypeError(
                f"optimized {tr.graph.name!r} was traced with argument "
                f"structure {tr.in_tree}, called with {tree}")
        for i, (leaf, (shape, dtype)) in enumerate(
                zip(leaves, tr.leaf_avals)):
            got = (tuple(jnp.shape(leaf)),
                   jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype")
                   else leaf.dtype)
            if got[0] != shape or got[1] != dtype:
                # every executor/bind closure is specialized to the traced
                # avals — fail loudly instead of deep inside a kernel
                raise TypeError(
                    f"optimized {tr.graph.name!r}: argument leaf {i} was "
                    f"traced as {dtype}{list(shape)}, called with "
                    f"{got[1]}{list(got[0])}; re-run optimize() for new "
                    f"shapes/dtypes")
        if self.passthrough is not None:
            return self.passthrough(*args)
        leaves = self._place_inputs(leaves)
        params = dict(tr.const_params)
        for i, leaf in enumerate(leaves):
            params[f"arg{i}"] = leaf
        env = core_api.run_segments(self.segments, self.executors,
                                    {tr.input_name: leaves[0]}, params)
        outs = []
        for kind, ref in tr.out_refs:
            if kind == "env":
                outs.append(env[ref])
            elif kind == "leaf":
                outs.append(leaves[ref])
            else:                                  # captured constant
                outs.append(ref)
        return jax.tree_util.tree_unflatten(tr.out_tree, outs)

    # -- introspection -----------------------------------------------------

    @property
    def graph(self) -> ir.NetGraph:
        return self.trace_result.graph

    @property
    def n_stacks(self) -> int:
        return len(self.executors)

    @property
    def n_sequences(self) -> int:
        return sum(len(p.sequences) for p in self.plans.values())

    def report(self) -> CoverageReport:
        """Per-stack coverage (ops captured vs. left opaque, planned HBM
        traffic from the :mod:`repro.core.resource` model) plus per-kernel
        registry hit counts with the backend that actually ran — a
        constraint-driven ref fallback is recorded, never silent."""
        return core_api.coverage_report(self.segments, self.plans,
                                        self.shapes, self.config.itemsize,
                                        kernel_dispatch=self.kernel_dispatches,
                                        autotune=self.autotune_decisions,
                                        verify=self.verify_findings,
                                        partitions=self.partitions,
                                        mode=self.config.mode)

    def explain(self) -> str:
        """Human-readable :meth:`report`, then the set-up split by span."""
        lines = [str(self.report())]
        if self.setup_spans:
            lines.append("optimize() set-up:")
            lines += [f"  {name:18s} {v['count']:5d}x {v['seconds']:9.3f} s"
                      for name, v in self.setup_spans.items()]
        return "\n".join(lines)


def optimize(fn: Callable, *example_args: Any,
             config: OptimizeConfig = OptimizeConfig()) -> OptimizedFn:
    """Trace a plain JAX callable and rewrite it BrainSlug-style.

    ``example_args`` are example inputs (any pytree of arrays, as for
    ``jax.jit``); the optimized callable is specialized to their
    shapes/dtypes.  Unrecognized primitives are kept as opaque ops —
    ``optimize`` never rejects a function, it just captures less of it
    (see :meth:`OptimizedFn.report`).
    """
    before = SPANS.snapshot()
    with span("optimize.trace"):
        tr = trace_mod.trace(fn, *example_args)
    # registry pass: backbone clusters a depth-first stack can't absorb
    # (attention / rmsnorm / swiglu / vocab-CE) dispatch to the dedicated
    # kernels instead of replaying OPAQUE prim.bind soup
    matches: tuple = ()
    if config.kernel_registry:
        with span("optimize.registry"):
            tr, matches = registry_mod.rewrite(tr, mode=config.mode)
    # every traced output must survive the rewrite, even one produced
    # mid-stack with no in-graph consumer (stack executors only
    # materialize their declared outputs)
    keep = frozenset(ref for kind, ref in tr.out_refs if kind == "env")
    # graph-level static verification (SSA / dead values / recorded-aval
    # consistency) before segmentation; plan/kernel-level checks run
    # inside compile_stacks, between the collapse and codegen stages
    graph_findings: tuple = ()
    if config.verify != "off":
        with span("optimize.verify"):
            graph_findings = tuple(verify_mod.verify_trace(tr))
            verify_mod.enforce(graph_findings, config.verify,
                               subject=tr.graph.name)
    # Autotuning (incl. the function-level floor) is disabled under a
    # mesh: timing forced host devices would commit nonsense decisions.
    under_mesh = config.mesh is not None and config.partition != "none"
    tuner = (autotune_mod.Autotuner.from_config(config)
             if config.autotune and not under_mesh else None)
    with span("optimize.compile"):
        segments = analyzer.analyze(tr.graph, layout="auto", keep=keep)
        executors, plans, dispatches, tuned, findings, parts = \
            core_api.compile_stacks(
                segments, tr.shapes, config, param_shapes=tr.param_shapes,
                dtypes=tr.dtypes, tuner=tuner)
    net = OptimizedFn(trace_result=tr, segments=segments,
                      executors=executors, plans=plans, config=config,
                      shapes=dict(tr.shapes),
                      param_shapes=dict(tr.param_shapes),
                      kernel_dispatches=dispatches,
                      kernel_matches=matches, autotune_decisions=tuned,
                      verify_findings=graph_findings + findings,
                      partitions=parts)
    if tuner is not None:
        with span("optimize.floor"):
            _floor_whole_function(tuner, net, fn, example_args, config)
    net.setup_spans = {k: v for k, v in SPANS.delta(before).items()
                       if v["count"]}
    return net


def _sig_value(v):
    """Stable attr freeze for cache keys: opaque ops hold replay closures
    whose default repr embeds a memory address — key on their qualname."""
    if isinstance(v, (list, tuple)):
        return tuple(_sig_value(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _sig_value(x)) for k, x in v.items()))
    if callable(v):
        return getattr(v, "__qualname__", type(v).__name__)
    return ir._freeze(v)


def _graph_signature(graph: ir.NetGraph) -> str:
    return repr(tuple(
        (op.kind.value, op.fn, op.inputs, op.output, op.params,
         tuple(sorted((k, _sig_value(v)) for k, v in op.attrs.items())))
        for op in graph.ops))


def _floor_whole_function(tuner, net: OptimizedFn, fn: Callable,
                          example_args: tuple,
                          config: OptimizeConfig) -> None:
    """The end-to-end guardrail: measure the whole rewritten callable
    against the raw traced function on the example args.  When the
    rewrite loses, ``net`` delegates to the raw callable (per-segment
    wins cannot always survive whole-graph XLA fusion).  Any failure
    here leaves the rewrite in place — the floor never raises."""
    tr = net.trace_result
    key_obj = {
        "kind": "function", "name": tr.graph.name,
        "sig": _graph_signature(tr.graph),
        "avals": [[list(s), str(d)] for s, d in tr.leaf_avals],
        "mode": config.mode,
        "differentiable": config.differentiable,
        "kernel_registry": config.kernel_registry,
        "backend": jax.default_backend(),
    }
    try:
        builders = {
            "raw": lambda: [("fwd", jax.jit(fn), example_args)],
            "optimized": lambda: [("fwd", jax.jit(net), example_args)],
        }
        decision = tuner.decide(key_obj, kind="function",
                                name=tr.graph.name,
                                requested="optimized", baseline="raw",
                                builders=builders)
    except Exception:                    # pragma: no cover - belt&braces
        return
    net.autotune_decisions[-1] = decision
    if decision.variant == "raw":
        net.passthrough = fn


# ---------------------------------------------------------------------------
# Deprecated IR-level entry points (one release of warnings, then removal
# from this namespace; repro.core.api keeps them for IR-building code).
# ---------------------------------------------------------------------------

def optimize_graph(*args, **kwargs) -> core_api.OptimizedNet:
    """Deprecated: use :func:`optimize` on a plain JAX function instead."""
    warnings.warn(
        "repro.api.optimize_graph is deprecated and will be removed from "
        "this namespace in the next release; use repro.api.optimize(fn, "
        "*example_args) — it traces plain JAX functions, no hand-built "
        "NetGraph needed (repro.core.api.optimize_graph remains for "
        "IR-level code).", DeprecationWarning, stacklevel=2)
    return core_api.optimize_graph(*args, **kwargs)


def optimize_stack(*args, **kwargs) -> codegen.Executor:
    """Deprecated: use :func:`optimize` on a plain JAX function instead."""
    warnings.warn(
        "repro.api.optimize_stack is deprecated and will be removed from "
        "this namespace in the next release; use repro.api.optimize(fn, "
        "*example_args) — it traces plain JAX functions, no hand-built "
        "StackProgram needed (repro.core.api.optimize_stack remains for "
        "IR-level code).", DeprecationWarning, stacklevel=2)
    return core_api.optimize_stack(*args, **kwargs)
