"""Public BrainSlug API — the paper's ``brainslug.optimize(model)``.

Two entry points:

* :func:`optimize_graph` — the transparent whole-network path (CNN family):
  takes a :class:`~repro.core.ir.NetGraph`, finds optimizable runs, collapses
  them against the device budget, and returns an :class:`OptimizedNet` whose
  ``__call__`` executes opaque ops breadth-first and collapsed stacks
  depth-first.
* :func:`optimize_stack` — the composable path used by the LM layers: takes a
  single :class:`~repro.core.ir.StackProgram` (a block's norm/act/residual
  chain) and returns a fused executor.  Model code stays declarative; the
  execution mode is a config knob.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

import jax.numpy as jnp

from repro.core import analyzer, codegen, collapse, ir, resource
from repro.core import autotune as autotune_mod
from repro.core import partition as partition_mod
from repro.core import registry as registry_mod
from repro.core import verify as verify_mod

#: Execution modes an OptimizeConfig accepts (validated eagerly — a typo
#: used to surface only deep inside codegen, as an opaque dispatch error).
MODES = ("brainslug", "xla", "barrier")

#: Layouts the graph entry points accept (``auto`` classifies per stack).
LAYOUTS = analyzer.LAYOUTS


@dataclasses.dataclass(frozen=True)
class OptimizeConfig:
    mode: str = "xla"            # 'brainslug' | 'xla' | 'barrier'
    device: resource.DeviceSpec = resource.TPU_V5E
    itemsize: int = 4
    max_steps_per_sequence: int | None = None
    # Size collapse plans for training: the generated rows backward holds
    # the recomputed forward chain *and* live cotangents in VMEM, so
    # differentiable plans get smaller tiles / earlier sequence splits.
    differentiable: bool = False
    # Rewrite traced OPAQUE backbone clusters (attention / rmsnorm /
    # swiglu / vocab-CE) onto the dedicated kernels via the registry
    # (repro.core.registry); only affects the traced repro.api.optimize
    # path.
    kernel_registry: bool = True
    # LRU bound for the compiled-executor caches (codegen code cache and
    # the fused fwd+bwd pair cache).  Generous by default; a long-lived
    # serve process cycling through shape signatures stays bounded.
    code_cache_size: int = 256
    # Measured autotuning (repro.core.autotune): micro-benchmark the
    # candidate execution variants per segment on the traced shapes and
    # commit the winner, hard-floored at the barrier/ref baseline so a
    # losing fused variant degrades gracefully.  Off by default: the
    # static planner stays deterministic and compile stays cheap unless
    # the never-slower contract is asked for.
    autotune: bool = False
    # Decision-cache directory (None -> $REPRO_AUTOTUNE_CACHE, else
    # ~/.cache/repro/autotune).  Entries are checksummed and
    # version-keyed; corrupt or stale files are quarantined, never fatal.
    autotune_cache_dir: str | None = None
    autotune_repeats: int = 3        # median-of-k timing
    autotune_warmup: int = 1         # untimed calls before the k
    # Per-candidate budget: a non-baseline candidate whose first call
    # (tracing + compile included) exceeds this is disqualified with a
    # recorded reason instead of stalling compile time.  The baseline is
    # exempt — the floor must always exist.
    autotune_timeout_ms: float | None = 2000.0
    # Mesh execution (repro.core.partition): a jax.sharding.Mesh (or a
    # partition.MeshAxes skeleton for static lint) plus a partition mode.
    # With a mesh, stacks and registry kernels compile inside shard_map
    # regions with derived PartitionSpecs, and collapse sizes tiles
    # against the *per-shard* shapes on a haircut per-device VMEM budget
    # (resource.shard_device).  partition='data' shards batch/rows,
    # 'tensor' shards heads/features, 'both' does both, 'none' ignores
    # the mesh.  Autotuning is disabled under a mesh: micro-benchmarks on
    # forced host devices would commit nonsense decisions.
    mesh: object | None = None
    partition: str = "none"
    # Static plan verification (repro.core.verify): re-derive every
    # compile artifact's invariants between the collapse and codegen
    # stages.  'strict' raises VerifyError on any violation before
    # anything compiles; 'warn' (default) records findings on the
    # optimized net + emits one UserWarning; 'off' skips the pass
    # entirely (zero compile-time cost).
    verify: str = "warn"

    def __post_init__(self) -> None:
        if self.mode not in MODES:
            raise ValueError(
                f"unknown mode {self.mode!r}; allowed modes: {MODES}")
        if self.verify not in verify_mod.VERIFY_MODES:
            raise ValueError(
                f"unknown verify mode {self.verify!r}; allowed: "
                f"{verify_mod.VERIFY_MODES}")
        if not isinstance(self.itemsize, int) or self.itemsize <= 0:
            raise ValueError(
                f"itemsize must be a positive int, got {self.itemsize!r}")
        if not isinstance(self.code_cache_size, int) \
                or self.code_cache_size < 1:
            raise ValueError(
                f"code_cache_size must be a positive int, got "
                f"{self.code_cache_size!r}")
        if not isinstance(self.autotune_repeats, int) \
                or self.autotune_repeats < 1:
            raise ValueError(
                f"autotune_repeats must be a positive int, got "
                f"{self.autotune_repeats!r}")
        if not isinstance(self.autotune_warmup, int) \
                or self.autotune_warmup < 0:
            raise ValueError(
                f"autotune_warmup must be a non-negative int, got "
                f"{self.autotune_warmup!r}")
        if self.partition not in partition_mod.PARTITIONS:
            raise ValueError(
                f"unknown partition {self.partition!r}; allowed: "
                f"{partition_mod.PARTITIONS}")
        if self.partition != "none" and self.mesh is None:
            raise ValueError(
                f"partition={self.partition!r} needs a mesh "
                "(OptimizeConfig(mesh=..., partition=...))")


#: OpKinds the paper leaves untouched by design ("Convolution and linear
#: layers cannot be optimized") — reported separately from OPAQUE fallbacks,
#: which are ops the frontend failed to recognize.
BACKBONE_KINDS = frozenset({
    ir.OpKind.MATMUL, ir.OpKind.CONV2D, ir.OpKind.ATTENTION,
    ir.OpKind.SSD, ir.OpKind.EMBED,
})


@dataclasses.dataclass(frozen=True)
class StackCoverage:
    """Per-stack slice of a :class:`CoverageReport`."""

    name: str
    n_ops: int
    kinds: tuple[str, ...]
    n_sequences: int
    hbm_breadth_bytes: int      # breadth-first traffic of this stack
    hbm_depth_bytes: int        # planned depth-first traffic
    layout: str = "rows"        # 'rows' | 'nhwc'
    #: how the stack executes: 'generated' (the brainslug Pallas kernels),
    #: 'xla' or 'barrier' — the committed variant when autotuned
    execution: str = "xla"


@dataclasses.dataclass(frozen=True)
class KernelCoverage:
    """One registry-dispatched KERNEL op in the rewritten network."""

    op_name: str
    kernel: str                 # registry id: attention / rmsnorm / ...
    backend: str                # 'pallas' | 'ref'
    fallback_reason: str | None = None   # why ref ran (None for pallas)


@dataclasses.dataclass(frozen=True)
class AutotuneCoverage:
    """One committed autotune decision in the ``report()`` payload."""

    name: str                 # stack / kernel / function label
    kind: str                 # 'stack' | 'kernel' | 'function' | 'callable'
    requested: str            # statically configured variant
    baseline: str             # never-slower floor
    chosen: str               # what actually runs
    guardrail_tripped: bool   # the floor overrode the requested variant
    source: str               # 'measured' | 'cache-mem' | 'cache-disk'
    measured_ms: tuple = ()   # ((variant, phase, ms), ...)
    events: tuple = ()        # cache hit/miss/quarantine notes
    failures: tuple = ()      # ((variant, reason), ...)


@dataclasses.dataclass(frozen=True)
class DistCoverage:
    """One mesh-partitioned segment in the ``report()`` payload: the
    shard_map boundary specs that were committed and the per-shard VMEM
    budget the tiles were actually sized against."""

    name: str                   # stack name / kernel op name
    kind: str                   # 'stack' | 'kernel'
    in_specs: tuple[tuple[str, str], ...]    # (operand, spec) as strings
    out_specs: tuple[tuple[str, str], ...]
    active: bool                # False: every operand ended up replicated
    shard_budget_bytes: int     # haircut per-device budget (0: no plan)
    notes: tuple[str, ...] = ()  # why a split was fenced / replicated


@dataclasses.dataclass(frozen=True)
class CoverageReport:
    """What the optimizer captured — the ``report()``/``explain()`` payload.

    ``capture_ratio`` is computed over the ops that *could* have been
    captured: everything except the backbone kinds (matmul / conv /
    attention / ssd / embed), which the paper's optimizer leaves untouched
    by design, and KERNEL ops, which the registry already routed to a
    dedicated kernel.  ``n_opaque`` counts frontend fallbacks — ops that
    stayed OPAQUE because no lifting rule recognized them.  ``kernels``
    lists every registry dispatch including the backend that actually ran,
    so a constraint-driven ref fallback is visible, never silent.
    """

    n_ops: int
    n_captured: int
    n_opaque: int
    n_backbone: int
    n_stacks: int
    capture_ratio: float
    stacks: tuple[StackCoverage, ...]
    n_synthetic: int = 0        # tracer plumbing (bind/proj), not fn ops
    n_kernel: int = 0           # registry-dispatched KERNEL ops
    kernels: tuple[KernelCoverage, ...] = ()
    autotune: tuple[AutotuneCoverage, ...] = ()
    #: Static-verifier findings recorded at compile time
    #: (repro.core.verify.Finding records) — under verify='warn' these are
    #: the violations that were waived; a long-lived serving process reads
    #: them back here long after the compile-time warning scrolled away.
    verify: tuple = ()
    #: Mesh partitioning: ("data", 4), ("model", 2)-style axis extents
    #: (empty when no mesh was configured) and one DistCoverage per
    #: partitioned segment.
    mesh_axes: tuple = ()
    dist: tuple[DistCoverage, ...] = ()

    @property
    def verify_errors(self) -> int:
        """Error-severity findings the verify='warn' run waived."""
        return sum(1 for f in self.verify if f.severity == "error")

    @property
    def verify_warnings(self) -> int:
        return sum(1 for f in self.verify if f.severity != "error")

    @property
    def guardrail_trips(self) -> int:
        """Decisions where the never-slower floor overrode the requested
        variant (the autotune acceptance-criteria stat)."""
        return sum(1 for a in self.autotune if a.guardrail_tripped)

    @property
    def autotune_cache_hits(self) -> int:
        return sum(1 for a in self.autotune
                   if a.source in ("cache-mem", "cache-disk"))

    @property
    def kernel_hits(self) -> dict[str, int]:
        """Per-kernel registry hit count (the acceptance-criteria stat)."""
        hits: dict[str, int] = {}
        for k in self.kernels:
            hits[k.kernel] = hits.get(k.kernel, 0) + 1
        return hits

    @property
    def kernel_fallbacks(self) -> dict[str, int]:
        """Per-kernel count of dispatches that ran the ref twin."""
        falls: dict[str, int] = {}
        for k in self.kernels:
            if k.backend != "pallas":
                falls[k.kernel] = falls.get(k.kernel, 0) + 1
        return falls

    def __str__(self) -> str:
        lines = [
            f"ops total={self.n_ops}  captured={self.n_captured}  "
            f"opaque-fallback={self.n_opaque}  backbone={self.n_backbone}  "
            f"kernels={self.n_kernel}  stacks={self.n_stacks}  "
            f"capture_ratio={100.0 * self.capture_ratio:.1f}%",
        ]
        for s in self.stacks:
            ratio = s.hbm_breadth_bytes / max(s.hbm_depth_bytes, 1)
            lines.append(
                f"  stack {s.name:28s} {s.layout:4s} {s.execution:9s} "
                f"ops={s.n_ops:3d} seqs={s.n_sequences}  HBM "
                f"{s.hbm_breadth_bytes / 2**20:8.2f} MiB -> "
                f"{s.hbm_depth_bytes / 2**20:8.2f} MiB  ({ratio:.2f}x)")
        for k in self.kernels:
            note = (f"  (fallback: {k.fallback_reason})"
                    if k.fallback_reason else "")
            lines.append(
                f"  kernel {k.kernel:12s} {k.op_name:28s} "
                f"backend={k.backend}{note}")
        for a in self.autotune:
            trip = "  GUARDRAIL" if a.guardrail_tripped else ""
            times = "  ".join(f"{v}/{p}={ms:.3f}ms"
                              for v, p, ms in a.measured_ms)
            lines.append(
                f"  autotune {a.kind:8s} {a.name:24s} "
                f"{a.requested} -> {a.chosen} [{a.source}]{trip}"
                + (f"  {times}" if times else ""))
            for ev in a.events:
                lines.append(f"    note: {ev}")
            for variant, why in a.failures:
                lines.append(f"    candidate {variant} failed: {why}")
        if self.mesh_axes:
            lines.append("  mesh " + " x ".join(
                f"{n}={e}" for n, e in self.mesh_axes))
        for d in self.dist:
            state = "sharded" if d.active else "replicated"
            specs = "  ".join(f"{k}={s}" for k, s in d.in_specs)
            budget = (f"  per-shard VMEM budget="
                      f"{d.shard_budget_bytes / 2**20:.2f} MiB"
                      if d.shard_budget_bytes else "")
            lines.append(f"  dist {d.kind:6s} {d.name:28s} "
                         f"{state}  {specs}{budget}")
            for note in d.notes:
                lines.append(f"    note: {note}")
        for f in self.verify:
            lines.append(f"  verify [{f.severity}] {f.invariant} "
                         f"@ {f.subject}: {f.detail}")
        return "\n".join(lines)


def coverage_report(segments, plans: Mapping[int, collapse.CollapsePlan],
                    shapes: Mapping[str, tuple[int, ...]],
                    itemsize: int,
                    kernel_dispatch: Mapping[
                        int, registry_mod.KernelDispatch] | None = None,
                    autotune: Mapping[
                        int, autotune_mod.Decision] | None = None,
                    verify: tuple = (),
                    partitions: "partition_mod.PartitionPlan | None" = None,
                    mode: str = "xla") -> CoverageReport:
    """Build the per-stack coverage + planned-HBM-traffic report for a
    rewritten network (shared by :class:`OptimizedNet` and the traced-path
    ``repro.api.OptimizedFn``).  ``autotune`` maps segment index (or -1
    for the function-level floor) to its committed decision; ``verify``
    carries the static verifier's compile-time findings; ``partitions``
    is the mesh partition plan (None for single-device compiles);
    ``mode`` is the configured execution mode of the stacks."""
    kernel_dispatch = kernel_dispatch or {}
    mesh_axes: tuple = ()
    dist: list[DistCoverage] = []
    if partitions is not None and partitions.segments:
        mesh_axes = tuple(zip(partitions.axes.names, partitions.axes.shape))
        for idx, part in sorted(partitions.segments.items()):
            seg = segments[idx]
            is_stack = seg.is_stack
            name = seg.stack.name if is_stack else seg.op.name
            plan = plans.get(idx) if is_stack else None
            dist.append(DistCoverage(
                name=name, kind="stack" if is_stack else "kernel",
                in_specs=tuple((k, str(s))
                               for k, s in part.in_specs.items()),
                out_specs=tuple((k, str(s))
                                for k, s in part.out_specs.items()),
                active=part.active,
                shard_budget_bytes=(plan.device.resource_limit
                                    if plan is not None else 0),
                notes=part.notes))
    tuned = tuple(
        AutotuneCoverage(
            name=d.name, kind=d.kind, requested=d.requested,
            baseline=d.baseline, chosen=d.variant,
            guardrail_tripped=d.guardrail_tripped, source=d.source,
            measured_ms=d.measured_ms, events=d.events,
            failures=d.failures)
        for _, d in sorted((autotune or {}).items()))
    n_captured = n_opaque = n_backbone = n_synthetic = 0
    stacks: list[StackCoverage] = []
    kernels: list[KernelCoverage] = []
    for idx, seg in enumerate(segments):
        if seg.is_stack:
            n_captured += len(seg.stack.ops)
            plan = plans[idx]
            in_shapes = {v: tuple(shapes[v]) for v in seg.stack.inputs}
            bf = resource.breadth_first_traffic(seg.stack, in_shapes,
                                                itemsize)
            df = resource.depth_first_traffic(plan, in_shapes, itemsize)
            decision = (autotune or {}).get(idx)
            run = decision.variant if decision is not None else mode
            stacks.append(StackCoverage(
                name=seg.stack.name, n_ops=len(seg.stack.ops),
                kinds=tuple(op.kind.value for op in seg.stack.ops),
                n_sequences=len(plan.sequences),
                hbm_breadth_bytes=bf, hbm_depth_bytes=df,
                layout=seg.stack.layout,
                execution="generated" if run == "brainslug" else run))
        elif seg.op.kind == ir.OpKind.KERNEL:
            d = kernel_dispatch.get(idx)
            kernels.append(KernelCoverage(
                op_name=seg.op.name, kernel=seg.op.attrs["kernel"],
                backend=d.backend.value if d else "unknown",
                fallback_reason=d.reason if d else None))
        elif seg.op.attrs.get("synthetic"):
            # tracer plumbing (param binds / tuple projections): neither a
            # recognition failure nor a traced-function op
            n_synthetic += 1
        elif seg.op.kind in BACKBONE_KINDS:
            n_backbone += 1
        else:
            n_opaque += 1
    total = n_captured + n_opaque + n_backbone + len(kernels)
    eligible = n_captured + n_opaque
    return CoverageReport(
        n_ops=total, n_captured=n_captured, n_opaque=n_opaque,
        n_backbone=n_backbone, n_stacks=len(stacks),
        capture_ratio=n_captured / eligible if eligible else 1.0,
        stacks=tuple(stacks), n_synthetic=n_synthetic,
        n_kernel=len(kernels), kernels=tuple(kernels), autotune=tuned,
        verify=tuple(verify), mesh_axes=mesh_axes, dist=tuple(dist))


def run_segments(segments, executors: Mapping[int, codegen.Executor],
                 env: dict, params: Mapping[str, jnp.ndarray]) -> dict:
    """Execute a rewritten network: stacks and registry KERNEL ops through
    their compiled executors, opaque ops breadth-first through the
    interpreter.  The one segment-walk shared by :class:`OptimizedNet` and
    the traced ``repro.api.OptimizedFn``; mutates and returns ``env``."""
    for idx, seg in enumerate(segments):
        if seg.is_stack:
            out = executors[idx]({k: env[k] for k in seg.stack.inputs},
                                 params)
            env.update(out)
        elif seg.op.kind == ir.OpKind.KERNEL:
            out = executors[idx]({k: env[k] for k in seg.op.inputs}, params)
            env.update(out)
        else:
            env[seg.op.output] = ir.apply_op(seg.op, env, params)
    return env


@dataclasses.dataclass
class OptimizedNet:
    """A rewritten network: opaque segments + compiled stacks (the paper's
    special BrainSlug layers standing in for the collapsed originals)."""

    graph: ir.NetGraph
    segments: list
    executors: dict[int, codegen.Executor]
    plans: dict[int, collapse.CollapsePlan]
    config: OptimizeConfig
    shapes: dict[str, tuple[int, ...]] = dataclasses.field(
        default_factory=dict)   # value name -> inferred shape
    kernel_dispatches: dict[int, registry_mod.KernelDispatch] = \
        dataclasses.field(default_factory=dict)
    autotune_decisions: dict[int, autotune_mod.Decision] = \
        dataclasses.field(default_factory=dict)
    #: Static-verifier findings recorded at compile time (verify='warn'
    #: waives error findings but keeps them readable here / in report()).
    verify_findings: tuple = ()
    #: Mesh partition plan (None for single-device compiles).
    partitions: "partition_mod.PartitionPlan | None" = None

    def __call__(self, x: jnp.ndarray,
                 params: Mapping[str, jnp.ndarray]) -> jnp.ndarray:
        env = run_segments(self.segments, self.executors,
                           {self.graph.input: x}, params)
        return env[self.graph.output]

    @property
    def n_stacks(self) -> int:
        return len(self.executors)

    @property
    def n_sequences(self) -> int:
        return sum(len(p.sequences) for p in self.plans.values())

    def report(self) -> CoverageReport:
        """Per-stack coverage + planned HBM traffic of this rewrite."""
        return coverage_report(self.segments, self.plans, self.shapes,
                               self.config.itemsize,
                               kernel_dispatch=self.kernel_dispatches,
                               autotune=self.autotune_decisions,
                               verify=self.verify_findings,
                               partitions=self.partitions,
                               mode=self.config.mode)

    def explain(self) -> str:
        """Human-readable :meth:`report` (ops captured vs. left opaque,
        planned HBM traffic per stack)."""
        return str(self.report())


def compile_stacks(segments, shapes: Mapping[str, tuple[int, ...]],
                   config: OptimizeConfig, *,
                   param_shapes: Mapping[str, tuple[int, ...]] | None = None,
                   dtypes: Mapping[str, object] | None = None,
                   tuner: "autotune_mod.Autotuner | None" = None
                   ) -> tuple[dict[int, codegen.Executor],
                              dict[int, collapse.CollapsePlan],
                              dict[int, registry_mod.KernelDispatch],
                              dict[int, autotune_mod.Decision],
                              tuple,
                              "partition_mod.PartitionPlan | None"]:
    """Collapse + compile every stack segment, and compile every registry
    KERNEL segment, against ``config`` (shared by :func:`optimize_graph`
    and the traced ``repro.api.optimize`` facade — one place threads
    OptimizeConfig into the collapser/codegen).  With ``config.autotune``
    each segment's variant is measured and hard-floored at its baseline
    (:mod:`repro.core.autotune`).

    This runs in two stages with the static verifier between them: every
    stack is *planned* first, then — unless ``config.verify == 'off'`` —
    :func:`repro.core.verify.verify_segments` re-derives each plan's
    invariants; under ``verify='strict'`` a violation raises
    :class:`~repro.core.verify.VerifyError` before anything compiles.
    Returns (executors, plans, kernel dispatch records, autotune
    decisions, verify findings, partition plan).

    With ``config.mesh`` set (and ``config.partition != 'none'``) every
    stack / registry-kernel segment gets derived shard_map boundary specs
    (:func:`repro.core.partition.plan_segments`); active stacks collapse
    against their **per-shard** input shapes on a haircut per-device
    budget (:func:`repro.core.resource.shard_device`) — data/tensor
    splits shrink the shard a device sees, which changes ``tile_rows`` /
    sequence splits — and codegen wraps their executors in shard_map.
    Autotuning is disabled under a mesh (measuring forced host devices
    would commit nonsense); the static planner decides."""
    partitions: "partition_mod.PartitionPlan | None" = None
    shard_dev = config.device
    if config.mesh is not None and config.partition != "none":
        partitions = partition_mod.plan_segments(
            segments, shapes, param_shapes, config.partition, config.mesh,
            sublane=config.device.sublane)
        shard_dev = resource.shard_device(config.device,
                                          partitions.axes.n_devices)
        tuner = None
    elif tuner is None and config.autotune:
        tuner = autotune_mod.Autotuner.from_config(config)
    # shard_map wrapping needs real devices; a MeshAxes skeleton (static
    # lint) still drives per-shard sizing + verification, just no codegen
    # wrapping.
    exec_mesh = (config.mesh if partitions is not None
                 and hasattr(config.mesh, "devices") else None)
    executors: dict[int, codegen.Executor] = {}
    plans: dict[int, collapse.CollapsePlan] = {}
    modes: dict[int, str] = {}
    dispatches: dict[int, registry_mod.KernelDispatch] = {}
    decisions: dict[int, autotune_mod.Decision] = {}

    # Stage 1: plan every stack (collapse, or measure-then-commit).
    for idx, seg in enumerate(segments):
        if not seg.is_stack:
            continue
        in_shapes = {v: tuple(shapes[v]) for v in seg.stack.inputs}
        mode = config.mode
        part = partitions.get(idx) if partitions is not None else None
        if part is not None and part.active:
            # Per-shard sizing: collapse what ONE device's shard_map
            # region executes, against the haircut budget.
            shard_in = partition_mod.shard_shapes(
                in_shapes, part.in_specs, partitions.axes)
            plan = collapse.collapse(
                seg.stack, shard_in, shard_dev,
                itemsize=config.itemsize,
                max_steps_per_sequence=config.max_steps_per_sequence,
                differentiable=config.differentiable)
        elif tuner is not None and config.mode != "barrier":
            # barrier IS the floor: nothing to measure against
            decision, mode, plan = autotune_mod.tune_stack(
                tuner, seg.stack, in_shapes, config,
                param_shapes=param_shapes)
            decisions[idx] = decision
        else:
            plan = collapse.collapse(
                seg.stack, in_shapes, config.device,
                itemsize=config.itemsize,
                max_steps_per_sequence=config.max_steps_per_sequence,
                differentiable=config.differentiable)
        plans[idx] = plan
        modes[idx] = mode

    # Stage 2: the static verifier gate, between planning and codegen.
    findings: tuple = ()
    if config.verify != "off":
        findings = tuple(verify_mod.verify_segments(
            segments, plans, shapes, config, dtypes=dtypes,
            param_shapes=param_shapes, partitions=partitions))
        verify_mod.enforce(findings, config.verify)

    # Stage 3: codegen (only reached when verification passed or was
    # waived).
    for idx, seg in enumerate(segments):
        part = partitions.get(idx) if partitions is not None else None
        if seg.is_stack:
            executors[idx] = codegen.compile_plan(
                plans[idx], mode=modes[idx],
                cache_size=config.code_cache_size,
                mesh=exec_mesh, part=part)
        elif seg.op.kind == ir.OpKind.KERNEL:
            backend = reason = None
            if tuner is not None:
                tuned = autotune_mod.tune_kernel(tuner, seg.op, config)
                if tuned is not None:
                    decisions[idx], backend, reason = tuned
            executors[idx], dispatches[idx] = codegen.compile_kernel_op(
                seg.op, mode=config.mode,
                cache_size=config.code_cache_size, backend=backend,
                reason=reason, mesh=exec_mesh, part=part)
    return executors, plans, dispatches, decisions, findings, partitions


def optimize_graph(graph: ir.NetGraph,
                   input_shape: tuple[int, ...],
                   config: OptimizeConfig = OptimizeConfig(),
                   layout: str = "nhwc") -> OptimizedNet:
    segments = analyzer.analyze(graph, layout=layout,  # validates layout
                                keep=frozenset({graph.output}))
    shapes: dict[str, tuple[int, ...]] = {graph.input: input_shape}
    for seg in segments:
        if seg.is_stack:
            in_shapes = {v: shapes[v] for v in seg.stack.inputs}
            shapes.update(ir.infer_shapes(seg.stack, in_shapes))
        else:
            _infer_opaque_shape(seg.op, shapes)
    graph_findings: tuple = ()
    if config.verify != "off":
        graph_findings = tuple(verify_mod.check_graph(
            graph, shapes=shapes, keep=frozenset({graph.output})))
        verify_mod.enforce(graph_findings, config.verify,
                           subject=graph.name)
    executors, plans, dispatches, tuned, findings, parts = compile_stacks(
        segments, shapes, config)
    return OptimizedNet(graph=graph, segments=segments, executors=executors,
                        plans=plans, config=config, shapes=shapes,
                        kernel_dispatches=dispatches,
                        autotune_decisions=tuned,
                        verify_findings=graph_findings + findings,
                        partitions=parts)


def optimize_stack(program: ir.StackProgram,
                   input_shapes: Mapping[str, tuple[int, ...]],
                   config: OptimizeConfig = OptimizeConfig()
                   ) -> codegen.Executor:
    plan = collapse.collapse(
        program, input_shapes, config.device, itemsize=config.itemsize,
        max_steps_per_sequence=config.max_steps_per_sequence,
        differentiable=config.differentiable)
    return codegen.compile_plan(plan, mode=config.mode,
                                cache_size=config.code_cache_size)


def _infer_opaque_shape(op: ir.OpNode, shapes: dict) -> None:
    """Shape propagation for the opaque kinds appearing in NetGraphs."""
    if op.kind == ir.OpKind.CONV2D:
        n, h, w, _ = shapes[op.inputs[0]]
        kh, kw, _, co = op.attrs["kernel_shape"]
        sh, sw = op.attrs.get("stride", (1, 1))
        ph, pw = op.attrs.get("padding", (0, 0))
        shapes[op.output] = (n, ir.pool_out_extent(h, kh, sh, ph),
                             ir.pool_out_extent(w, kw, sw, pw), co)
    elif op.kind == ir.OpKind.MATMUL:
        shp = shapes[op.inputs[0]]
        shapes[op.output] = shp[:-1] + (op.attrs["features_out"],)
    elif (op.kind in (ir.OpKind.OPAQUE, ir.OpKind.KERNEL)
          and "out_shape" in op.attrs):
        shapes[op.output] = tuple(op.attrs["out_shape"])
    else:
        shapes[op.output] = shapes[op.inputs[0]]
