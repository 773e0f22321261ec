"""Measured autotuning with a crash-safe decision cache — the never-slower
guardrail.

The planner's static cost model picks a schedule per segment; this module
checks that choice against the clock.  At ``optimize()`` compile time each
tunable segment enumerates candidate execution variants — fused-pallas /
fused-XLA / barrier for stacks (plus tile-size and sequence-split variants
from the collapse plan), PALLAS vs REF for registry kernels — and
micro-benchmarks every candidate on the real traced shapes (warmup +
median-of-k, ``jax.block_until_ready``).  The winner is committed, and every
decision is **hard-floored at the baseline**: a candidate is eligible only
when it measures no slower than the barrier/ref/raw baseline in every
measured phase, so a losing fused variant degrades gracefully instead of
shipping a regression ("Exploiting Parallelism Opportunities with Deep
Learning Frameworks", arXiv:1908.04705 — the right choice is hardware- and
shape-dependent and must be measured, not modeled).

Decisions persist in an on-disk cache so long-lived servers and repeat jobs
skip the search entirely:

* location — ``OptimizeConfig.autotune_cache_dir``, else
  ``$REPRO_AUTOTUNE_CACHE``, else ``~/.cache/repro/autotune/``;
* key — sha256 over the canonical JSON of (kind, structural signature,
  shapes, dtypes/itemsize, requested mode, XLA backend — which also
  decides Pallas interpret mode); the jax + repro versions ride inside
  the entry and are verified on load;
* write — the checkpointer's atomic tmp-then-rename idiom (fsync before
  rename), so a killed process can never leave a half-written entry;
* defense in depth — schema version + per-entry checksum; corrupt,
  truncated, or version-stale entries are quarantined (renamed to
  ``*.quarantined``) and silently re-measured.  A bad cache file must never
  crash or mis-dispatch ``optimize()``.

Candidates that fail to build/lower or exceed the per-candidate measurement
timeout are recorded as failures with reasons, not fatal errors; the
baseline is exempt from the timeout (the floor must always exist).  All
counters live in :data:`STATS` (snapshot/delta protocol) so tests can
assert "a warm cache performs zero micro-benchmark runs".
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codegen
from repro.core import collapse as collapse_mod
from repro.core import ir
from repro.core import registry as registry_mod
from repro.obs import DispatchStats

#: On-disk entry format version; a bump invalidates (quarantines) every
#: older entry on first contact.
SCHEMA_VERSION = 1

#: A non-baseline candidate must measure within this factor of the baseline
#: in every phase to stay eligible — small enough that the committed choice
#: cannot ship a visible regression, large enough to absorb timer noise.
FLOOR_SLACK = 1.02

STATS = DispatchStats(keys=(
    "measure_runs",        # timed candidate invocations (warmup + repeats)
    "decisions",           # decide() calls that ran the measurement path
    "cache_hit_mem",       # served from the in-process memo
    "cache_hit_disk",      # served from the on-disk cache
    "cache_miss",          # no usable cached entry: measured
    "cache_quarantined",   # corrupt/truncated/stale entries set aside
    "guardrail_trips",     # requested variant lost to the floor
    "candidate_failures",  # candidates that failed to build/measure
))

#: In-process decision memo (key hash -> Decision).  Sits in front of the
#: disk cache; cleared by :func:`clear_memory_cache` (benchmark drivers).
_MEM_CACHE: dict[str, "Decision"] = {}


def clear_memory_cache() -> None:
    _MEM_CACHE.clear()


def default_cache_dir() -> str:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune")


def _canonical(obj: Any) -> str:
    """Deterministic JSON for hashing/checksums (``default=str`` absorbs
    dtypes and anything else JSON does not know)."""
    return json.dumps(obj, sort_keys=True, separators=(",", ":"),
                      default=str)


def _key_hash(key_obj: Any) -> str:
    return hashlib.sha256(_canonical(key_obj).encode()).hexdigest()[:32]


def _versions() -> dict[str, str]:
    import repro
    return {"jax": jax.__version__,
            "repro": getattr(repro, "__version__", "0")}


# ---------------------------------------------------------------------------
# Decision record.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Decision:
    """One committed autotune decision (what ``report()`` surfaces)."""

    kind: str                 # 'stack' | 'kernel' | 'function' | 'callable'
    name: str                 # segment / kernel / function label
    requested: str            # the statically configured variant
    baseline: str             # the never-slower floor variant
    variant: str              # what was committed
    measured_ms: tuple = ()   # ((variant, phase, ms), ...)
    failures: tuple = ()      # ((variant, reason), ...)
    guardrail_tripped: bool = False   # requested variant was not committed
    source: str = "measured"  # 'measured' | 'cache-mem' | 'cache-disk'
    events: tuple = ()        # cache/measurement notes for report()
    autotune_ms: float = 0.0  # wall time this decision cost (0 on warm hit)

    def to_payload(self) -> dict:
        return {
            "kind": self.kind, "name": self.name,
            "requested": self.requested, "baseline": self.baseline,
            "variant": self.variant,
            "measured_ms": [list(m) for m in self.measured_ms],
            "failures": [list(f) for f in self.failures],
            "guardrail_tripped": bool(self.guardrail_tripped),
        }

    @classmethod
    def from_payload(cls, payload: Any) -> "Decision":
        if not isinstance(payload, dict):
            raise ValueError("payload is not a mapping")
        for k in ("kind", "name", "requested", "baseline", "variant"):
            if not isinstance(payload.get(k), str):
                raise ValueError(f"payload field {k!r} missing or not str")
        measured = tuple(
            (str(v), str(p), float(ms))
            for v, p, ms in payload.get("measured_ms", ()))
        failures = tuple((str(v), str(r))
                         for v, r in payload.get("failures", ()))
        return cls(kind=payload["kind"], name=payload["name"],
                   requested=payload["requested"],
                   baseline=payload["baseline"],
                   variant=payload["variant"], measured_ms=measured,
                   failures=failures,
                   guardrail_tripped=bool(
                       payload.get("guardrail_tripped", False)))

    def ms_for(self, variant: str) -> float | None:
        """Summed measured phases for one variant (None if unmeasured)."""
        vals = [ms for v, _, ms in self.measured_ms if v == variant]
        return float(sum(vals)) if vals else None


# ---------------------------------------------------------------------------
# Disk cache: atomic writes, checksum + schema + version validation,
# quarantine on any defect.  No method ever raises.
# ---------------------------------------------------------------------------

class DecisionCache:
    """Crash-safe decision store.  ``load``/``store`` swallow every IO and
    format defect: the worst outcome of a bad cache is a re-measurement."""

    def __init__(self, cache_dir: str | None = None) -> None:
        self.dir = cache_dir or default_cache_dir()

    def _path(self, key_hash: str) -> str:
        return os.path.join(self.dir, key_hash + ".json")

    def _quarantine(self, path: str, reason: str,
                    events: list[str]) -> None:
        STATS.record("cache_quarantined")
        events.append(f"cache: quarantined {os.path.basename(path)} "
                      f"({reason})")
        try:
            os.replace(path, path + ".quarantined")
        except OSError:
            try:
                os.remove(path)
            except OSError:
                pass

    def load(self, key_obj: Any
             ) -> tuple["Decision | None", tuple[str, ...]]:
        """Returns (decision, events).  Any defect quarantines the entry
        and returns ``(None, events)`` — never raises."""
        events: list[str] = []
        path = self._path(_key_hash(key_obj))
        try:
            if not os.path.exists(path):
                return None, ()
            with open(path, "r", encoding="utf-8") as fh:
                blob = json.load(fh)
        except (OSError, ValueError, UnicodeDecodeError):
            self._quarantine(path, "unreadable or corrupt JSON", events)
            return None, tuple(events)
        try:
            if not isinstance(blob, dict):
                raise ValueError("entry is not a mapping")
            if blob.get("schema") != SCHEMA_VERSION:
                raise ValueError(
                    f"stale schema {blob.get('schema')!r} "
                    f"(want {SCHEMA_VERSION})")
            if blob.get("key") != _canonical(key_obj):
                raise ValueError("key mismatch (hash collision or tamper)")
            payload = blob.get("payload")
            checksum = hashlib.sha256(
                _canonical(payload).encode()).hexdigest()
            if blob.get("checksum") != checksum:
                raise ValueError("checksum mismatch (truncated entry)")
            if blob.get("versions") != _versions():
                raise ValueError(
                    f"stale versions {blob.get('versions')!r}")
            decision = Decision.from_payload(payload)
        except (ValueError, TypeError, KeyError) as e:
            self._quarantine(path, str(e), events)
            return None, tuple(events)
        return decision, tuple(events)

    def store(self, key_obj: Any, decision: "Decision") -> None:
        """Atomic tmp-then-rename write (the checkpointer idiom); failures
        are swallowed — a read-only cache dir only costs re-measurement."""
        path = self._path(_key_hash(key_obj))
        blob = {
            "schema": SCHEMA_VERSION,
            "key": _canonical(key_obj),
            "versions": _versions(),
            "payload": decision.to_payload(),
        }
        blob["checksum"] = hashlib.sha256(
            _canonical(blob["payload"]).encode()).hexdigest()
        tmp = path + f".tmp.{os.getpid()}"
        try:
            os.makedirs(self.dir, exist_ok=True)
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(blob, fh)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, path)
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass


# ---------------------------------------------------------------------------
# Measurement harness.
# ---------------------------------------------------------------------------

def measure_ms(fn: Callable, args: tuple, *, repeats: int = 3,
               warmup: int = 1, timeout_ms: float | None = None,
               use_jit: bool = True) -> tuple[float | None, str | None]:
    """Time ``fn(*args)``: warmup calls, then median of ``repeats``.

    Returns ``(median_ms, None)`` or ``(None, reason)``.  The first call
    (which pays tracing/compilation) is checked against ``timeout_ms``; a
    candidate that cannot even warm up inside the budget is disqualified
    rather than allowed to stall compile time.  Never raises.
    """
    try:
        timed = jax.jit(fn) if use_jit else fn
        t0 = time.perf_counter()
        jax.block_until_ready(timed(*args))
        first_ms = (time.perf_counter() - t0) * 1e3
        STATS.record("measure_runs")
        if timeout_ms is not None and first_ms > timeout_ms:
            return None, (f"timeout: first call took {first_ms:.1f}ms "
                          f"(> {timeout_ms:.0f}ms budget)")
        for _ in range(max(0, warmup - 1)):
            jax.block_until_ready(timed(*args))
            STATS.record("measure_runs")
        times = []
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            jax.block_until_ready(timed(*args))
            times.append(time.perf_counter() - t0)
            STATS.record("measure_runs")
        return float(np.median(times)) * 1e3, None
    except Exception as e:                     # lowering/shape/OOM failure
        return None, f"{type(e).__name__}: {e}"


def synth_array(shape: tuple[int, ...], dtype: Any = jnp.float32,
                seed: int = 0) -> jnp.ndarray:
    """Deterministic measurement operand of the traced shape/dtype."""
    dt = np.dtype(dtype)
    if dt.kind in "iu":
        return jnp.zeros(shape, dt)
    if dt.kind == "b":
        return jnp.zeros(shape, bool)
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32)) \
        .astype(dt)


#: One measurement phase: (phase label, callable, args tuple).
Phase = tuple  # ("fwd" | "grad", Callable, tuple)


# ---------------------------------------------------------------------------
# The tuner.
# ---------------------------------------------------------------------------

class Autotuner:
    """Measure-then-commit variant selection with memo + disk cache."""

    def __init__(self, *, cache_dir: str | None = None, repeats: int = 3,
                 warmup: int = 1, timeout_ms: float | None = 2000.0,
                 use_jit: bool = True) -> None:
        self.cache = DecisionCache(cache_dir)
        self.repeats = repeats
        self.warmup = warmup
        self.timeout_ms = timeout_ms
        self.use_jit = use_jit

    @classmethod
    def from_config(cls, config) -> "Autotuner":
        return cls(cache_dir=config.autotune_cache_dir,
                   repeats=config.autotune_repeats,
                   warmup=config.autotune_warmup,
                   timeout_ms=config.autotune_timeout_ms)

    def decide(self, key_obj: Any, *, kind: str, name: str, requested: str,
               baseline: str,
               builders: Mapping[str, Callable[[], list]]) -> Decision:
        """Commit a variant.  ``builders[variant]()`` returns the list of
        measurement phases for that variant; building and measuring may
        fail (recorded, never raised).  The baseline variant is exempt
        from the timeout and is the floor of every decision."""
        t0 = time.perf_counter()
        key = _key_hash(key_obj)

        cached = _MEM_CACHE.get(key)
        if cached is not None and cached.variant in builders:
            STATS.record("cache_hit_mem")
            return dataclasses.replace(
                cached, source="cache-mem",
                autotune_ms=(time.perf_counter() - t0) * 1e3)

        disk, load_events = self.cache.load(key_obj)
        if disk is not None and disk.variant in builders:
            STATS.record("cache_hit_disk")
            decision = dataclasses.replace(
                disk, source="cache-disk",
                events=disk.events + load_events + ("cache: disk hit",),
                autotune_ms=(time.perf_counter() - t0) * 1e3)
            _MEM_CACHE[key] = decision
            return decision

        STATS.record("cache_miss")
        events: list[str] = list(load_events)
        if disk is not None:
            events.append(
                f"cache: entry variant {disk.variant!r} no longer a "
                f"candidate; re-measured")
        failures: list[tuple[str, str]] = []
        measured: list[tuple[str, str, float]] = []
        totals: dict[str, float] = {}

        def run_variant(label: str, timeout: float | None
                        ) -> dict[str, float] | None:
            try:
                phases = builders[label]()
            except Exception as e:             # build/lowering failure
                failures.append((label, f"{type(e).__name__}: {e}"))
                STATS.record("candidate_failures")
                return None
            out: dict[str, float] = {}
            for phase, fn, args in phases:
                ms, why = measure_ms(
                    fn, args, repeats=self.repeats, warmup=self.warmup,
                    timeout_ms=timeout, use_jit=self.use_jit)
                if ms is None:
                    failures.append((label, f"{phase}: {why}"))
                    STATS.record("candidate_failures")
                    return None
                out[phase] = ms
                measured.append((label, phase, ms))
            return out

        base_phases = run_variant(baseline, None)
        if base_phases is not None:
            totals[baseline] = sum(base_phases.values())
        else:
            events.append(
                f"baseline {baseline!r} failed to measure; fail-open to "
                f"the requested variant")
        for label in builders:
            if label == baseline:
                continue
            phases = run_variant(label, self.timeout_ms)
            if phases is None:
                continue
            if base_phases is not None:
                slower = [p for p, ms in phases.items()
                          if p in base_phases
                          and ms > base_phases[p] * FLOOR_SLACK]
                if slower:
                    events.append(
                        f"{label}: floored by {baseline} on "
                        f"phase(s) {', '.join(sorted(slower))}")
                    continue
            totals[label] = sum(phases.values())

        if totals:
            chosen = min(totals, key=lambda lb: totals[lb])
        else:                                  # nothing measured at all
            chosen = requested if requested in builders else baseline
            events.append("no candidate measured; committing the "
                          "requested variant unverified")
        tripped = chosen != requested
        if tripped:
            STATS.record("guardrail_trips")
        STATS.record("decisions")
        decision = Decision(
            kind=kind, name=name, requested=requested, baseline=baseline,
            variant=chosen, measured_ms=tuple(measured),
            failures=tuple(failures), guardrail_tripped=tripped,
            source="measured", events=tuple(events),
            autotune_ms=(time.perf_counter() - t0) * 1e3)
        _MEM_CACHE[key] = decision
        self.cache.store(key_obj, decision)
        return decision


# ---------------------------------------------------------------------------
# Stack-segment tuning (compile_stacks hook).
# ---------------------------------------------------------------------------

def _stack_operands(stack: ir.StackProgram,
                    in_shapes: Mapping[str, tuple[int, ...]],
                    param_shapes: Mapping[str, tuple[int, ...]] | None,
                    itemsize: int) -> tuple[dict, dict]:
    """Synthesize executor operands on the traced shapes.  Param shapes
    come from the trace when available; otherwise a param broadcasts over
    the trailing (feature) dim of the op that consumes it."""
    dtype = {2: jnp.bfloat16, 4: jnp.float32, 8: jnp.float64}.get(
        itemsize, jnp.float32)
    inputs = {k: synth_array(tuple(v), dtype, seed=i)
              for i, (k, v) in enumerate(sorted(in_shapes.items()))}
    all_shapes = ir.infer_shapes(stack, dict(in_shapes))
    params: dict[str, jnp.ndarray] = {}
    for op in stack.ops:
        for p in op.params:
            if p in params:
                continue
            if param_shapes and p in param_shapes:
                shape = tuple(param_shapes[p])
            else:
                shape = (tuple(all_shapes[op.inputs[0]]) or (1,))[-1:]
            params[p] = synth_array(shape, dtype, seed=len(params) + 7)
    return inputs, params


def _plan_variants(stack: ir.StackProgram,
                   in_shapes: Mapping[str, tuple[int, ...]],
                   config) -> dict[str, tuple[str, Any]]:
    """Candidate (mode, plan) pairs per variant label.  'barrier' is the
    floor; fused XLA always competes; the pallas schedule (plus tile-size
    and sequence-split variants) competes only when requested."""
    plan = collapse_mod.collapse(
        stack, in_shapes, config.device, itemsize=config.itemsize,
        max_steps_per_sequence=config.max_steps_per_sequence,
        differentiable=config.differentiable)
    variants: dict[str, tuple[str, Any]] = {"barrier": ("barrier", plan)}
    if config.mode != "barrier":
        variants["xla"] = ("xla", plan)
    if config.mode == "brainslug":
        variants["brainslug"] = ("brainslug", plan)
        if plan.sequences and all(s.tile_rows for s in plan.sequences):
            halved = dataclasses.replace(plan, sequences=tuple(
                dataclasses.replace(s, tile_rows=max(8, s.tile_rows // 2))
                for s in plan.sequences))
            if halved.sequences != plan.sequences:
                rows = halved.sequences[0].tile_rows
                variants[f"brainslug@rows{rows}"] = ("brainslug", halved)
        if len(stack.ops) > 1 and len(plan.sequences) == 1:
            split = collapse_mod.collapse(
                stack, in_shapes, config.device, itemsize=config.itemsize,
                max_steps_per_sequence=max(1, len(stack.ops) // 2),
                differentiable=config.differentiable)
            if len(split.sequences) > 1:
                variants[f"brainslug@seq{len(split.sequences)}"] = \
                    ("brainslug", split)
    return variants


def tune_stack(tuner: Autotuner, stack: ir.StackProgram,
               in_shapes: Mapping[str, tuple[int, ...]], config,
               param_shapes: Mapping[str, tuple[int, ...]] | None = None
               ) -> tuple[Decision, str, Any]:
    """Measure the stack's execution variants; returns
    ``(decision, mode, plan)`` for codegen.  Any internal failure falls
    back to the statically planned variant."""
    variants = _plan_variants(stack, in_shapes, config)
    requested = config.mode if config.mode in variants else "barrier"
    stack_params = {p for op in stack.ops for p in op.params}
    key_obj = {
        "kind": "stack", "sig": repr(stack.signature()),
        "shapes": sorted((k, list(v)) for k, v in in_shapes.items()),
        "param_shapes": sorted((k, list(v))
                               for k, v in (param_shapes or {}).items()
                               if k in stack_params),
        "itemsize": config.itemsize,
        "device": getattr(config.device, "name", str(config.device)),
        "mode": requested,
        "differentiable": config.differentiable,
        "max_steps": config.max_steps_per_sequence,
        "backend": jax.default_backend(),
    }
    inputs, params = _stack_operands(stack, in_shapes, param_shapes,
                                     config.itemsize)

    def make_builder(mode: str, plan: Any) -> Callable[[], list]:
        def build() -> list:
            ex = codegen.compile_plan(
                plan, mode=mode,
                cache_size=config.code_cache_size)
            phases: list = [("fwd", ex, (inputs, params))]
            if config.differentiable:
                def loss(i, p):
                    out = ex(i, p)
                    return sum(
                        jnp.sum(jnp.square(v.astype(jnp.float32)))
                        for v in out.values())
                phases.append(("grad", jax.grad(loss), (inputs, params)))
            return phases
        return build

    builders = {label: make_builder(mode, plan)
                for label, (mode, plan) in variants.items()}
    decision = tuner.decide(key_obj, kind="stack", name=stack.name,
                            requested=requested, baseline="barrier",
                            builders=builders)
    mode, plan = variants.get(decision.variant, variants["barrier"])
    return decision, mode, plan


# ---------------------------------------------------------------------------
# Registry-kernel tuning (PALLAS vs REF, extending plan_dispatch).
# ---------------------------------------------------------------------------

def tune_kernel(tuner: Autotuner, op: ir.OpNode, config
                ) -> tuple[Decision, Any, str | None] | None:
    """Measure PALLAS vs REF for one registry KERNEL op.  Returns
    ``(decision, backend, reason)`` or None when there is nothing to tune
    (the static planner already forced the ref twin)."""
    static_dispatch = registry_mod.plan_dispatch(op, config.mode)
    if static_dispatch.backend is not registry_mod.KernelType.PALLAS:
        return None
    shapes = tuple(tuple(s) for s in op.attrs["arg_shapes"])
    dtypes = op.attrs.get("arg_dtypes",
                          ("float32",) * len(shapes))
    key_obj = {
        "kind": "kernel", "kernel": op.attrs["kernel"],
        "arg_shapes": [list(s) for s in shapes],
        "arg_dtypes": [str(d) for d in dtypes],
        "static": repr(ir._freeze(
            {k: v for k, v in op.attrs.items()
             if k not in codegen._KERNEL_PLUMBING_ATTRS})),
        "backend": jax.default_backend(),
    }
    args = tuple(synth_array(s, d, seed=i)
                 for i, (s, d) in enumerate(zip(shapes, dtypes)))

    def make_builder(backend) -> Callable[[], list]:
        def build() -> list:
            inner = codegen.kernel_inner(
                op, backend=backend,
                cache_size=config.code_cache_size)
            return [("fwd", inner, args)]
        return build

    builders = {
        "pallas": make_builder(registry_mod.KernelType.PALLAS),
        "ref": make_builder(registry_mod.KernelType.REF),
    }
    decision = tuner.decide(key_obj, kind="kernel",
                            name=op.name, requested="pallas",
                            baseline="ref", builders=builders)
    if decision.variant == "pallas":
        return decision, registry_mod.KernelType.PALLAS, None
    pallas_ms = decision.ms_for("pallas")
    ref_ms = decision.ms_for("ref")
    if pallas_ms is not None and ref_ms is not None:
        reason = (f"autotune: ref {ref_ms:.3f}ms beat pallas "
                  f"{pallas_ms:.3f}ms on measured shapes")
    else:
        reason = "autotune: pallas candidate failed to measure"
    return decision, registry_mod.KernelType.REF, reason


# ---------------------------------------------------------------------------
# Whole-callable tuning: the benchmark/facade-level floor.
# ---------------------------------------------------------------------------

def pick_callable(name: str, candidates: Mapping[str, Callable],
                  args: tuple, *, baseline: str,
                  requested: str | None = None,
                  cache_dir: str | None = None, key_extra: Any = None,
                  repeats: int = 3, warmup: int = 1,
                  timeout_ms: float | None = None, use_jit: bool = False
                  ) -> tuple[Decision, Callable]:
    """Measure whole callables on real args and commit the fastest one
    that is never slower than ``candidates[baseline]``.  Returns
    ``(decision, chosen callable)``.  Used by the benchmark drivers and
    the ``optimize()`` function-level floor; callers pass pre-jitted
    callables (``use_jit=False``) or let the harness jit."""
    if baseline not in candidates:
        raise ValueError(f"baseline {baseline!r} not in candidates "
                         f"{sorted(candidates)}")
    requested = requested if requested in candidates else baseline
    leaves = jax.tree_util.tree_leaves(args)
    key_obj = {
        "kind": "callable", "name": name,
        "avals": [[list(np.shape(x)), str(np.asarray(x).dtype)]
                  for x in leaves],
        "candidates": sorted(candidates),
        "requested": requested, "baseline": baseline,
        "extra": key_extra, "backend": jax.default_backend(),
    }
    tuner = Autotuner(cache_dir=cache_dir, repeats=repeats, warmup=warmup,
                      timeout_ms=timeout_ms, use_jit=use_jit)
    builders = {label: (lambda fn=fn: [("fwd", fn, args)])
                for label, fn in candidates.items()}
    decision = tuner.decide(key_obj, kind="callable", name=name,
                            requested=requested, baseline=baseline,
                            builders=builders)
    return decision, candidates.get(decision.variant,
                                    candidates[baseline])
