"""Counters and spans: the one registry of what the program did and where
its host time went.

* :class:`DispatchStats` — named counters with a snapshot/delta protocol.
  Each subsystem keeps its own instance (``repro.launch.engine.STATS``,
  ``repro.kernels.fused_stack.ops.STATS``, ...).
* :func:`span` — a context manager that times a named stretch of host
  code into the process-wide :data:`SPANS` registry (a count and seconds
  per name, by ``time.perf_counter``) and enters
  ``jax.profiler.TraceAnnotation`` of the same name, so that under a
  running profiler the span lands on the host plane, on the device
  trace's clock.  With no profiler running, the annotation costs only its
  object.

Span names are declared up front in :data:`SPAN_NAMES`; an unknown name is
a ``KeyError``, as an unknown counter key is.  A span never sits in code
that JAX traces (a model step, a kernel wrapper, anything called under
``jax.jit``): there it would time the trace, not the run.
"""
from __future__ import annotations

import time
from typing import Mapping

import jax


class DispatchStats:
    """Trace-time dispatch counters (the mode stat the acceptance criteria
    ask for): which path ran — the generated depth-first kernel or the
    reference-interpreter fallback.  Counts are incremented when the path is
    *traced*, i.e. once per compilation, which is exactly the "was the
    generated kernel used" question.

    The instance is a process-global singleton (``STATS``); callers that
    need isolation take a :meth:`snapshot` first and diff against it
    (``STATS.delta(before)``) instead of asserting absolute counts —
    benchmark drivers additionally :meth:`reset` at phase boundaries so
    counts do not bleed across runs.

    The class is key-set agnostic so other dispatch surfaces can reuse the
    snapshot/delta protocol: the serving drivers instantiate their own
    counters (``repro.launch.serve.STATS`` / ``repro.launch.engine.STATS``)
    with *runtime* dispatch keys — there the counts are per call, not per
    trace, because "how many decode dispatches did the loop issue" is the
    question those counters answer."""

    BASE_KEYS = ("fwd_generated", "fwd_reference",
                 "bwd_generated", "bwd_reference")

    def __init__(self, keys: tuple[str, ...] = BASE_KEYS) -> None:
        self._keys = tuple(keys)
        self.reset()

    def reset(self) -> None:
        self.counts: dict[str, int] = {k: 0 for k in self._keys}

    def record(self, key: str, n: int = 1) -> None:
        if key not in self.counts:
            raise KeyError(
                f"unknown dispatch counter {key!r}; declared: {self._keys}")
        self.counts[key] += n

    def snapshot(self) -> dict[str, int]:
        """An immutable copy of the current counts, for later diffing."""
        return dict(self.counts)

    def delta(self, before: Mapping[str, int]) -> dict[str, int]:
        """Counts recorded since ``before`` (a :meth:`snapshot`)."""
        return {k: v - before.get(k, 0) for k, v in self.counts.items()}


#: Every span the program opens, with the metric or report that reads it.
SPAN_NAMES = (
    # the serve engine's scheduler tick (``Engine._serve``); read by
    # ``Engine.report()["host_spans"]`` and the benchmark's
    # ``tick_host_ms.serve``
    "engine.admit",         # admission: prefix lookup, hashing, block gate
    "engine.tick",          # the rest of the tick: holds the six below
    "engine.prepare",       # slot reset, lane arrays, write barrier, COW
    "engine.verify",        # block-table invariants (verify_mode != off)
    "engine.upload",        # host -> device transfers of the step's inputs
    "engine.dispatch",      # the mixed step's asynchronous enqueue
    "engine.sync",          # waiting for the step, and its tokens back
    "engine.commit",        # counters, block fill, prefix publish, emit
    # ``repro.api.optimize``; read by ``OptimizedFn.setup_spans`` /
    # ``explain()`` and the benchmark's ``*.paper`` set-up metrics
    "optimize.trace",       # jaxpr -> IR, with the numerical probes
    "trace.probe",          # one behavioural probe of a sub-jaxpr call
    "trace.chain_probe",    # one behavioural probe of an elementwise chain
    "optimize.registry",    # the kernel-registry rewrite
    "optimize.verify",      # graph-level static verification
    "optimize.compile",     # segmentation, collapse, codegen, executors
    "optimize.floor",       # the autotuner's whole-function floor
)


class SpanStats:
    """Count and seconds per declared span name, with the snapshot/delta
    protocol of :class:`DispatchStats`: ``delta`` gives
    ``{name: {"count": n, "seconds": s}}``."""

    def __init__(self, names: tuple[str, ...]) -> None:
        self._names = tuple(names)
        self.reset()

    def reset(self) -> None:
        self.counts: dict[str, int] = {k: 0 for k in self._names}
        self.seconds: dict[str, float] = {k: 0.0 for k in self._names}

    def span(self, name: str) -> "_Span":
        """Time the ``with`` body as ``name``."""
        if name not in self.counts:
            raise KeyError(
                f"unknown span {name!r}; declared: {self._names}")
        return _Span(self, name)

    def snapshot(self) -> dict[str, tuple[int, float]]:
        return {k: (self.counts[k], self.seconds[k]) for k in self._names}

    def delta(self, before: Mapping[str, tuple[int, float]]
              ) -> dict[str, dict]:
        """Count and seconds per name since ``before`` (a
        :meth:`snapshot`)."""
        out = {}
        for k in self._names:
            n0, s0 = before.get(k, (0, 0.0))
            out[k] = {"count": self.counts[k] - n0,
                      "seconds": self.seconds[k] - s0}
        return out


class _Span:
    __slots__ = ("_reg", "_name", "_note", "_t0")

    def __init__(self, reg: SpanStats, name: str) -> None:
        self._reg, self._name = reg, name

    def __enter__(self) -> "_Span":
        self._note = _Annotation(self._name)
        self._note.__enter__()
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        dt = _clock() - self._t0
        self._note.__exit__(*exc)
        reg = self._reg
        reg.counts[self._name] += 1
        reg.seconds[self._name] += dt
        return False


_Annotation = jax.profiler.TraceAnnotation
_clock = time.perf_counter


#: The process-wide span registry.
SPANS = SpanStats(SPAN_NAMES)
span = SPANS.span
