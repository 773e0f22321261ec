"""Shared benchmark utilities: paper-style timing (min of K repeats) and
HLO cost extraction for schedule-level comparisons."""
from __future__ import annotations

import time
from typing import Callable

import jax


def reset_dispatch_stats() -> None:
    """Zero the fused-stack, kernel-registry, and autotune counters at a
    benchmark phase boundary.  All three STATS are process-global
    singletons; without this, counts recorded while one benchmark traces
    its executables bleed into the next phase's numbers.  The autotuner's
    in-memory decision memo is cleared too so each benchmark's warm-cache
    behaviour comes from the on-disk cache, which is the artifact CI
    uploads."""
    from repro.core import autotune, registry
    from repro.kernels.fused_stack import ops as fused_ops

    fused_ops.STATS.reset()
    registry.STATS.reset()
    autotune.STATS.reset()
    autotune.clear_memory_cache()


def bench_autotune_cache_dir() -> str:
    """Shared on-disk decision cache for the benchmark drivers — kept
    under results/bench so `benchmarks.run` bundles it with the summary
    and CI can upload it as an artifact.  ``REPRO_AUTOTUNE_CACHE``
    overrides (same variable the library honors)."""
    import os
    return os.environ.get("REPRO_AUTOTUNE_CACHE",
                          "results/bench/autotune_cache")


def autotune_pick(name: str, candidates: dict, args: tuple, *,
                  baseline: str, requested: str | None = None,
                  use_jit: bool = False, **kw) -> dict:
    """Run the never-slower autotuner over pre-built benchmark callables
    and return the row fields every benchmark table carries:
    ``chosen_variant`` (the committed winner), ``autotune_ms`` (wall time
    the measurement itself cost; 0.0 on a cache hit) and
    ``guardrail_trips`` (1 when the requested variant measured slower
    than the baseline and was floored)."""
    from repro.core import autotune

    # benchmark rows compare min-of-5 timings; give the tuner the same
    # sample budget so its median doesn't trip the floor on CPU noise
    kw.setdefault("repeats", 5)
    kw.setdefault("warmup", 2)
    decision, _ = autotune.pick_callable(
        name, candidates, args, baseline=baseline, requested=requested,
        cache_dir=bench_autotune_cache_dir(), use_jit=use_jit, **kw)
    base_ms = decision.ms_for(baseline)
    chosen_ms = decision.ms_for(decision.variant)
    # effective speedup of the committed dispatch over the baseline, from
    # the decision's own guardrail measurements: 1.0 when the baseline
    # itself was committed, and never below 1/FLOOR_SLACK otherwise
    tuned = (base_ms / chosen_ms if decision.variant != baseline
             and base_ms and chosen_ms else 1.0)
    return {
        "chosen_variant": decision.variant,
        "autotune_ms": decision.autotune_ms,
        "guardrail_trips": int(decision.guardrail_tripped),
        "tuned_speedup": tuned,
    }


def merge_tuned(fwd: dict, train: dict) -> dict:
    """Combine a forward-phase and a training-phase pick into one set of
    row fields: the headline ``chosen_variant`` is the forward winner,
    the training winner rides alongside, measurement cost and guardrail
    trips are summed across both phases."""
    return {
        "chosen_variant": fwd["chosen_variant"],
        "chosen_variant_train": train["chosen_variant"],
        "autotune_ms": fwd["autotune_ms"] + train["autotune_ms"],
        "guardrail_trips": fwd["guardrail_trips"] + train["guardrail_trips"],
        "tuned_speedup": fwd["tuned_speedup"],
        "tuned_train_speedup": train["tuned_speedup"],
    }


def time_fn(fn: Callable, *args, repeats: int = 5, warmup: int = 2) -> float:
    """Minimum wall time over ``repeats`` calls (paper §5: 'we take the
    minimum execution time')."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def hlo_cost(fn: Callable, *args) -> dict:
    """flops / bytes-accessed of the compiled function (schedule metric:
    bytes-accessed is the memory-traffic term the depth-first schedule
    attacks)."""
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0))}


def time_grad_fn(loss_fn: Callable, params, *args,
                 repeats: int = 5, warmup: int = 2) -> float:
    """Training-step timing: wall time of one jitted fwd+bwd
    (``jax.grad`` of ``loss_fn`` in its first argument)."""
    g = jax.jit(jax.grad(loss_fn))
    return time_fn(g, params, *args, repeats=repeats, warmup=warmup)


def write_csv(path: str, header: list[str], rows: list) -> None:
    import os
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        f.write(",".join(header) + "\n")
        for row in rows:
            f.write(",".join(str(x) for x in row) + "\n")


def write_json(path: str, rows: list[dict]) -> None:
    """Benchmark rows as JSON (one object per row) next to the CSV — the
    machine-readable artifact downstream tooling consumes."""
    import json
    import os
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(rows, f, indent=2, default=float)
