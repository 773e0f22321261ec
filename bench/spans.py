"""Per-layer numbers read from the program's own spans
(``repro.obs.span``), shared by the readers under ``metrics/``.

A span is timed twice: into the process's ``repro.obs.SPANS`` registry
(count and seconds per name), and, under a running profiler, as a host
event of the same name on the device trace's clock.  Set-up happens
before any profiler window, so set-up spans are read from the registry;
the serve engine's tick is read from the traced window.  A program
without the registry or the spans gives nothing to read, never an
error.
"""
from __future__ import annotations

import statistics

TICK, SYNC, ADMIT = "engine.tick", "engine.sync", "engine.admit"


def tick_host_seconds(trace) -> list[float]:
    """For each ``engine.tick`` span wholly inside the traced window: its
    length less the ``engine.sync`` spans nested in it (the host waiting
    for the device), plus the ``engine.admit`` span that opened its
    scheduler iteration, just before it.  A tick whose admission the
    trace does not hold is left out with the partial ones."""
    lo, hi = trace.window
    ticks, syncs, admits = [], [], []
    for e in trace.host:
        if e.name == TICK and lo <= e.start and e.end <= hi:
            ticks.append(e)
        elif e.name == SYNC:
            syncs.append(e)
        elif e.name == ADMIT:
            admits.append(e)
    ticks.sort(key=lambda e: e.start)
    admits.sort(key=lambda e: e.start)
    out, prev_end = [], None
    for t in ticks:
        admit = [a for a in admits if a.end <= t.start
                 and (prev_end is None or a.start >= prev_end)]
        prev_end = t.end
        if not admit:
            continue
        waited = sum(s.end - s.start for s in syncs
                     if t.start <= s.start and s.end <= t.end)
        a = admit[-1]
        out.append((t.end - t.start - waited + a.end - a.start) * 1e-9)
    return out


def tick_host_ms(trace) -> float | None:
    """Median of :func:`tick_host_seconds`, in ms: the host time per tick
    in which the engine keeps the device waiting."""
    if trace is None:
        return None
    per_tick = tick_host_seconds(trace)
    return 1e3 * statistics.median(per_tick) if per_tick else None


def registry_seconds(name: str) -> float | None:
    """Seconds the process spent in span ``name``, from the registry; only
    while exactly one ``optimize()`` call has run in the process, whose
    set-up the metric describes."""
    try:
        from repro.obs import SPANS
    except ImportError:             # a program that keeps no spans
        return None
    if SPANS.counts.get("optimize.trace") != 1 or not SPANS.counts.get(name):
        return None
    return SPANS.seconds[name]
