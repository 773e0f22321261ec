"""A kernel family's share of its roofline in a traced window."""
from __future__ import annotations

import counts


def share(trace, source: str, work: counts.KernelWork, expected_calls: int,
          peaks: dict) -> float | None:
    """Least time of ``work`` over the summed device time of the events
    attributed to ``source``, in %.  Nothing when the trace holds no such
    event, or not exactly the ``expected_calls`` the work was counted for
    (a kernel left out of the path, or events the profiler dropped, would
    otherwise inflate the share)."""
    n = trace.kernel_count(source)
    seconds = trace.kernel_seconds(source)
    if n == 0 or seconds <= 0 or n != expected_calls:
        return None
    least = work.least_seconds(peaks["bf16_flops_per_s"],
                               peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def per_step_share(rec, name: str, source: str, peaks: dict):
    """``share`` for a kernel family whose work and calls repeat every
    step of a closed loop."""
    work = rec.facts.get("kernel_work", {}).get(name)
    steps = rec.facts.get("traced_steps")
    if rec.trace is None or work is None or not steps:
        return None
    calls = rec.facts["kernel_calls"][name]
    return share(rec.trace, source,
                 counts.KernelWork(work.flops * steps, work.bytes * steps),
                 calls * steps, peaks)
