"""What every run shares: finding a cell's files by name, the peaks
table, the record a driver fills, and the result line.

Nothing here lists a configuration, a traffic mix or a metric.  A cell
names its configuration and its mix in ``BENCHMARK.json``; they are found
as files:

* ``bench/configs/<config>.json`` — the sizes as run, and
  ``bench/configs/<config>.py`` — the plain reference beside them;
* ``bench/traffic/<traffic>.json`` — the mix's parameters, whose
  ``driver`` key names ``bench/drivers/<driver>.py``;
* ``bench/limits/<workload>.json`` — the limits of the cell's
  correctness comparison;
* ``bench/metrics/<metric>.py`` — one per per-layer metric, with
  ``read(record, peaks) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import re
import sys
from typing import Any

BENCH = pathlib.Path(__file__).resolve().parent
PEAKS_FILE = BENCH / "peaks.json"


class BenchError(RuntimeError):
    """A run that cannot produce a result: it prints none and exits
    non-zero."""


def load_module(path: pathlib.Path):
    """Import a benchmark file by path (names may hold ``-`` and ``.``)."""
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    name = "bench_" + re.sub(r"\W", "_", str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    return json.loads(path.read_text())


def peaks_for(kind: str, table: pathlib.Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown kind is
    an error, never a default."""
    peaks = load_json(table)
    if kind not in peaks:
        raise BenchError(f"no published peaks for device kind {kind!r} in "
                         f"{table.name}; known: {sorted(peaks)}")
    return peaks[kind]


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    workload: str
    cfg: dict
    model: Any                  # the configuration's plain-reference module
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    out_dir: pathlib.Path       # for this run's trace; inside the checkout

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def load_cell(manifest: dict, workload: str, *, seed: int, seconds: float,
              trace: bool, bench: pathlib.Path = BENCH) -> Cell:
    entries = {w["name"]: w for w in manifest["workloads"]}
    if workload not in entries:
        raise BenchError(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(entries)}")
    w = entries[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    return Cell(
        workload=workload,
        cfg=load_json(bench.parent / cfg_entry["file"]),
        model=load_module(bench / "configs" / f"{w['config']}.py"),
        traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench / "limits" / f"{workload}.json"),
        seed=seed, seconds=seconds, trace=trace,
        out_dir=bench.parent / ".bench_out" / workload)


def metrics_of(manifest: dict, workload: str, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``workload``
    reports: those that list it, and those that list no workloads."""
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def peak_bytes() -> int | None:
    """Peak bytes in use on the (first) device, where the backend keeps
    the count."""
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


@dataclasses.dataclass
class Record:
    """What a driver hands back.  ``e2e`` holds the end-to-end values it
    measured by the host clock; ``facts`` the numbers per-layer readers
    use (counts, window lengths, gaps); ``trace`` the reduced device trace
    of the traced window (``--trace 1``); ``checks`` each number compared
    with its limit."""
    e2e: dict[str, float] = dataclasses.field(default_factory=dict)
    facts: dict[str, Any] = dataclasses.field(default_factory=dict)
    trace: Any = None
    checks: dict[str, dict] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int | None = None

    def check(self, name: str, value: float, limit: float) -> None:
        """Record one compared number; it passes when it does not exceed
        its limit (a NaN never passes)."""
        self.checks[name] = {"value": value, "limit": limit}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and self.attempted > 0 and all(
            math.isfinite(c["value"]) and c["value"] <= c["limit"]
            for c in self.checks.values())


def result_line(rec: Record, metric_entries: list[dict],
                values: dict[str, float], device: dict,
                breakdown: dict | None) -> str:
    out = {"correct": rec.correct, "attempted": rec.attempted,
           "failed": rec.failed,
           "metrics": {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in metric_entries if m["name"] in values},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = rec.checks
    return json.dumps(out)
