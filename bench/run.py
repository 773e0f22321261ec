#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: point JAX's compilation cache into the checkout
(``repro.launch.compile_cache.configure``), refuse to run without a TPU,
build the cell's weights and inputs on the device from the seed, warm the
cell's own shapes, measure for ``--seconds``, check what the timed path
produced against the plain reference, and print one JSON object as the
last line of standard output.  ``--trace 1`` reports the per-layer metrics
instead of the end-to-end ones, from the same window plus a short traced
one after it.  The numbers compared for ``correct`` are printed with
their limits as the last lines of standard error and under ``checks`` in
the result line.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_tpu(chips: int) -> list:
    """The TPU devices, or BenchError: this benchmark never falls back to
    another platform."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise harness.BenchError(
            f"no TPU found (JAX platform {devices[0].platform!r}); this "
            f"benchmark measures the chip only")
    if len(devices) < chips:
        raise harness.BenchError(
            f"{len(devices)} TPU device(s); the cell needs {chips}")
    return devices


def measure(manifest: dict, args, *, find_devices=require_tpu,
            bench: pathlib.Path = BENCH, peaks: dict | None = None,
            process_start: float = PROCESS_START) -> str:
    """One run of one cell; returns the result line.  ``find_devices`` and
    ``peaks`` are for tests, which drive the rest of a run on the CPU."""
    src = bench.parent / "src"
    if not (src / "repro").is_dir():
        raise harness.BenchError(f"no repro package under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro.launch import compile_cache

    compile_cache.configure()
    entry = {w["name"]: w for w in manifest["workloads"]}.get(args.workload)
    if entry is None:
        raise harness.BenchError(f"unknown workload {args.workload!r}")
    devices = find_devices(entry["chips"])
    dev = devices[0]
    if peaks is None:
        peaks = harness.peaks_for(dev.device_kind)
    cell = harness.load_cell(manifest, args.workload, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             bench=bench)
    driver = harness.load_module(bench / "drivers" / f"{cell.driver}.py")
    rec = driver.run(cell, process_start=process_start)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": entry["chips"],
              "memory_peak_bytes": rec.memory_peak_bytes}
    breakdown = None
    if cell.trace:
        entries = harness.metrics_of(manifest, args.workload, "per_layer")
        values = {}
        for m in entries:
            reader = harness.load_module(bench / "metrics" / f"{m['name']}.py")
            v = reader.read(rec, peaks)
            if v is not None:
                values[m["name"]] = v
        if rec.trace is not None:
            device["busy_s"] = rec.trace.busy_s
            device["window_s"] = rec.trace.window_s
            breakdown = rec.trace.breakdown()
    else:
        entries = harness.metrics_of(manifest, args.workload, "end_to_end")
        values = {m["name"]: rec.e2e[m["name"]] for m in entries
                  if m["name"] in rec.e2e}
    missing = [m["name"] for m in entries if m["name"] not in values
               and not cell.trace]
    if missing:
        raise harness.BenchError(f"driver measured no {missing}")
    for name, c in rec.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return harness.result_line(rec, entries, values, device, breakdown)


def main(argv=None) -> int:
    args = parse(argv)
    manifest_path = ROOT / "BENCHMARK.json"
    try:
        if not manifest_path.is_file():
            raise harness.BenchError(f"missing {manifest_path}")
        line = measure(json.loads(manifest_path.read_text()), args)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
