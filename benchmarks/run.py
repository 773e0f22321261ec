"""Benchmark entry point — one bench per paper table/figure.

  fig10    stacked <MaxPool,BN,ReLU> blocks (strategies + overflow artifact)
  table2   full-network census + schedule speed-up (CNN zoo + LM archs)
  fig15    batch-size scaling of the schedule effect
  roofline three-term roofline per dry-run cell (needs results/dryrun)
  serve    continuous-batching engine vs static batching throughput
  scaling  data-parallel train-step throughput, 1 -> 8 forced host devices

``python -m benchmarks.run`` runs everything with CPU-sized defaults and
writes CSVs under results/bench/.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

from benchmarks import common


def write_summary(out_path: str = "BENCH_summary.json",
                  bench_dir: str = "results/bench") -> dict:
    """Consolidate every per-bench JSON artifact into one machine-readable
    summary at the repo root — the perf trajectory downstream tooling (and
    CI artifact diffing across PRs) consumes."""
    summary: dict = {"benches": {}}
    for path in sorted(glob.glob(os.path.join(bench_dir, "*.json"))):
        name = os.path.splitext(os.path.basename(path))[0]
        try:
            with open(path) as f:
                summary["benches"][name] = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            summary["benches"][name] = {"error": str(e)}
    # the never-slower decision cache the benches populated: named in the
    # summary so CI uploads it next to the rows it explains
    cache_dir = common.bench_autotune_cache_dir()
    entries = sorted(os.path.basename(p) for p in
                     glob.glob(os.path.join(cache_dir, "*.json")))
    summary["autotune_cache"] = {"dir": cache_dir, "n_entries": len(entries),
                                 "entries": entries}
    with open(out_path, "w") as f:
        json.dump(summary, f, indent=2, default=float)
    print(f"[benchmarks] wrote {out_path} "
          f"({len(summary['benches'])} artifacts)")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("benches", nargs="*",
                    default=["fig10", "table2", "fig15", "roofline",
                             "serve", "scaling"])
    ap.add_argument("--quick", action="store_true",
                    help="smaller grids (CI mode)")
    args = ap.parse_args(argv)
    from repro.launch import compile_cache
    compile_cache.configure()
    t0 = time.time()

    for bench in args.benches:
        print(f"\n===== {bench} =====", flush=True)
        common.reset_dispatch_stats()   # phase boundary: no count bleed
        if bench == "fig10":
            from benchmarks import fig10_stacked_layers as m
            m.run(block_counts=(1, 4, 16) if args.quick
                  else (1, 2, 4, 8, 12, 16, 24, 32, 40))
        elif bench == "table2":
            from benchmarks import table2_networks as m
            m.run_cnns()
            m.run_lms()
        elif bench == "fig15":
            from benchmarks import fig15_batch_scaling as m
            m.run(batches=(1, 8, 64) if args.quick
                  else (1, 2, 4, 8, 16, 32, 64, 128, 256))
        elif bench == "roofline":
            from benchmarks import roofline_report as m
            m.run()
        elif bench == "serve":
            from benchmarks import serve_throughput as m
            if args.quick:
                m.run(**m.QUICK_KWARGS)
            else:
                m.run()
        elif bench == "scaling":
            from benchmarks import scaling_curve as m
            m.run(device_counts=(1, 8) if args.quick else (1, 2, 4, 8),
                  steps=3 if args.quick else 6)
        else:
            print(f"unknown bench {bench!r}", file=sys.stderr)
            return 2
    write_summary()
    print(f"\n[benchmarks] done in {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
