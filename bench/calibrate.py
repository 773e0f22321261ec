#!/usr/bin/env python3
"""Readings that the correctness limits are set from (``bench/limits``).

    python3 bench/calibrate.py --workload <name> --seeds 1,2,... [--seconds S]

For every seed, in one process on the chip: the program's reading of each
compared number (a sound run of the timed path at the cell's own size),
the control's (the plain reference in the next lower precision than the
configuration states, put in the program's place), and for a training
cell the planted fault that reads most (half of each batch left out, in
the reference put in the program's place).  A state left unchanged reads
1 on the gradient-change number by construction and needs no run.  Prints
one JSON line per seed and a summary last.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import run as run_mod  # noqa: E402

#: the precision one step below what each configuration states
CONTROL = {"float32": "high", "bfloat16": "fp8"}


def image_readings(cell, seeds, seconds):
    import jax
    import numpy as np

    image = harness.load_module(BENCH / "drivers" / "image.py")
    train = cell.traffic["mode"] == "train"
    precision = cell.cfg["matmul_precision"]
    control = CONTROL[precision]
    step = None
    for seed in seeds:
        init_params, batches = image.inputs(cell, seed)
        with jax.default_matmul_precision(precision):
            if step is None:
                step, _ = image.build(cell, init_params(), batches[0][0])
            if train:
                state, prog = image.first_steps(step, init_params(), batches)
                prog["change"] = image.change_norms(state[0], init_params())
                prog = image.to_host(prog)
                del state
            else:
                sample = image.sample_steps(
                    dataclasses.replace(cell, seed=seed), cell.traffic)
                params = init_params()
                _, _, kept = image.closed_loop(
                    lambda i: step(batches[i % len(batches)][0], params),
                    seconds, keep=sample)
                outputs = {i: np.asarray(jax.device_get(v), np.float64)
                           for i, v in kept.items()}
                del kept, params
        if train:
            ref = image.reference_numbers(cell, init_params, batches)
            ctrl = image.reference_numbers(cell, init_params, batches,
                                           control)
            half = [(x[: len(x) // 2], y[: len(y) // 2]) for x, y in batches]
            fault = image.reference_numbers(cell, init_params, half)
            out = {"program": image.compare_train(prog, ref)[0],
                   "control": image.compare_train(ctrl, ref)[0],
                   "half_batch": image.compare_train(fault, ref)[0]}
        else:
            params = init_params()
            prog_gap, ctrl_gap = 0.0, 0.0
            for i, y in outputs.items():
                x = batches[i % len(batches)][0]
                want = image.reference_logits(cell, params, x)
                prog_gap = max(prog_gap, image.worst_row_gap(y, want))
                ctrl_gap = max(ctrl_gap, image.worst_row_gap(
                    image.reference_logits(cell, params, x, control), want))
            del params
            out = {"program": {"logit_row_gap": prog_gap},
                   "control": {"logit_row_gap": ctrl_gap}}
        yield seed, out


def serve_readings(cell, seeds, seconds):
    import functools

    import jax

    serve = harness.load_module(BENCH / "drivers" / "serve.py")
    import generate
    from repro.launch.engine import Request

    cfg, mix = cell.cfg, cell.traffic
    warmed = False
    for seed in seeds:
        w = jax.jit(functools.partial(cell.model.init, cfg))(
            serve.key_of(seed))
        engine = serve.build_engine(cfg, w, seed)
        if not warmed:
            engine.run(serve.warm_queue(cfg))
            warmed = True
        queue = generate.serve_queue(mix, cfg["vocab_size"], seed)
        stream = serve.Stream(engine, [
            Request(request_id=q.rid, prompt=q.prompt,
                    max_new_tokens=q.max_new) for q in queue])
        log = serve.new_log()
        serve.first_wave(stream, log, cfg["serve"]["slots"])
        serve.serve_window(stream, log["last_t"], seconds, log)
        serve.finish(stream, log, queue, mix["sample_tokens"])
        stream.close()
        del stream, engine
        c = dataclasses.replace(cell, seed=seed)
        prog, n = serve.served_logit_gap(c, w, queue, log["done"])
        ctrl, _ = serve.served_logit_gap(c, w, queue, log["done"],
                                         quant=CONTROL[cfg["torch_dtype"]])
        del w
        yield seed, {"program": {"served_logit_gap": prog},
                     "control": {"served_logit_gap": ctrl},
                     "tokens_compared": n}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.launch import compile_cache

    compile_cache.configure()
    entry = {w["name"]: w for w in manifest["workloads"]}[args.workload]
    run_mod.require_tpu(entry["chips"])
    seconds = args.seconds or manifest["run_seconds"]
    cell = harness.load_cell(manifest, args.workload, seed=0,
                             seconds=seconds, trace=False)
    seeds = [int(s) for s in args.seeds.split(",")]
    readings = (serve_readings if cell.driver == "serve"
                else image_readings)(cell, seeds, seconds)
    summary: dict = {}
    t0 = time.perf_counter()
    for seed, out in readings:
        print(json.dumps({"seed": seed, **out,
                          "t": time.perf_counter() - t0}), flush=True)
        for kind in ("program", "control", "half_batch"):
            for name, v in out.get(kind, {}).items():
                summary.setdefault(kind, {}).setdefault(name, []).append(v)
    print(json.dumps({"summary": {
        kind: {name: {"max": max(v), "min": min(v)}
               for name, v in by.items()}
        for kind, by in summary.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
