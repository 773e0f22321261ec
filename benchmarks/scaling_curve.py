"""Device-scaling curve: LM train-step throughput over 1..N devices.

Each point runs the explicit data-parallel shard_map driver
(``repro.distributed.data_parallel``) on an n-device mesh, all points in
this one process: a device belongs to one process at a time, so a child
per point could not reach a chip the parent holds.  On the CPU, XLA fixes
the host device count when the backend starts, so :func:`run` asks for the
widest point's count first — which takes effect only when nothing has
touched the backend yet (``python -m benchmarks.run scaling``); points
wider than the devices that exist are left out.  Rows carry tokens/s, the
parallel efficiency vs the 1-device point, and the gradient wire bytes
the all-reduce moves per step (uncompressed f32 vs the int8
error-feedback payload).

On CPU the "devices" share the same cores, so tokens/s is flat-to-noisy
— the artifact is the *curve shape* plumbing (CI asserts the rows exist
and the wire-byte ratio, not wall-clock scaling, which needs real
accelerators).
"""
from __future__ import annotations

import os
import time

from benchmarks import common

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def _measure(n: int, *, arch: str, steps: int, batch: int, seq: int,
             compress: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.data import pipeline as data_mod
    from repro.distributed import data_parallel as dp_mod
    from repro.launch import train as tr

    tc = tr.TrainerConfig(arch=arch, steps=steps, mode="xla",
                          data_parallel=True, compress=compress,
                          mesh_devices=n, batch_override=batch,
                          seq_override=seq, log_every=10**9)
    trainer = tr.build_trainer(tc)
    pipe = data_mod.Pipeline(trainer.cfg, trainer.shape,
                             data_mod.DataConfig(seed=0), start_step=0,
                             batch_override=trainer.shape.global_batch)
    it = iter(pipe)
    p, o = trainer.params, trainer.opt_state

    def next_batch():
        _, b = next(it)
        return jax.tree_util.tree_map(jnp.asarray, b)

    try:
        p, o, m = trainer.step_fn(p, o, next_batch())      # compile
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            p, o, m = trainer.step_fn(p, o, next_batch())
        jax.block_until_ready(m["loss"])
        dt = time.perf_counter() - t0
    finally:
        pipe.close()
    tokens = batch * seq * steps
    return {
        "devices": n, "compress": compress,
        "tokens_per_s": tokens / dt,
        "step_ms": dt / steps * 1e3,
        "loss": float(m["loss"]),
        "wire_bytes_f32": dp_mod.wire_bytes(trainer.params, compress=False),
        "wire_bytes_int8": dp_mod.wire_bytes(trainer.params, compress=True),
    }


def run(device_counts=(1, 2, 4, 8), arch="deepseek-7b", steps=6,
        batch=8, seq=32, out_json="results/bench/scaling_curve.json"):
    flags = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} {_FORCE_FLAG}={max(device_counts)}".strip())
    import jax
    n_devices = len(jax.devices())
    device_counts = [n for n in device_counts if n <= n_devices]
    rows = []
    base = None
    for n in device_counts:
        row = _measure(n, arch=arch, steps=steps, batch=batch, seq=seq,
                       compress=False)
        if base is None:
            base = row["tokens_per_s"]
        row["efficiency"] = row["tokens_per_s"] / (base * n)
        rows.append(row)
        print(f"[scaling] devices={n} {row['tokens_per_s']:8.0f} tok/s "
              f"step={row['step_ms']:.1f}ms "
              f"eff={row['efficiency']:.2f}", flush=True)
    # one compressed point at the widest mesh: same curve, 4x fewer
    # gradient wire bytes (the cross-pod roofline term)
    n = device_counts[-1]
    row = _measure(n, arch=arch, steps=steps, batch=batch, seq=seq,
                   compress=True)
    row["efficiency"] = row["tokens_per_s"] / (base * n)
    rows.append(row)
    ratio = row["wire_bytes_f32"] / row["wire_bytes_int8"]
    print(f"[scaling] devices={n} (int8 grads) "
          f"{row['tokens_per_s']:8.0f} tok/s "
          f"wire {ratio:.2f}x smaller", flush=True)
    common.write_json(out_json, rows)
    return rows


if __name__ == "__main__":
    run()
