"""End-to-end training driver: sharded train step, deterministic data,
atomic checkpoints with auto-resume, straggler watchdog, failure injection.

This is the same ``train_step`` the dry-run lowers for the production
meshes; on CPU it runs a reduced config on the host mesh so the examples
and integration tests exercise the full loop (including kill/resume)
end-to-end.

Usage:
  python -m repro.launch.train --arch deepseek-7b --reduced --steps 100
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.checkpoint import checkpointer as ckpt
from repro.configs import LM_SHAPES, get_config
from repro.configs.base import ModelConfig, RuntimeConfig, ShapeConfig
from repro.data import pipeline as data_mod
from repro.distributed import compression
from repro.distributed import data_parallel as dp_mod
from repro.distributed import fault_tolerance as ft
from repro.distributed import sharding as shd
from repro.launch import compile_cache, mesh as mesh_mod
from repro.launch import steps as steps_mod
from repro.models import lm
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    arch: str = "deepseek-7b"
    shape: str = "train_4k"
    reduced: bool = True               # CPU-runnable variant
    steps: int = 100
    mode: str = "xla"                  # 'brainslug' | 'xla' | 'barrier'
    remat: str = "none"
    ckpt_dir: str = ""
    ckpt_every: int = 25
    log_every: int = 10
    seed: int = 0
    batch_override: int | None = None
    seq_override: int | None = None
    lr: float = 3e-3
    # explicit data-parallel driver: shard_map step over the mesh "data"
    # axis with a hand-written gradient all-reduce (see
    # repro.distributed.data_parallel) instead of the GSPMD default
    data_parallel: bool = False
    compress: bool = False             # int8 error-feedback grad payload
    mesh_devices: int | None = None    # force an n-device test mesh
    # arbitrary ModelConfig field overrides (applied after reduction) —
    # lets examples size custom models without a new registry entry
    config_overrides: tuple = ()       # of (field, value) pairs


@dataclasses.dataclass
class Trainer:
    tc: TrainerConfig
    cfg: ModelConfig
    shape: ShapeConfig
    mesh: Any
    step_fn: Callable
    params: Any
    opt_state: Any
    start_step: int
    watchdog: ft.StragglerWatchdog
    checkpointer: ckpt.AsyncCheckpointer | None
    history: list

    def run(self, failure_hook: Callable[[int], None] | None = None
            ) -> list[dict]:
        pipe = data_mod.Pipeline(
            self.cfg, self.shape,
            data_mod.DataConfig(seed=self.tc.seed),
            start_step=self.start_step,
            batch_override=self.shape.global_batch)
        try:
            for step, batch in pipe:
                if step >= self.tc.steps:
                    break
                if failure_hook is not None:
                    failure_hook(step)
                self.watchdog.start()
                dev_batch = jax.tree_util.tree_map(jnp.asarray, batch)
                self.params, self.opt_state, metrics = self.step_fn(
                    self.params, self.opt_state, dev_batch)
                loss = float(metrics["loss"])
                slow = self.watchdog.stop()
                rec = {"step": step, "loss": loss,
                       "grad_norm": float(metrics["grad_norm"]),
                       "slow": bool(slow)}
                self.history.append(rec)
                if step % self.tc.log_every == 0:
                    print(f"[train] step={step} loss={loss:.4f} "
                          f"gnorm={rec['grad_norm']:.3f}", flush=True)
                if (self.checkpointer is not None and step > 0
                        and step % self.tc.ckpt_every == 0):
                    self.checkpointer.submit(
                        step, {"params": self.params,
                               "opt": self.opt_state},
                        extra={"next_step": step + 1, "loss": loss})
            if self.checkpointer is not None:
                last = self.tc.steps - 1
                self.checkpointer.submit(
                    self.tc.steps,
                    {"params": self.params, "opt": self.opt_state},
                    extra={"next_step": self.tc.steps,
                           "loss": self.history[-1]["loss"]
                           if self.history else float("nan")})
                self.checkpointer.wait()
        finally:
            pipe.close()
        return self.history


def build_trainer(tc: TrainerConfig) -> Trainer:
    cfg = get_config(tc.arch)
    shape = LM_SHAPES[tc.shape]
    if tc.reduced:
        cfg = cfg.reduced()
        shape = shape.reduced()
    if tc.config_overrides:
        cfg = dataclasses.replace(cfg, **dict(tc.config_overrides))
    if tc.batch_override:
        shape = dataclasses.replace(shape, global_batch=tc.batch_override)
    if tc.seq_override:
        shape = dataclasses.replace(shape, seq_len=tc.seq_override)

    mesh = (mesh_mod.make_test_mesh(tc.mesh_devices) if tc.mesh_devices
            else mesh_mod.make_host_mesh())
    rt = RuntimeConfig(mode=tc.mode, remat=tc.remat)
    rules = shd.ShardingRules()

    params, axes = lm.init(jax.random.PRNGKey(tc.seed), cfg)
    pspecs = shd.repair_specs(params, shd.param_specs(axes, rules, mesh),
                              mesh)
    opt_cfg = adamw.AdamWConfig(
        lr=tc.lr if tc.reduced else steps_mod.default_opt_config().lr)
    opt_state = adamw.init(params)

    if tc.data_parallel:
        # explicit shard_map data-parallel step: params/opt replicated,
        # batch sharded over "data", hand-written (optionally compressed)
        # gradient all-reduce inside the region
        dpc = dp_mod.DPConfig(compress=tc.compress)

        def loss(p, b):
            return lm.loss_fn(p, b, cfg, rt)

        raw_step = dp_mod.make_dp_train_step(loss, opt_cfg, mesh, dpc)
        opt_state = {"opt": opt_state}
        if tc.compress:
            opt_state["err"] = compression.init_error_state(params)

        def dp_step(p, opt_wrap, batch):
            state = {"params": p, "opt": opt_wrap["opt"]}
            if "err" in opt_wrap:
                state["err"] = opt_wrap["err"]
            new_state, metrics = raw_step(state, batch)
            ow = {k: new_state[k] for k in opt_wrap}
            return new_state["params"], ow, metrics

        with mesh:
            step_fn = jax.jit(dp_step, donate_argnums=(0, 1))
    else:
        step = steps_mod.make_train_step(cfg, rt, opt_cfg)
        ospecs = shd.opt_state_specs(pspecs, mesh)
        bspecs = steps_mod._maybe_batch_spec(
            steps_mod.input_specs(cfg, shape), mesh)

        def to_sh(tree):
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), tree,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))

        with mesh:
            step_fn = jax.jit(step,
                              in_shardings=(to_sh(pspecs), to_sh(ospecs),
                                            to_sh(bspecs)),
                              donate_argnums=(0, 1))

    # ---- auto-resume -------------------------------------------------------
    start_step = 0
    checkpointer = None
    if tc.ckpt_dir:
        # robust resume: crash orphans are swept, a truncated latest
        # checkpoint falls back to the previous complete one
        restored = ckpt.restore_latest(tc.ckpt_dir,
                                       {"params": params, "opt": opt_state})
        if restored is not None:
            tree, extra, latest = restored
            params, opt_state = tree["params"], tree["opt"]
            if tc.data_parallel and tc.compress:
                # the saved residual compensated a quantization the saved
                # params already absorbed — replaying it would apply that
                # correction twice; resume restarts the feedback loop
                opt_state = {**opt_state, "err": compression.
                             reset_error_state(opt_state["err"])}
            start_step = int(extra.get("next_step", latest))
            print(f"[train] resumed from step {latest} "
                  f"(next_step={start_step})", flush=True)
        checkpointer = ckpt.AsyncCheckpointer(tc.ckpt_dir)

    return Trainer(tc=tc, cfg=cfg, shape=shape, mesh=mesh, step_fn=step_fn,
                   params=params, opt_state=opt_state,
                   start_step=start_step,
                   watchdog=ft.StragglerWatchdog(),
                   checkpointer=checkpointer, history=[])


def train(tc: TrainerConfig,
          failure_hook: Callable[[int], None] | None = None) -> list[dict]:
    trainer = build_trainer(tc)
    try:
        return trainer.run(failure_hook)
    finally:
        if trainer.checkpointer is not None:
            trainer.checkpointer.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mode", default="xla",
                    choices=["brainslug", "xla", "barrier"])
    ap.add_argument("--remat", default="none")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--data-parallel", action="store_true",
                    help="explicit shard_map DP step (hand-written "
                         "gradient all-reduce)")
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression "
                         "(requires --data-parallel)")
    ap.add_argument("--mesh-devices", type=int, default=None,
                    help="force an n-device test mesh "
                         "(host platform devices)")
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args(argv)
    compile_cache.configure()

    tc = TrainerConfig(arch=args.arch, shape=args.shape, steps=args.steps,
                       mode=args.mode, remat=args.remat,
                       reduced=args.reduced, ckpt_dir=args.ckpt_dir,
                       ckpt_every=args.ckpt_every,
                       batch_override=args.batch, seq_override=args.seq,
                       lr=args.lr, data_parallel=args.data_parallel,
                       compress=args.compress,
                       mesh_devices=args.mesh_devices)
    t0 = time.time()
    history = train(tc)
    dt = time.time() - t0
    if history:
        print(f"[train] done: {len(history)} steps in {dt:.1f}s, "
              f"loss {history[0]['loss']:.4f} -> {history[-1]['loss']:.4f}",
              flush=True)
    if args.metrics_out:
        os.makedirs(os.path.dirname(args.metrics_out) or ".", exist_ok=True)
        with open(args.metrics_out, "w") as f:
            json.dump(history, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
