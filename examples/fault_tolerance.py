"""Fault-tolerance walkthrough: kill a training run mid-step, resume from
the atomic checkpoint, and verify the final loss matches an uninterrupted
run bit-for-bit in expectation.  Also demonstrates elastic mesh re-planning
when hosts are lost.

    PYTHONPATH=src python examples/fault_tolerance.py
"""
import shutil
import tempfile

import numpy as np

from repro.distributed import fault_tolerance as ft
from repro.launch import compile_cache
from repro.launch.train import TrainerConfig, train


def main() -> None:
    compile_cache.configure()
    workdir = tempfile.mkdtemp(prefix="repro_ft_")
    tc = lambda d: TrainerConfig(arch="deepseek-7b", reduced=True,  # noqa: E731
                                 steps=24, ckpt_dir=d, ckpt_every=8,
                                 batch_override=2, seq_override=32, lr=3e-3)

    print("=== 1. uninterrupted run (24 steps) ===")
    full = train(tc(workdir + "/a"))
    print(f"final loss: {full[-1]['loss']:.5f}")

    print("\n=== 2. run killed at step 13 (injected failure) ===")
    hook = ft.failure_injector({13})
    try:
        train(tc(workdir + "/b"), failure_hook=hook)
    except ft.SimulatedFailure as e:
        print(f"crashed as injected: {e}")

    print("\n=== 3. restart — auto-resumes from the step-8 checkpoint ===")
    resumed = train(tc(workdir + "/b"))
    print(f"resumed at step {resumed[0]['step']}, "
          f"final loss: {resumed[-1]['loss']:.5f}")
    match = np.isclose(resumed[-1]["loss"], full[-1]["loss"], rtol=1e-6)
    print(f"matches uninterrupted run: {match}")
    assert match

    print("\n=== 4. elastic re-meshing after losing hosts ===")
    for survivors in (256, 244, 192, 100):
        plan = ft.plan_mesh(survivors, model_parallel=16)
        idle = survivors - plan.n_devices
        print(f"  {survivors:4d} chips survive -> mesh {plan.shape} "
              f"({plan.n_devices} used, {idle} idle)")

    shutil.rmtree(workdir, ignore_errors=True)
    print("\nall good.")


if __name__ == "__main__":
    main()
