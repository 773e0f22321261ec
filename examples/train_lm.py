"""End-to-end LM training driver example.

Default: a CPU-sized deepseek-family model for 200 steps with checkpoints
every 50 (resume by re-running the same command).  ``--hundred-m`` scales
the model to ~100M parameters — the same code path, sized for a real
accelerator (on CPU it is slow; the default proves the loop end-to-end).

    PYTHONPATH=src python examples/train_lm.py
    PYTHONPATH=src python examples/train_lm.py --mode brainslug --steps 50
"""
import argparse

from repro.launch import compile_cache
from repro.launch.train import TrainerConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--mode", default="xla",
                    choices=["brainslug", "xla", "barrier"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--hundred-m", action="store_true",
                    help="~100M-param config (accelerator-sized)")
    args = ap.parse_args()
    compile_cache.configure()

    if args.hundred_m:
        # ~100M params: 8L x d512 x ffn2048, 32k vocab at seq 512
        overrides = (("n_layers", 8), ("d_model", 512), ("n_heads", 8),
                     ("n_kv_heads", 4), ("d_head", 64), ("d_ff", 2048),
                     ("vocab_size", 32768))
        tc = TrainerConfig(arch=args.arch, reduced=True, steps=args.steps,
                           mode=args.mode, ckpt_dir=args.ckpt_dir,
                           ckpt_every=50, batch_override=8,
                           seq_override=512, lr=1e-3,
                           config_overrides=overrides)
    else:
        tc = TrainerConfig(arch=args.arch, reduced=True, steps=args.steps,
                           mode=args.mode, ckpt_dir=args.ckpt_dir,
                           ckpt_every=50, batch_override=4,
                           seq_override=64, lr=3e-3)

    history = train(tc)
    if history:
        print(f"\nloss: {history[0]['loss']:.4f} -> "
              f"{history[-1]['loss']:.4f} over {len(history)} steps")
        print(f"checkpoints under {args.ckpt_dir} — re-run to resume.")
    else:
        print("nothing to do (already trained to --steps; "
              "bump --steps or clear the checkpoint dir)")


if __name__ == "__main__":
    main()
