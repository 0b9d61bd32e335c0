"""End-to-end training driver: a ~100M-parameter llama-style model trained
for a few hundred steps with the full production stack — sharded step,
deterministic data pipeline, atomic checkpoints, preemption guard, versioned
snapshot store.

  PYTHONPATH=src python examples/train_lm.py                 # ~100M, 200 steps
  PYTHONPATH=src python examples/train_lm.py --tiny          # CI-sized
  PYTHONPATH=src python examples/train_lm.py --resume        # restart test

The config is deepseek-7b's family scaled to ~100M params (8L x 768d, the
same GQA/SwiGLU/RMSNorm stack as the full config) so everything exercised
here is exactly what the production configs run.
"""

import argparse
import dataclasses

import numpy as np

from repro.configs import get_config
from repro.configs.shapes import Shape
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import train


def model_100m():
    base = get_config("deepseek_7b")
    return dataclasses.replace(
        base, name="llama-100m", n_layers=8, d_model=768, n_heads=12,
        n_kv_heads=12, d_ff=2048, vocab=32000, q_block=256, kv_block=256)


def model_tiny():
    return get_config("deepseek_7b", reduced=True)


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/atomax_train_lm")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    if args.tiny:
        cfg = model_tiny()
        shape = Shape("train", args.seq or 128, args.batch or 2, "train")
        steps = args.steps or 20
    else:
        cfg = model_100m()
        shape = Shape("train", args.seq or 512, args.batch or 4, "train")
        steps = args.steps or 200

    import jax
    n_params = cfg.n_params()
    print(f"[example] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"seq={shape.seq_len} batch={shape.global_batch} steps={steps}")
    if not args.resume:
        import shutil
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    params, opt, hist = train(cfg, shape, steps=steps,
                              ckpt_dir=args.ckpt_dir, ckpt_every=50,
                              log_every=10, lr=1e-3)
    losses = hist["loss"]
    print(f"[example] loss {losses[0]:.3f} -> {losses[-1]:.3f}  "
          f"({np.mean(hist['step_time'][1:]):.2f}s/step)")
    assert losses[-1] < losses[0], "loss should decrease"


if __name__ == "__main__":
    main()
