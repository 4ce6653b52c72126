"""End-to-end training driver: ~100M-param llama-style model, DP×TP
mesh, the MSCCL++ stack on the gradient-reduction critical path
(mode=explicit), async checkpoints, resumable data pipeline.

    python examples/train_llm.py --steps 300          # the real run
    python examples/train_llm.py --steps 5 --tiny     # smoke
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh

from repro.models.config import ModelConfig
from repro.train import loop as train_loop
from repro.train import optimizer as opt


def model_100m() -> ModelConfig:
    return ModelConfig(
        name="llama-100m", family="dense",
        n_layers=10, d_model=640, n_heads=10, n_kv_heads=5, head_dim=64,
        d_ff=2560, vocab=32000, max_seq=2048, dtype="float32",
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_llm")
    ap.add_argument("--tiny", action="store_true",
                    help="shrink the model for a smoke run")
    ap.add_argument("--mode", default="explicit",
                    choices=["auto", "explicit"])
    args = ap.parse_args()

    cfg = model_100m()
    if args.tiny:
        cfg = dataclasses.replace(cfg, n_layers=2, d_model=128, n_heads=4,
                                  n_kv_heads=2, head_dim=32, d_ff=256,
                                  vocab=1024)
    n_params = cfg.param_count()
    print(f"model: {cfg.name}  params≈{n_params/1e6:.0f}M  mode={args.mode}")

    devs = jax.devices()[:8]
    mesh = Mesh(np.asarray(devs).reshape(2, 4), ("data", "model"))
    res = train_loop.run(
        cfg, mesh,
        train_loop.TrainConfig(
            steps=args.steps, global_batch=args.batch, seq_len=args.seq,
            ckpt_dir=args.ckpt_dir, ckpt_every=50, log_every=10,
            mode=args.mode),
        opt_cfg=opt.AdamWConfig(lr=3e-4, total_steps=args.steps,
                                warmup_steps=max(args.steps // 20, 1)))
    print(f"final loss: {res['losses'][-1]:.4f}  "
          f"mean step: {res['mean_step_s']:.3f}s  "
          f"stragglers: {res['stragglers']}")
    if res["plan_stats"]:
        # explicit mode: the DP communicators' compile-once record —
        # every gradient shape planned exactly once, then replayed
        print(f"dp plan caches: {res['plan_stats']}")


if __name__ == "__main__":
    main()
