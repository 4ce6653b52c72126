"""Training launcher: any assigned architecture on any mesh.

    python -m repro.launch.train --arch qwen3-1.7b --reduced \\
        --steps 50 --batch 8 --seq 128 --mode explicit

Full configs target the production mesh (real TPU pods); ``--reduced``
runs the smoke-scale variant of the same family on local devices. The
mesh is (data, model) from --dp/--tp (defaults fit the local device
count). On a CPU host, emulate a slice by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the run.
"""
import argparse

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import local_mesh
from repro.train import loop as train_loop
from repro.train import optimizer as opt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(
        list(configs._MODULES)))
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config of the same family")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dp", type=int, default=0)
    ap.add_argument("--tp", type=int, default=0)
    ap.add_argument("--mode", default="auto", choices=["auto", "explicit"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)

    mesh = local_mesh(args.dp, args.tp)
    print(f"arch={cfg.name} params≈{cfg.param_count()/1e6:.1f}M "
          f"mesh={tuple(mesh.shape.values())} mode={args.mode}")

    res = train_loop.run(
        cfg, mesh,
        train_loop.TrainConfig(
            steps=args.steps, global_batch=args.batch, seq_len=args.seq,
            mode=args.mode, ckpt_dir=args.ckpt_dir, log_every=10),
        opt_cfg=opt.AdamWConfig(lr=args.lr, total_steps=args.steps,
                                warmup_steps=max(args.steps // 20, 1)))
    print(f"final loss {res['losses'][-1]:.4f} "
          f"({res['mean_step_s']:.2f}s/step, {res['stragglers']} stragglers)")


if __name__ == "__main__":
    main()
