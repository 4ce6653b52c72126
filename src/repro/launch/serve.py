"""Serving launcher: batched prefill+decode for any decode-capable arch.

    python -m repro.launch.serve --arch qwen3-1.7b --reduced \\
        --batch 8 --prompt-len 12 --tokens 32 [--kv-quant]

With ``--replicas N`` it instead runs the continuous-batching stack
(docs/serving.md): N engine replicas of tp devices each, every one
initialized from the SAME exported plan-file set (--plan-dir keeps the
artifact), behind the least-loaded router, driven by a seeded
virtual-clock request trace::

    python -m repro.launch.serve --arch qwen3-1.7b --reduced \\
        --replicas 2 --tp 2 --mode explicit --requests 20

The mesh defaults to this host's devices (TP up to 4, the rest DP). On
a CPU host, emulate a slice by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the run.
"""
import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.distributed import sharding as shd
from repro.distributed.step import init_sharded
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import local_mesh
from repro.serve.engine import Engine, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(
        list(configs._MODULES)))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--max-kv", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel size (0: from the device count)")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel size (0: from the device count)")
    ap.add_argument("--mode", choices=("auto", "explicit"), default="auto",
                    help="decode partitioning: GSPMD (auto) or the "
                         "explicit-TP plan-replay hot path (§5.2)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache with per-token scales "
                         "(both modes; explicit keeps scales "
                         "TP-replicated next to the cache)")
    ap.add_argument("--replicas", type=int, default=0,
                    help=">=1: run the continuous-batching router over "
                         "N plan-file replicas instead of the one-shot "
                         "prefill+decode path")
    ap.add_argument("--requests", type=int, default=20,
                    help="router path: synthetic requests to serve")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="router path: Poisson arrival rate "
                         "(requests per virtual second)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan-dir", default=None,
                    help="router path: where to export/load the shared "
                         "plan-file set (default: a temp dir)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if cfg.family == "encoder":
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")
    if args.reduced:
        cfg = configs.reduced(cfg)

    if args.replicas >= 1:
        return _serve_router(cfg, args)

    mesh = local_mesh(args.dp, args.tp)
    params, _ = init_sharded(cfg, mesh, shd.MeshAxes(), jax.random.key(0))
    eng = Engine(cfg, params, mesh,
                 ServeConfig(batch=args.batch, max_kv=args.max_kv,
                             temperature=args.temperature, mode=args.mode,
                             kv_quant=args.kv_quant))
    if args.mode != eng.mode:
        print(f"note: mode={args.mode} unavailable, running {eng.mode}")
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)

    t0 = time.perf_counter()
    logits = eng.prefill(prompts)
    t_pre = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = eng.decode(logits, num_tokens=args.tokens)
    t_dec = time.perf_counter() - t0
    rep = eng.plan_report()
    print(f"arch={cfg.name} mode={eng.mode} prefill {t_pre*1e3:.0f}ms, "
          f"decode {t_dec/args.tokens*1e3:.1f}ms/token × {args.batch} seqs "
          f"(pred comm {rep['predicted_comm_us_per_token']}us/token)")
    print("seq0:", out[0][:12].tolist())


def _serve_router(cfg, args):
    """The continuous-batching path: plan once → export → N replicas
    load the same files → seeded virtual-clock trace through the
    least-loaded router."""
    import tempfile
    from collections import deque

    from repro.serve.router import build_replicas
    from repro.serve.scheduler import Request

    plan_dir = args.plan_dir or tempfile.mkdtemp(prefix="repro_plan_set_")
    router = build_replicas(
        cfg, ServeConfig(batch=args.batch, max_kv=args.max_kv,
                         temperature=args.temperature,
                         mode=args.mode, kv_quant=args.kv_quant),
        n_replicas=args.replicas,
        tp=args.tp or max(len(jax.devices()) // args.replicas, 1),
        plan_dir=plan_dir,
        mode=args.mode)

    rng = np.random.RandomState(args.seed)
    t, pending = 0.0, deque()
    for i in range(args.requests):
        t += rng.exponential(1.0 / args.rate)
        plen = int(min(rng.zipf(1.5), args.prompt_len))
        pending.append(Request(
            rid=i, prompt=rng.randint(0, cfg.vocab, plen).astype(np.int32),
            max_new_tokens=args.tokens, arrival_s=t,
            temperature=args.temperature, seed=i))

    step_s = 0.05
    t0 = time.perf_counter()
    while pending or router.outstanding():
        while pending and pending[0].arrival_s <= router.now:
            router.submit(pending.popleft())
        if router.n_active == 0 and router.outstanding() == 0 and pending:
            router.advance_to(pending[0].arrival_s)
            continue
        info = router.tick()
        router.advance(step_s * (1 + info.micro_steps))
    wall = time.perf_counter() - t0

    m = router.metrics()
    rep = router.plan_report()
    print(f"arch={cfg.name} router: {args.replicas} replicas x "
          f"tp={router.replicas[0].eng.mesh.shape['model']} modes={rep['modes']} degraded={rep['degraded']} "
          f"(plans from {plan_dir})")
    print(f"served {m['completed']}/{args.requests} requests "
          f"({m['dropped']} dropped), {m['tokens']} tokens at "
          f"{m['tokens_per_vs']} tok/vs; ttft_vs p50={m['ttft_vs']['p50']:.3f} "
          f"p95={m['ttft_vs']['p95']:.3f}; bucket_steps={m['bucket_steps']} "
          f"[{wall:.1f}s wall]")
    for rid in sorted(router.streams)[:1]:
        print(f"req{rid}:", router.streams[rid][:12])


if __name__ == "__main__":
    main()
