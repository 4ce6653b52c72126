import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell
against the production mesh, prove it fits, and extract the roofline
inputs (deliverables e & g).

The two lines above MUST precede any other import (jax locks the device
count at first init). Meshes: single-pod (16,16)=256 chips, multi-pod
(2,16,16)=512 chips ('pod' axis = DCN).

Usage:
    python -m repro.launch.dryrun --arch qwen3-1.7b --cell train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--mode explicit]
    python -m repro.launch.dryrun --list
Results land in experiments/dryrun/<arch>__<cell>__<mesh>[__<mode>].json.
"""
import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.distributed.step import (  # noqa: E402
    make_prefill_step, make_serve_step, make_train_step)
from repro.launch.mesh import make_production_mesh, mesh_axes_for  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.roofline import analysis as roof  # noqa: E402
from repro.train import optimizer as opt  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def input_specs(arch: str, cell: str):
    """ShapeDtypeStruct stand-ins for every model input of a cell —
    weak-type-correct, shardable, zero allocation."""
    cfg = configs.get_config(arch)
    shp = configs.SHAPES[cell]
    b, s = shp["global_batch"], shp["seq_len"]
    if cfg.frontend != "none" and shp["kind"] != "decode":
        tokens = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    else:
        tokens = jax.ShapeDtypeStruct((b, s), jnp.int32)
    labels = jax.ShapeDtypeStruct((b, s), jnp.int32)
    params = jax.eval_shape(functools.partial(tf.init_params, cfg),
                            jax.random.key(0))
    return cfg, dict(tokens=tokens, labels=labels, params=params,
                     batch=b, seq=s, kind=shp["kind"])


def model_flops(cfg, kind: str, batch: int, seq: int) -> float:
    """Analytic useful FLOPs: 6·N·D (+attention scores) for train,
    2·N·D for inference. Attention: per layer, causal qk+pv ≈
    2·s·min(s,window)·nh·hd per token-pair side; windows cap the
    quadratic term. Recurrent families (rwkv/ssm) have O(s) state math
    folded into the parameter count."""
    n = cfg.active_param_count() if cfg.family == "moe" else cfg.param_count()
    attn_prefill = attn_decode = 0.0
    if cfg.family != "rwkv6":
        wins = [w if w is not None else seq for w in tf.layer_windows(cfg)]
        layers_per_win = cfg.n_layers / len(wins)
        # qk + pv per layer (causal halves s·kv on average — keep full as
        # the roofline target, matching the chunked implementation)
        attn_prefill = sum(2.0 * 2.0 * seq * min(seq, w) * cfg.n_heads
                           * cfg.hd for w in wins) * layers_per_win * batch
        attn_decode = sum(2.0 * 2.0 * min(seq, w) * cfg.n_heads * cfg.hd
                          for w in wins) * layers_per_win * batch
    if kind == "train":
        return 6.0 * n * batch * seq + 3.0 * attn_prefill
    if kind == "prefill":
        return 2.0 * n * batch * seq + attn_prefill
    return 2.0 * n * batch + attn_decode  # decode: one token/sequence


# ---------------------------------------------------------------------------
# Hillclimb optimization bundles (§Perf): applied with --opt. Baselines
# stay paper/assignment-faithful; these are the beyond-baseline variants.
# ---------------------------------------------------------------------------
OPTIMIZATIONS = {
    # worst roofline fraction: 24 heads don't divide the 16-way model
    # axis -> GSPMD falls back to head_dim sharding and reshards every
    # attention reshape. Pad to 48 (g=3 preserved, nkv 8->16): exact
    # math (masked), every projection shards.
    "llama3.2-3b": dict(pad_heads_to=48, attn_chunk=2048),
    "hymba-1.5b": dict(pad_heads_to=80),
    # most collective-bound + paper-representative (MoE): explicit mode
    # puts the 2PH hierarchical DP reduction + bf16 wire on the grad path
    "mixtral-8x22b": dict(mode="explicit", dp_wire_dtype="bfloat16"),
    # the paper's llama2-70b-shaped decode: int8 KV cache halves the
    # dominant decode memory term
    "internvl2-76b": dict(kv_quant=True),
}


def lower_cell(arch: str, cell: str, *, multi_pod: bool, mode: str = "auto",
               apply_opt: bool = False):
    import dataclasses as _dc

    import jax.numpy as _jnp

    mesh = make_production_mesh(multi_pod=multi_pod)
    ax = mesh_axes_for(mesh)
    cfg, specs = input_specs(arch, cell)
    kind = specs["kind"]
    bundle = OPTIMIZATIONS.get(arch, {}) if apply_opt else {}
    kv_quant = bool(bundle.get("kv_quant"))
    dp_wire = (_jnp.bfloat16 if bundle.get("dp_wire_dtype") == "bfloat16"
               else None)
    if bundle.get("mode"):
        mode = bundle["mode"]
    if bundle.get("pad_heads_to"):
        cfg = _dc.replace(cfg, pad_heads_to=bundle["pad_heads_to"])
        specs["params"] = jax.eval_shape(
            functools.partial(tf.init_params, cfg), jax.random.key(0))
    if bundle.get("attn_chunk"):
        cfg = _dc.replace(cfg, attn_chunk=bundle["attn_chunk"])

    if kind == "train":
        step, _ = make_train_step(
            cfg, mesh, ax, opt.AdamWConfig(), mode=mode,
            global_batch=specs["batch"], seq_len=specs["seq"],
            remat_policy="full", fsdp=True, donate=False,
            dp_wire_dtype=dp_wire)
        opt_state = {
            "mu": jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                specs["params"]),
            "nu": jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                specs["params"]),
            "count": jax.ShapeDtypeStruct((), jnp.int32),
        }
        batch = dict(tokens=specs["tokens"], labels=specs["labels"])
        lowered = step.lower(specs["params"], opt_state, batch)
    elif kind == "prefill":
        step, _ = make_prefill_step(
            cfg, mesh, ax, global_batch=specs["batch"], seq_len=specs["seq"],
            fsdp=True, remat_policy="none")
        lowered = step.lower(specs["params"], specs["tokens"])
    else:  # decode
        step, _ = make_serve_step(
            cfg, mesh, ax, batch=specs["batch"], max_kv=specs["seq"],
            donate=False, fsdp=False, kv_quant=kv_quant)
        cache = jax.eval_shape(functools.partial(
            tf.init_cache, cfg, specs["batch"], specs["seq"],
            dtype=jnp.int8 if kv_quant else None))
        tokens = jax.ShapeDtypeStruct((specs["batch"],), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = step.lower(specs["params"], cache, tokens, pos)
    return mesh, cfg, specs, lowered


def run_cell(arch: str, cell: str, *, multi_pod: bool, mode: str = "auto",
             opt_bundle: bool = False, save: bool = True) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = 512 if multi_pod else 256
    t0 = time.time()
    mesh, cfg, specs, lowered = lower_cell(arch, cell, multi_pod=multi_pod,
                                           mode=mode, apply_opt=opt_bundle)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}

    cost_list = compiled.cost_analysis()
    cost = cost_list if isinstance(cost_list, dict) else (
        cost_list[0] if cost_list else {})
    hlo = compiled.as_text()
    pod_boundary = 256 if multi_pod else None
    rep = roof.roofline(
        arch=arch, cell=cell, mesh_name=mesh_name, chips=chips,
        cost=cost, hlo_text=hlo,
        model_flops=model_flops(cfg, specs["kind"], specs["batch"],
                                specs["seq"]) / chips,
        hw=roof.hardware_for("TPU v5 lite"), pod_boundary=pod_boundary)

    result = {
        "arch": arch, "cell": cell, "mesh": mesh_name,
        "mode": ("opt" if opt_bundle else mode),
        "chips": chips, "kind": specs["kind"],
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem_info,
        "cost_analysis_raw": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "collectives": {k: v for k, v in
                        roof.hlo_parse.analyze(
                            hlo, pod_boundary=pod_boundary).coll.items()},
        "hlo_flops": rep.hlo_flops, "hlo_traffic_bytes": rep.hlo_bytes,
        "roofline": {
            "compute_s": rep.compute_s, "memory_s": rep.memory_s,
            "collective_s": rep.collective_s, "dominant": rep.dominant,
            "useful_flop_ratio": rep.useful_flop_ratio,
            "roofline_fraction": rep.roofline_fraction,
            "model_flops_per_chip": rep.model_flops,
        },
        "ok": True,
    }
    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        suffix = "__opt" if opt_bundle else (f"__{mode}" if mode != "auto" else "")
        out = OUT_DIR / f"{arch}__{cell}__{mesh_name}{suffix}.json"
        out.write_text(json.dumps(result, indent=1))
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="auto", choices=["auto", "explicit"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="apply the per-arch hillclimb optimization bundle")
    args = ap.parse_args()

    if args.list:
        for a, c in configs.all_cells():
            print(f"{a:24s} {c}")
        return

    cells = configs.all_cells() if args.all else [(args.arch, args.cell)]
    failures = []
    for arch, cell in cells:
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        suffix = f"__{args.mode}" if args.mode != "auto" else ""
        out = OUT_DIR / f"{arch}__{cell}__{mesh_name}{suffix}.json"
        if args.skip_existing and out.exists():
            print(f"[skip] {arch} {cell} {mesh_name}")
            continue
        try:
            r = run_cell(arch, cell, multi_pod=args.multi_pod, mode=args.mode,
                         opt_bundle=args.opt)
            rf = r["roofline"]
            print(f"[ok] {arch:24s} {cell:12s} {mesh_name:8s} "
                  f"compile={r['compile_s']:.1f}s "
                  f"dominant={rf['dominant']:10s} "
                  f"frac={rf['roofline_fraction']:.2f}")
        except Exception as e:
            failures.append((arch, cell, str(e)))
            print(f"[FAIL] {arch} {cell}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed: "
                         + ", ".join(f"{a}/{c}" for a, c, _ in failures))


if __name__ == "__main__":
    main()
