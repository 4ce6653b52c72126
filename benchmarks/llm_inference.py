"""Paper Fig. 10 analogue: end-to-end LLM decode speedup from swapping
the AllReduce implementation (llama2-70b, TP=8).

Method (no TPU in this container): the decode step's communication is
counted exactly — llama2-70b TP=8 runs 2 AllReduces per layer × 80
layers on (batch, 1, 8192) bf16 activations. We price each AllReduce
under the NCCL-role baseline vs. the MSCCL++ selector pick using the
α-β link model (calibrated to the paper's own measured latencies:
MSCCL++ cuts the 1KB AllReduce from 9.5µs to 5.0µs — we reproduce
that ratio structurally via the removed sync rounds), and combine with
the roofline compute+memory time of the decode step per batch config.

Output mirrors Fig. 10's bsz/seqlen grid with predicted decode speedup.

``decode_auto_vs_explicit`` complements the analytic grid with a REAL
(CPU-emulated) measurement: the same tiny model decoded through the
auto (GSPMD psum) step and the explicit plan-replay step
(``make_serve_step(mode="explicit")``), wall-clocked per token and
checked for bit-identical greedy output. Emitted into
``BENCH_collectives.json`` by ``run.py --json``; CPU wall time is
structure only, not TPU time. ``explicit_decode_smoke`` is the
2-device variant ``scripts/check.sh --smoke`` runs per PR.

``moe_decode_auto_vs_explicit`` is the MoE analogue: a tiny
expert-parallel model decoded both ways, the explicit path replaying
the capacity-bucketed dispatch/combine all_to_all plan per layer
(``decode_plans["moe_alltoall"]``) — the paper's §2.1 MoE collective
on the §5.2 hot path. ``moe_decode_smoke`` is its 2-device smoke.

``hybrid_decode_auto_vs_explicit`` covers the hybrid (attention+SSM)
family: the SSM branch runs per-shard on its d_inner rows and its
out-proj partial replays the same per-layer AllReduce plan as the
attention/MLP partials (3 replays per layer). ``hybrid_decode_smoke``
is its 2-device smoke. ``int8kv_decode_auto_vs_explicit`` is the int8
KV cache point: dense decode with a quantized cache both ways — the
explicit path quantizes/dequantizes against the TP-replicated scale
entries, so the plan set (and the compile counters) are identical to
the fp point.
"""
from __future__ import annotations

import time

from repro import configs
from repro.core import selector as sel
from repro.roofline.analysis import hardware_for

TP = 8
# paper Fig. 10 batch configurations
GRID = [(8, 1024), (16, 1024), (32, 1024), (8, 4096), (16, 4096), (32, 4096)]

# NCCL-role baseline: ring algorithm at every size + fixed stack
# overhead per call (the paper's §5.1 observation: NCCL's small-message
# latency floor is ~2x MSCCL++'s measured 5.0µs at 1KB)
_NCCL_OVERHEAD_US = 4.5


def decode_comm_us(cfg, batch: int, backend: str) -> float:
    """Per-token communication time: 2 AllReduce/layer over the TP=8
    activations (attention out-proj + MLP down-proj)."""
    nbytes = batch * cfg.d_model * 2  # bf16 activations, one token
    if backend == "nccl":
        per = sel.estimate_us("allreduce_ring", TP, nbytes) + _NCCL_OVERHEAD_US
    else:
        algo = sel.choose("all_reduce", n=TP, nbytes=nbytes)
        per = sel.estimate_us(algo, TP, nbytes)
    return 2 * cfg.n_layers * per


def decode_compute_us(cfg, batch: int, seqlen: int, hw) -> float:
    """Roofline decode step time on 8 chips of peak table entry ``hw``:
    weight streaming dominates (memory-bound at small batch) + KV
    reads."""
    param_bytes = cfg.param_count() * 2 / TP
    kv_bytes = (cfg.n_layers * batch * cfg.n_kv_heads * seqlen
                * cfg.hd * 2 * 2) / TP
    mem_s = (param_bytes + kv_bytes) / hw.hbm_bw
    flops = 2 * cfg.param_count() * batch / TP
    comp_s = flops / hw.peak_flops
    return max(mem_s, comp_s) * 1e6


def _bench_cfg():
    from repro.models.config import ModelConfig

    return ModelConfig(
        name="decode-bench", family="dense",
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, vocab=512, max_seq=256, dtype="float32")


def _bench_moe_cfg():
    """mixtral-shaped tiny MoE: 4 experts top-2, experts divisible by
    the EP axis sizes the bench/smoke meshes use (2, 4)."""
    from repro.models.config import ModelConfig, MoEConfig

    return ModelConfig(
        name="moe-decode-bench", family="moe",
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, vocab=512, max_seq=256, dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2))


def _bench_hybrid_cfg():
    """hymba-shaped tiny hybrid: parallel attention+SSM heads, sliding
    window — the SSM inner dim (= d_model) divides the TP axis sizes
    the bench/smoke meshes use (2, 4)."""
    from repro.models.config import ModelConfig, SSMConfig

    return ModelConfig(
        name="hybrid-decode-bench", family="hybrid", window=64,
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, vocab=512, max_seq=256, dtype="float32",
        ssm=SSMConfig(state_dim=16))


def _run_engine(cfg, params, mesh, mode, *, batch, prompts, tokens,
                kv_quant=False):
    from repro.serve.engine import Engine, ServeConfig

    eng = Engine(cfg, params, mesh,
                 ServeConfig(batch=batch, max_kv=128, mode=mode,
                             kv_quant=kv_quant))
    assert eng.mode == mode, f"requested {mode!r}, engine fell back"
    logits = eng.prefill(prompts)
    compiles0 = eng.comm.stats["compiles"]
    t0 = time.perf_counter()
    toks = eng.decode(logits, num_tokens=tokens)
    dt = time.perf_counter() - t0
    assert eng.comm.stats["compiles"] == compiles0, \
        "decode recompiled plans instead of replaying"
    return toks, dt / tokens * 1e3, eng


def _compare_modes(cfg, *, mesh_shape, axis_names, batch, prompt_len,
                   seed, tokens, kv_quant=False):
    """Shared scaffolding of every auto-vs-explicit comparison: build
    the mesh, init params, decode the same prompts through both engine
    modes. Returns (toks_auto, toks_explicit, ms_auto, ms_explicit,
    explicit_engine)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.distributed import sharding as shd
    from repro.distributed.step import init_sharded

    n = int(np.prod(mesh_shape))
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(mesh_shape),
                axis_names)
    params, _ = init_sharded(cfg, mesh, shd.MeshAxes(), jax.random.key(0))
    prompts = np.random.RandomState(seed).randint(
        0, cfg.vocab, (batch, prompt_len)).astype(np.int32)
    toks_a, ms_a, _ = _run_engine(cfg, params, mesh, "auto",
                                  batch=batch, prompts=prompts,
                                  tokens=tokens, kv_quant=kv_quant)
    toks_e, ms_e, eng = _run_engine(cfg, params, mesh, "explicit",
                                    batch=batch, prompts=prompts,
                                    tokens=tokens, kv_quant=kv_quant)
    return toks_a, toks_e, ms_a, ms_e, eng


def decode_auto_vs_explicit(points=None, *, batch=4, tokens=16,
                            dp=2, tp=4) -> dict:
    """Measured auto (GSPMD psum) vs explicit (compiled-plan replay)
    decode on the same params: ms/token both ways + bit-equality of the
    greedy output. The §5.2 comparison the ROADMAP asks to record."""
    cfg = _bench_cfg()
    toks_a, toks_e, ms_a, ms_e, eng = _compare_modes(
        cfg, mesh_shape=(dp, tp), axis_names=("data", "model"),
        batch=batch, prompt_len=4, seed=0, tokens=tokens)
    point = dict(
        bench="decode_auto_vs_explicit", model=cfg.name, dp=dp, tp=tp,
        batch=batch, tokens=tokens, n_layers=cfg.n_layers,
        backend=eng.comm.backend or "xla",
        wall_ms_per_token_auto=round(ms_a, 2),
        wall_ms_per_token_explicit=round(ms_e, 2),
        speedup_explicit=round(ms_a / ms_e, 3),
        tokens_bit_identical=bool((toks_a == toks_e).all()),
        predicted_comm_us_per_token=eng.plan_report()[
            "predicted_comm_us_per_token"],
    )
    if points is not None:
        points.append(point)
    return point


def moe_decode_auto_vs_explicit(points=None, *, batch=4, tokens=16,
                                dp=2, ep=4) -> dict:
    """Measured auto (GSPMD) vs explicit (plan-replay) decode for the
    MoE family: the explicit step runs expert-parallel dispatch/combine
    through the init-compiled capacity-bucketed all_to_all plan every
    layer — the last big collective family the explicit path covers
    (ROADMAP). Records ms/token both ways, bit-equality of the greedy
    output, and the per-bucket dispatch hits of the moe_alltoall plan."""
    cfg = _bench_moe_cfg()
    toks_a, toks_e, ms_a, ms_e, eng = _compare_modes(
        cfg, mesh_shape=(dp, ep), axis_names=("data", "model"),
        batch=batch, prompt_len=4, seed=0, tokens=tokens)
    rep = eng.plan_report()
    point = dict(
        bench="moe_decode_auto_vs_explicit", model=cfg.name, dp=dp, ep=ep,
        batch=batch, tokens=tokens, n_layers=cfg.n_layers,
        experts=cfg.moe.num_experts, top_k=cfg.moe.top_k,
        backend=eng.comm.backend or "xla",
        wall_ms_per_token_auto=round(ms_a, 2),
        wall_ms_per_token_explicit=round(ms_e, 2),
        speedup_explicit=round(ms_a / ms_e, 3),
        tokens_bit_identical=bool((toks_a == toks_e).all()),
        moe_alltoall_buckets=rep["plans"]["moe_alltoall"]["buckets"],
        moe_alltoall_hits=rep["plans"]["moe_alltoall"]["hits"],
        predicted_comm_us_per_token=rep["predicted_comm_us_per_token"],
    )
    if points is not None:
        points.append(point)
    return point


def moe_decode_smoke(tokens=4) -> dict:
    """Seconds-fast 2-device explicit-MoE smoke (``scripts/check.sh
    --smoke``): EP=2 model-only mesh, asserts the explicit step
    generates through the bucketed all_to_all plan (compile counters
    flat, per-bucket hits advancing) and matches the auto path's greedy
    tokens bit-for-bit."""
    cfg = _bench_moe_cfg()
    toks_a, toks_e, _, ms_e, eng = _compare_modes(
        cfg, mesh_shape=(2,), axis_names=("model",),
        batch=2, prompt_len=3, seed=1, tokens=tokens)
    assert (toks_a == toks_e).all(), "explicit MoE decode diverged from auto"
    rep = eng.plan_report()
    a2a = rep["plans"]["moe_alltoall"]
    assert sum(a2a["hits"].values()) > 0, "moe_alltoall plan never dispatched"
    return dict(ep=2, tokens=tokens, ms_per_token=round(ms_e, 2),
                tokens_bit_identical=True,
                buckets=a2a["buckets"], hits=a2a["hits"],
                predicted_comm_us_per_token=rep[
                    "predicted_comm_us_per_token"])


def hybrid_decode_auto_vs_explicit(points=None, *, batch=4, tokens=16,
                                   dp=2, tp=4) -> dict:
    """Measured auto (GSPMD) vs explicit (plan-replay) decode for the
    hybrid attention+SSM family: the explicit step shards the SSM
    inner dim over TP (state model-sharded in the cache) and completes
    the SSM out-proj partial with its own replay of the per-layer
    AllReduce plan — 3 replays per layer instead of the dense 2.
    Closes the last ROADMAP family gap alongside int8 KV. Records
    ms/token both ways and bit-equality of the greedy output."""
    cfg = _bench_hybrid_cfg()
    toks_a, toks_e, ms_a, ms_e, eng = _compare_modes(
        cfg, mesh_shape=(dp, tp), axis_names=("data", "model"),
        batch=batch, prompt_len=4, seed=0, tokens=tokens)
    rep = eng.plan_report()
    point = dict(
        bench="hybrid_decode_auto_vs_explicit", model=cfg.name, dp=dp,
        tp=tp, batch=batch, tokens=tokens, n_layers=cfg.n_layers,
        ssm_state_dim=cfg.ssm.state_dim, window=cfg.window,
        backend=eng.comm.backend or "xla",
        wall_ms_per_token_auto=round(ms_a, 2),
        wall_ms_per_token_explicit=round(ms_e, 2),
        speedup_explicit=round(ms_a / ms_e, 3),
        tokens_bit_identical=bool((toks_a == toks_e).all()),
        allreduce_replays_per_layer=3,
        predicted_comm_us_per_token=rep["predicted_comm_us_per_token"],
    )
    if points is not None:
        points.append(point)
    return point


def int8kv_decode_auto_vs_explicit(points=None, *, batch=4, tokens=16,
                                   dp=2, tp=4) -> dict:
    """The int8 KV cache on the explicit hot path: dense decode with a
    quantized cache through both engine modes. The explicit step
    quantizes every new token against the TP-replicated scale entries
    and dequantizes per gathered head — the plan set is identical to
    the fp point (no scale collective), which the flat compile
    counters inside ``_run_engine`` assert."""
    cfg = _bench_cfg()
    toks_a, toks_e, ms_a, ms_e, eng = _compare_modes(
        cfg, mesh_shape=(dp, tp), axis_names=("data", "model"),
        batch=batch, prompt_len=4, seed=0, tokens=tokens, kv_quant=True)
    point = dict(
        bench="int8kv_decode_auto_vs_explicit", model=cfg.name, dp=dp,
        tp=tp, batch=batch, tokens=tokens, n_layers=cfg.n_layers,
        cache_dtype="int8",
        backend=eng.comm.backend or "xla",
        wall_ms_per_token_auto=round(ms_a, 2),
        wall_ms_per_token_explicit=round(ms_e, 2),
        speedup_explicit=round(ms_a / ms_e, 3),
        tokens_bit_identical=bool((toks_a == toks_e).all()),
        predicted_comm_us_per_token=eng.plan_report()[
            "predicted_comm_us_per_token"],
    )
    if points is not None:
        points.append(point)
    return point


def hybrid_decode_smoke(tokens=4) -> dict:
    """Seconds-fast 2-device explicit-hybrid smoke (``scripts/check.sh
    --smoke``): TP=2 model-only mesh, asserts the explicit step decodes
    the attention+SSM family through plan replay (compile counters
    flat inside ``_run_engine``) bit-identically to auto."""
    cfg = _bench_hybrid_cfg()
    toks_a, toks_e, _, ms_e, eng = _compare_modes(
        cfg, mesh_shape=(2,), axis_names=("model",),
        batch=2, prompt_len=3, seed=1, tokens=tokens)
    assert (toks_a == toks_e).all(), \
        "explicit hybrid decode diverged from auto"
    rep = eng.plan_report()
    return dict(tp=2, tokens=tokens, ms_per_token=round(ms_e, 2),
                tokens_bit_identical=True,
                predicted_comm_us_per_token=rep[
                    "predicted_comm_us_per_token"],
                hits=rep["plans"]["layer_allreduce"]["hits"])


def explicit_decode_smoke(tokens=4) -> dict:
    """Seconds-fast 2-device explicit-decode smoke
    (``scripts/check.sh --smoke``): TP=2 model-only mesh, asserts the
    explicit step generates, replays (compile counters flat), and
    matches the auto path's greedy tokens bit-for-bit."""
    cfg = _bench_cfg()
    toks_a, toks_e, _, ms_e, eng = _compare_modes(
        cfg, mesh_shape=(2,), axis_names=("model",),
        batch=2, prompt_len=3, seed=1, tokens=tokens)
    assert (toks_a == toks_e).all(), "explicit decode diverged from auto"
    rep = eng.plan_report()
    return dict(tp=2, tokens=tokens, ms_per_token=round(ms_e, 2),
                tokens_bit_identical=True,
                predicted_comm_us_per_token=rep[
                    "predicted_comm_us_per_token"],
                hits=rep["plans"]["layer_allreduce"]["hits"])


def main(rows=None):
    rows = rows if rows is not None else []
    cfg = configs.get_config("llama2-70b")
    # an analytic model of a TPU v5e deployment, computed on any host
    hw = hardware_for("TPU v5 lite")
    for bsz, seqlen in GRID:
        comp = decode_compute_us(cfg, bsz, seqlen, hw)
        nccl = decode_comm_us(cfg, bsz, "nccl")
        ours = decode_comm_us(cfg, bsz, "mscclpp")
        t_base = comp + nccl
        t_ours = comp + ours
        speedup = t_base / t_ours
        rows.append(("decode_llama2_70b", f"bsz{bsz}_seq{seqlen}",
                     round(t_base, 1), round(t_ours, 1),
                     f"{speedup:.3f}x",
                     f"comm {nccl:.0f}->{ours:.0f}us"))
    # prefill: compute-bound, gain should shrink (paper: <=6%)
    for bsz, seqlen in GRID[:3]:
        flops = 2 * cfg.param_count() * bsz * seqlen / TP
        comp = flops / hw.peak_flops * 1e6
        nbytes = bsz * seqlen * cfg.d_model * 2
        nccl = 2 * cfg.n_layers * (sel.estimate_us("allreduce_ring", TP, nbytes)
                                   + _NCCL_OVERHEAD_US)
        algo = sel.choose("all_reduce", n=TP, nbytes=nbytes)
        ours = 2 * cfg.n_layers * sel.estimate_us(algo, TP, nbytes)
        speedup = (comp + nccl) / (comp + ours)
        rows.append(("prefill_llama2_70b", f"bsz{bsz}_seq{seqlen}",
                     round(comp + nccl, 1), round(comp + ours, 1),
                     f"{speedup:.3f}x", ""))
    # measured (CPU-emulated) auto-vs-explicit decode on the real engine
    p = decode_auto_vs_explicit()
    rows.append(("decode_auto_vs_explicit",
                 f"dp{p['dp']}_tp{p['tp']}_bsz{p['batch']}",
                 p["wall_ms_per_token_auto"],
                 p["wall_ms_per_token_explicit"],
                 f"{p['speedup_explicit']}x",
                 "bit-identical" if p["tokens_bit_identical"]
                 else "MISMATCH"))
    # ... and the MoE expert-parallel analogue (bucketed all_to_all plans)
    m = moe_decode_auto_vs_explicit()
    rows.append(("moe_decode_auto_vs_explicit",
                 f"dp{m['dp']}_ep{m['ep']}_bsz{m['batch']}",
                 m["wall_ms_per_token_auto"],
                 m["wall_ms_per_token_explicit"],
                 f"{m['speedup_explicit']}x",
                 "bit-identical" if m["tokens_bit_identical"]
                 else "MISMATCH"))
    # ... the hybrid attention+SSM family (SSM out-proj on the plan path)
    h = hybrid_decode_auto_vs_explicit()
    rows.append(("hybrid_decode_auto_vs_explicit",
                 f"dp{h['dp']}_tp{h['tp']}_bsz{h['batch']}",
                 h["wall_ms_per_token_auto"],
                 h["wall_ms_per_token_explicit"],
                 f"{h['speedup_explicit']}x",
                 "bit-identical" if h["tokens_bit_identical"]
                 else "MISMATCH"))
    # ... and the int8 KV cache point (quantized cache, same plan set)
    q = int8kv_decode_auto_vs_explicit()
    rows.append(("int8kv_decode_auto_vs_explicit",
                 f"dp{q['dp']}_tp{q['tp']}_bsz{q['batch']}",
                 q["wall_ms_per_token_auto"],
                 q["wall_ms_per_token_explicit"],
                 f"{q['speedup_explicit']}x",
                 "bit-identical" if q["tokens_bit_identical"]
                 else "MISMATCH"))
    return rows
