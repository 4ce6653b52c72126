"""Explicit bucketed fused prefill (the PR's plan-path half).

A fused prefill micro-step pushes a whole prompt *chunk* per slot
through :func:`repro.distributed.step.make_prefill_sched_step` instead
of one token — a differently-shaped XLA program whose collectives
replay the engine's sequence-bucketed plan families. The contract is
the same as every explicit-path PR before it: the optimization must be
invisible in the tokens. Here that means a fused-prefill scheduler run
emits, for every request, the exact stream the token-by-token (PR 9)
scheduler produces — across the decode-capable config zoo (dense with
qk-norm, MoE with windowed attention, hybrid attention+SSM), at TP in
{2, 4}, with and without the int8 KV cache, and across a ring wrap
(prompt longer than the smallest layer kv window).

Plan accounting rides along: with `ServeConfig.prefill_seq_buckets`
set, fused micro-steps replay the init-compiled ladder — communicator
compile counters stay flat across sequence buckets — and the
scheduler's no-stall invariant (decode slots emit one token on every
tick, no matter what is prefilling next to them) survives fusion.
"""
import dataclasses

import numpy as np
import jax
import pytest
from jax.sharding import Mesh

from benchmarks import loadgen
from repro import configs
from repro.core.comm import BucketedPlan
from repro.distributed import sharding as shd
from repro.distributed import step as step_mod
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request, Scheduler

BATCH = 4


def _mesh(shape, names):
    devs = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def _engine(arch, tp, *, max_kv=64, kv_quant=False, seq_buckets=None,
            mode="explicit"):
    cfg = configs.reduced(configs.get_config(arch))
    mesh = _mesh((1, tp), ("data", "model"))
    params, _ = step_mod.init_sharded(cfg, mesh, shd.MeshAxes(),
                                      jax.random.key(0))
    return Engine(cfg, params, mesh,
                  ServeConfig(batch=BATCH, max_kv=max_kv, mode=mode,
                              kv_quant=kv_quant,
                              prefill_seq_buckets=seq_buckets), mode=mode)


def _trace(vocab, *, seed=0, n=6, max_prompt=9, rid0=0):
    """Mixed traffic: prompt lengths from 1 (pure decode from the first
    combined step) up past the chunk size, every third request
    temperature-sampled, all arriving at t=0 so prefill contention is
    maximal."""
    rng = np.random.default_rng(seed)
    trace = []
    for i in range(n):
        plen = [1, 2, max_prompt, 5, 3, max_prompt - 1][i % 6]
        trace.append(Request(
            rid=rid0 + i,
            prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 5)),
            temperature=0.8 if i % 3 == 2 else 0.0, seed=i))
    return trace


def _serve(eng, trace, *, fused, **kw):
    sched = Scheduler(eng, fused_prefill=fused, **kw)
    for r in trace:
        sched.submit(r)
    sched.run_until_drained(step_s=0.05)
    return sched


# ---------------------------------------------------------------------------
# the acceptance contract: fused == token-by-token, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch,tp,kv_quant", [
    ("qwen3-1.7b", 2, False),
    ("qwen3-1.7b", 4, False),
    ("qwen3-1.7b", 2, True),         # int8 KV composes with fusion
    ("mixtral-8x22b", 2, False),     # MoE + windowed attention
    ("hymba-1.5b", 2, False),        # hybrid attention+SSM
])
def test_fused_prefill_bit_identical_to_token_path(arch, tp, kv_quant):
    """Same engine, same trace, two schedulers: chunked fused prefill
    vs. the PR 9 token-by-token micro-steps. Every stream identical."""
    eng = _engine(arch, tp, kv_quant=kv_quant)
    vocab = eng.cfg.vocab
    fused = _serve(eng, _trace(vocab), fused=True)
    assert fused.fused_prefill        # family supported, no silent gate
    cold = _serve(eng, _trace(vocab, rid0=100), fused=False)
    for i in range(6):
        assert fused.streams[i] == cold.streams[100 + i], \
            f"rid {i} diverged under fused prefill"
    # fused really ran chunks: bucket counters saw a seq bucket > 1
    grid = fused._prefill_bucket_steps
    assert any(s > 1 for _, s in grid), grid


def test_fused_prefill_exact_across_ring_wrap():
    """Prompts longer than the smallest layer kv window: the chunk
    length is ring-capped (a fused write may never wrap within one
    micro-step), then the tail walks token-by-token — still bit-equal
    to the plain path."""
    eng = _engine("mixtral-8x22b", 2, max_kv=8)
    vocab = eng.cfg.vocab
    rng = np.random.default_rng(3)
    mk = [Request(rid=r, prompt=rng.integers(0, vocab, 12).astype(np.int32),
                  max_new_tokens=3, temperature=0.0, seed=r)
          for r in range(2)]
    fused = _serve(eng, mk, fused=True)
    cold = _serve(eng, [dataclasses.replace(r, rid=r.rid + 10) for r in mk],
                  fused=False)
    for r in mk:
        assert fused.streams[r.rid] == cold.streams[r.rid + 10]


# ---------------------------------------------------------------------------
# plan accounting: shared seq-bucket ladder, compile counters flat
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def bucketed_eng():
    return _engine("qwen3-1.7b", 2, seq_buckets=(4, 8))


def test_seq_buckets_extend_the_allreduce_ladder(bucketed_eng):
    """compile_decode_plans(seq_buckets=...) merges {batch*s} rows into
    the layer-AllReduce ladder — the fused S-token micro-step reduces
    batch*S rows through the same frozen family decode uses."""
    ar = bucketed_eng.decode_plans["layer_allreduce"]
    assert isinstance(ar, BucketedPlan)
    for s in (4, 8):
        assert BATCH * s in ar.buckets
    # the decode slot ladder is still in there untouched
    for b in step_mod.slot_buckets(BATCH):
        assert b in ar.buckets


def test_fused_prefill_replays_not_recompiles(bucketed_eng):
    """With the ladder shipped in the engine's plan set, serving mixed
    prompt lengths through the fused path costs ZERO new communicator
    compiles — every micro-step, at every (slot, seq) bucket, is pure
    replay — and the dispatch counters land on configured buckets."""
    compiles0 = bucketed_eng.comm.stats["compiles"]
    sched = _serve(bucketed_eng, _trace(bucketed_eng.cfg.vocab, rid0=200),
                   fused=True)
    assert bucketed_eng.comm.stats["compiles"] == compiles0
    assert sched._seq_buckets == (4, 8)
    for (b, s), n in sched._prefill_bucket_steps.items():
        assert s in (4, 8) and n > 0
        assert b in step_mod.slot_buckets(BATCH)
    rep = sched.plan_report()["scheduler"]
    assert rep["fused_prefill"] and rep["seq_buckets"] == [4, 8]
    assert sum(rep["prefill_bucket_steps"].values()) > 0


def test_fused_prefill_never_stalls_decode(bucketed_eng):
    """The PR 9 no-stall invariant survives fusion: while a long
    prompt chews through fused chunk micro-steps, a co-resident decode
    request still emits exactly one token on every tick."""
    sched = Scheduler(bucketed_eng, max_slots=2, prefill_chunk=3,
                      fused_prefill=True)
    sched.submit(Request(rid=301, prompt=np.asarray([7], np.int32),
                         max_new_tokens=8))
    sched.submit(Request(rid=300, prompt=np.arange(1, 10, dtype=np.int32),
                         max_new_tokens=3))
    infos = []
    while sched.outstanding():
        infos.append(sched.tick())
        sched.advance(1.0)
    live = [i for i in infos if any(e.rid == 301 and e.done
                                    for e in i.emissions)]
    first_done = infos.index(live[0])
    for info in infos[:first_done + 1]:
        assert any(e.rid == 301 for e in info.emissions), \
            "decode request stalled behind a fused prefill"
        assert info.micro_steps <= sched.prefill_chunk - 1
    assert len(sched.streams[301]) == 8


# ---------------------------------------------------------------------------
# gating: unsupported families and unusable ladders fail the right way
# ---------------------------------------------------------------------------
def test_fused_prefill_gated_off_for_recurrent_family():
    """rwkv6's recurrent state is not chunk-steppable — requesting
    fusion silently keeps the token-by-token path (the documented
    fallback), and serving still works."""
    cfg = configs.reduced(configs.get_config("rwkv6-7b"))
    mesh = _mesh((1, 1), ("data", "model"))
    params, _ = step_mod.init_sharded(cfg, mesh, shd.MeshAxes(),
                                      jax.random.key(0))
    eng = Engine(cfg, params, mesh,
                 ServeConfig(batch=2, max_kv=16, mode="auto"), mode="auto")
    sched = Scheduler(eng, fused_prefill=True)
    assert not sched.fused_prefill
    sched.submit(Request(rid=0, prompt=np.asarray([3, 1, 4], np.int32),
                         max_new_tokens=2))
    sched.run_until_drained(step_s=0.05)
    assert len(sched.streams[0]) == 2


def test_fused_prefill_rejects_unusable_seq_buckets():
    """Every configured bucket above the smallest layer kv window is
    unusable (a fused write would wrap the ring) — an empty usable
    ladder with fusion requested is a loud config error."""
    eng = _engine("mixtral-8x22b", 2, max_kv=8)      # min_kv = 8
    scfg = dataclasses.replace(eng.scfg, prefill_seq_buckets=(16, 32))
    eng2 = Engine(eng.cfg, eng.params, eng.mesh, scfg, mode="auto")
    with pytest.raises(ValueError, match="no usable prefill sequence"):
        Scheduler(eng2, fused_prefill=True)


def test_step_rope_table_is_sized_by_the_cache():
    """A global attention layer writes position p to cache slot p, so
    the RoPE table the decode and fused-prefill steps embed as a constant
    covers the cache, not the model's max_seq: at max_seq=131072 the
    table alone would be 16 MiB per program (and a full-width step's
    executable too large for a compile cache)."""
    cfg = dataclasses.replace(configs.reduced(configs.get_config("qwen3-1.7b")),
                              max_seq=131_072)
    mesh = _mesh((1, 1), ("data", "model"))
    params = jax.eval_shape(
        lambda: step_mod.init_sharded(cfg, mesh, shd.MeshAxes(),
                                      jax.random.key(0))[0])
    from repro.models import transformer as tf

    cache = jax.eval_shape(lambda: tf.init_cache(cfg, BATCH, 64))
    vec = jax.ShapeDtypeStruct((BATCH,), np.int32)
    decode, _ = step_mod.make_sched_step(cfg, mesh, shd.MeshAxes(),
                                         batch=BATCH, max_kv=64)
    prefill, _ = step_mod.make_prefill_sched_step(
        cfg, mesh, shd.MeshAxes(), batch=BATCH, seq=8, max_kv=64)
    texts = [
        decode.lower(params, cache, vec, vec,
                     jax.ShapeDtypeStruct((BATCH,), np.bool_)).as_text(),
        prefill.lower(params, cache,
                      jax.ShapeDtypeStruct((BATCH, 8), np.int32), vec,
                      vec).as_text()]
    for text in texts:
        assert "131072x" not in text and len(text) < 4 * 2**20
