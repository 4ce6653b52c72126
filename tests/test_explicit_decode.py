"""Explicit decode hot path (paper §5.2): auto-vs-explicit greedy
bit-equivalence (dense TP, MoE expert parallelism, hybrid attention+SSM
head sharding, and the int8 KV cache), plan replay (compile counters
flat across decode calls), bucketed plan compilation + pad-at-dispatch
correctness for every padding strategy (rows / tiled / blocks),
partial-manual shard_map, and graceful auto fallback (rwkv6 —
the one remaining decode family with no explicit path)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import configs
from repro.core import comm as comm_lib
from repro.core.comm import BucketedPlan, Communicator
from repro.distributed import sharding as shd
from repro.distributed import step as step_mod
from repro.serve.engine import Engine, ServeConfig


def _mesh(shape, names):
    devs = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def _cfg():
    return configs.reduced(configs.get_config("qwen3-1.7b"))


def _params(cfg, mesh):
    return step_mod.init_sharded(cfg, mesh, shd.MeshAxes(),
                                 jax.random.key(0))[0]


# ---------------------------------------------------------------------------
# the acceptance contract: bit-identical greedy decode, pure plan replay
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 4)])
def test_decode_auto_vs_explicit_bit_equal(dp, tp):
    """Greedy tokens identical over >= 16 steps at TP in {2, 4}."""
    mesh = _mesh((dp, tp), ("data", "model"))
    cfg = _cfg()
    params = _params(cfg, mesh)
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, (4, 4)).astype(np.int32)

    toks = {}
    for mode in ("auto", "explicit"):
        eng = Engine(cfg, params, mesh, ServeConfig(batch=4, max_kv=64),
                     mode=mode)
        assert eng.mode == mode          # no silent fallback
        logits = eng.prefill(prompts)
        toks[mode] = eng.decode(logits, num_tokens=16)
    np.testing.assert_array_equal(toks["auto"], toks["explicit"])


def test_explicit_decode_replays_not_recompiles():
    """Compile counters stay flat across decode calls, and the bucketed
    dispatch counters show the full-batch bucket serving the traffic."""
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = _cfg()
    eng = Engine(cfg, _params(cfg, mesh), mesh,
                 ServeConfig(batch=8, max_kv=32), mode="explicit")
    assert eng.mode == "explicit"
    # all plans exist before any request (init-compiled)
    compiles_at_init = eng.comm.stats["compiles"]
    assert compiles_at_init > 0
    prompts = np.random.RandomState(1).randint(
        0, cfg.vocab, (8, 3)).astype(np.int32)
    logits = eng.prefill(prompts)
    eng.decode(logits, num_tokens=2)
    eng.decode(eng.prefill(prompts), num_tokens=2)   # second batch of calls
    assert eng.comm.stats["compiles"] == compiles_at_init
    ar = eng.decode_plans["layer_allreduce"]
    assert isinstance(ar, BucketedPlan)
    # batch=8, dp=2 -> 4 local rows: decode dispatches hit the 4-bucket
    assert ar.hits[ar.bucket_for(4)] > 0


# ---------------------------------------------------------------------------
# explicit-EP MoE decode (the tentpole: bucketed all_to_all on the hot path)
# ---------------------------------------------------------------------------
def _moe_cfg(arch="mixtral-8x22b"):
    return configs.reduced(configs.get_config(arch))


@pytest.mark.parametrize("dp,ep,arch", [
    (1, 2, "mixtral-8x22b"),
    (2, 4, "mixtral-8x22b"),
    (2, 2, "phi3.5-moe-42b-a6.6b"),
    (1, 4, "phi3.5-moe-42b-a6.6b"),
])
def test_moe_decode_auto_vs_explicit_bit_equal(dp, ep, arch):
    """MoE greedy tokens identical over >= 16 steps at EP in {2, 4}:
    the explicit step's per-layer dispatch/combine replay the
    init-compiled capacity-bucketed all_to_all plan."""
    mesh = _mesh((dp, ep), ("data", "model"))
    cfg = _moe_cfg(arch)
    params = _params(cfg, mesh)
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, (4, 4)).astype(np.int32)

    toks = {}
    for mode in ("auto", "explicit"):
        eng = Engine(cfg, params, mesh, ServeConfig(batch=4, max_kv=64),
                     mode=mode)
        assert eng.mode == mode          # no silent fallback
        logits = eng.prefill(prompts)
        toks[mode] = eng.decode(logits, num_tokens=16)
    np.testing.assert_array_equal(toks["auto"], toks["explicit"])


def test_moe_explicit_replays_bucketed_alltoall():
    """Compile counters stay flat across MoE decode calls and the
    moe_alltoall per-bucket hit counters advance (dispatch + combine
    per layer trace)."""
    mesh = _mesh((2, 2), ("data", "model"))
    cfg = _moe_cfg()
    eng = Engine(cfg, _params(cfg, mesh), mesh,
                 ServeConfig(batch=4, max_kv=32), mode="explicit")
    assert eng.mode == "explicit"
    a2a = eng.decode_plans["moe_alltoall"]
    assert isinstance(a2a, BucketedPlan)
    assert a2a.pad_strategy == "blocks"
    # bucket ladder: per-rank rows e_local * capacity(slot bucket),
    # lossless capacity = n_tok * top_k (see ep_capacity)
    e_local = cfg.moe.num_experts // 2
    assert a2a.buckets[-1] == e_local * 2 * cfg.moe.top_k  # b_local=2
    compiles_at_init = eng.comm.stats["compiles"]
    assert compiles_at_init > 0
    prompts = np.random.RandomState(1).randint(
        0, cfg.vocab, (4, 3)).astype(np.int32)
    eng.decode(eng.prefill(prompts), num_tokens=2)
    assert eng.comm.stats["compiles"] == compiles_at_init
    # the decode trace dispatched the full-capacity bucket (twice per
    # layer group: dispatch + combine)
    assert a2a.hits[a2a.buckets[-1]] > 0
    rep = eng.plan_report()
    assert rep["plans"]["moe_alltoall"]["pad_strategy"] == "blocks"
    assert rep["predicted_comm_us_per_token"] > 0


def test_moe_explicit_rejects_without_plan():
    """decode_step with comms but no compiled moe_alltoall plan fails
    loudly rather than silently recompiling inside the trace."""
    from repro.distributed.step import TPDecodeComms
    from repro.models import transformer as tf

    cfg = _moe_cfg()
    comms = TPDecodeComms(cfg, "model", 2, hidden_plan=None, moe_plan=None)
    cache = tf.init_cache(cfg, 2, 8)
    with pytest.raises(NotImplementedError, match="moe_alltoall"):
        tf.decode_step({}, cfg, cache, jnp.zeros((2,), jnp.int32),
                       jnp.int32(0), comms=comms)


# ---------------------------------------------------------------------------
# explicit hybrid (attention+SSM head sharding) and int8-KV decode
# ---------------------------------------------------------------------------
def _hybrid_cfg():
    return configs.reduced(configs.get_config("hymba-1.5b"))


@pytest.mark.parametrize("dp,tp", [(1, 2), (2, 4)])
def test_hybrid_decode_auto_vs_explicit_bit_equal(dp, tp):
    """Hybrid greedy tokens identical over >= 16 steps at TP in {2, 4}:
    the SSM branch runs on each shard's d_inner rows (state
    model-sharded in the cache) and its out-proj partial is completed
    by its own replay of the per-layer AllReduce plan."""
    mesh = _mesh((dp, tp), ("data", "model"))
    cfg = _hybrid_cfg()
    params = _params(cfg, mesh)
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, (4, 4)).astype(np.int32)

    toks = {}
    for mode in ("auto", "explicit"):
        eng = Engine(cfg, params, mesh, ServeConfig(batch=4, max_kv=64),
                     mode=mode)
        assert eng.mode == mode          # no silent fallback
        logits = eng.prefill(prompts)
        toks[mode] = eng.decode(logits, num_tokens=16)
    np.testing.assert_array_equal(toks["auto"], toks["explicit"])


def test_hybrid_explicit_replays_not_recompiles():
    """Hybrid decode stays pure plan replay: compile counters flat, the
    layer AllReduce serving three partials per layer (attention, SSM,
    MLP) all through the same bucketed plan family."""
    mesh = _mesh((2, 2), ("data", "model"))
    cfg = _hybrid_cfg()
    eng = Engine(cfg, _params(cfg, mesh), mesh,
                 ServeConfig(batch=4, max_kv=32), mode="explicit")
    assert eng.mode == "explicit"
    compiles_at_init = eng.comm.stats["compiles"]
    assert compiles_at_init > 0
    prompts = np.random.RandomState(1).randint(
        0, cfg.vocab, (4, 3)).astype(np.int32)
    eng.decode(eng.prefill(prompts), num_tokens=2)
    assert eng.comm.stats["compiles"] == compiles_at_init
    ar = eng.decode_plans["layer_allreduce"]
    assert isinstance(ar, BucketedPlan)
    assert ar.hits[ar.bucket_for(2)] > 0         # batch=4, dp=2 -> 2 local
    # hybrid accounting: 3 AllReduces per layer in the predicted cost
    rep = eng.plan_report()
    assert rep["predicted_comm_us_per_token"] > 0


def test_hybrid_explicit_cache_keeps_ssm_model_sharded():
    """The explicit cache contract: KV entries whole along 'model', the
    SSM state still sharded on it (each rank carries its d_inner rows)."""
    mesh = _mesh((2, 2), ("data", "model"))
    cfg = _hybrid_cfg()
    cspecs = shd.explicit_decode_cache_pspecs(
        cfg, mesh, shd.MeshAxes(), batch=4, kv_lens=[16])

    def _axes(sp):
        out = []
        for e in tuple(sp):
            if isinstance(e, (tuple, list)):
                out += list(e)
            elif e is not None:
                out.append(e)
        return out

    for sp in jax.tree.leaves(cspecs["k"] + cspecs["v"],
                              is_leaf=lambda x: isinstance(x, P)):
        assert "model" not in _axes(sp)
    for sp in cspecs["ssm"]:
        assert "model" in _axes(sp)


@pytest.mark.parametrize("dp,tp,arch", [
    (1, 2, "qwen3-1.7b"),
    (2, 4, "qwen3-1.7b"),
    (1, 2, "hymba-1.5b"),        # int8 KV composes with the hybrid family
    (1, 2, "mixtral-8x22b"),     # ...and with MoE expert parallelism
])
def test_int8_kv_decode_auto_vs_explicit_bit_equal(dp, tp, arch):
    """int8 KV cache on the explicit path: greedy tokens identical to
    auto over >= 16 steps at TP in {2, 4}. Every rank quantizes the
    same new token against the same scale (KV projections replicated),
    and the per-head dequantize gathers its head's scales alongside the
    KV gather — no extra collective, so compile counters stay flat."""
    mesh = _mesh((dp, tp), ("data", "model"))
    cfg = configs.reduced(configs.get_config(arch))
    params = _params(cfg, mesh)
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, (4, 4)).astype(np.int32)

    toks = {}
    for mode in ("auto", "explicit"):
        eng = Engine(cfg, params, mesh,
                     ServeConfig(batch=4, max_kv=64, kv_quant=True),
                     mode=mode)
        assert eng.mode == mode          # no silent fallback
        assert "k_scale" in eng.cache
        compiles0 = eng.comm.stats["compiles"]
        logits = eng.prefill(prompts)
        toks[mode] = eng.decode(logits, num_tokens=16)
        assert eng.comm.stats["compiles"] == compiles0
    np.testing.assert_array_equal(toks["auto"], toks["explicit"])


def test_make_serve_step_explicit_standalone():
    """make_serve_step(mode='explicit') without an engine: builds its
    own communicator and produces finite logits of the right shape."""
    from repro.models import transformer as tf

    mesh = _mesh((2,), ("model",))
    cfg = _cfg()
    params = _params(cfg, mesh)
    step, cspecs = step_mod.make_serve_step(
        cfg, mesh, shd.MeshAxes(), batch=2, max_kv=16, donate=False,
        mode="explicit")
    cache = tf.init_cache(cfg, 2, 16)
    logits, cache = step(params, cache,
                         jnp.zeros((2,), jnp.int32), jnp.int32(0))
    assert logits.shape == (2, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    # the cache contract strips the model axis (kept whole along TP)
    def _axes(sp):
        out = []
        for e in tuple(sp):
            if isinstance(e, (tuple, list)):
                out += list(e)
            elif e is not None:
                out.append(e)
        return out

    for sp in jax.tree.leaves(cspecs, is_leaf=lambda x: isinstance(x, P)):
        assert "model" not in _axes(sp)


# ---------------------------------------------------------------------------
# bucketed plan compilation (continuous batching)
# ---------------------------------------------------------------------------
N = 4


def _bucket_run(mesh4, fn, x):
    return jax.jit(shard_map(fn, mesh=mesh4, in_specs=P("x", None, None),
                             out_specs=P("x", None, None),
                             check_vma=False))(x)


def test_bucketed_allreduce_pads_at_dispatch(mesh4):
    comm = Communicator("x", n=N, backend="xla")
    bp = comm.plan_for("all_reduce", (8, 16), jnp.float32, buckets=(2, 4, 8))
    assert comm.stats["compiles"] == 3          # one per bucket
    for rows in (1, 2, 3, 5, 8):
        x = jnp.asarray(np.random.RandomState(rows).randn(N, rows, 16),
                        jnp.float32)
        y = _bucket_run(mesh4, lambda xs: bp(xs[0])[None], x)
        assert y.shape == (N, rows, 16)
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x.sum(0)),
                                   rtol=1e-5, atol=1e-5)
    # five distinct row counts, three plans: bucketed, not per-shape
    assert comm.stats["compiles"] == 3
    assert bp.hits == {2: 2, 4: 1, 8: 2}


def test_bucketed_allgather_slices_padding_per_block(mesh4):
    comm = Communicator("x", n=N, backend="xla")
    bp = comm.plan_for("all_gather", (4, 8), jnp.float32, buckets=(2, 4))
    for rows in (1, 3, 4):
        x = jnp.asarray(np.random.RandomState(rows).randn(N, rows, 8),
                        jnp.float32)
        y = _bucket_run(mesh4, lambda xs: bp(xs[0])[None], x)
        assert y.shape == (N, N * rows, 8)
        want = np.concatenate([np.asarray(x[j]) for j in range(N)], axis=0)
        np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-6)


def test_bucketed_plan_cache_and_validation(mesh4):
    comm = Communicator("x", n=N, backend="xla")
    bp1 = comm.plan_for("all_reduce", (4, 8), jnp.float32, buckets=(2, 4))
    compiles = comm.stats["compiles"]
    # same key -> same artifact (shared hit counters), zero new compiles
    bp2 = comm.plan_for("all_reduce", (4, 8), jnp.float32, buckets=(2, 4))
    assert bp2 is bp1
    assert comm.stats["compiles"] == compiles
    # an overlapping plain compile hits the underlying plan cache
    comm.compile("all_reduce", (4, 8), jnp.float32)
    assert comm.stats["compiles"] == compiles
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        bp1.bucket_for(5)
    with pytest.raises(ValueError, match="pads per family"):
        comm.plan_for("gather_scatter", (4, 8), jnp.float32, buckets=(4,))
    with pytest.raises(ValueError, match="exceed the largest bucket"):
        comm.plan_for("all_reduce", (8, 8), jnp.float32, buckets=(2, 4))
    # blocks strategy: full payload rows must divide into per-rank blocks
    with pytest.raises(ValueError, match="per-rank blocks"):
        comm.plan_for("all_to_all", (6, 8), jnp.float32, buckets=(2,))
    # buckets=None degrades to a plain ExecutionPlan
    plan = comm.plan_for("all_reduce", (4, 8), jnp.float32)
    assert not isinstance(plan, BucketedPlan)


def test_bucketed_alltoall_pads_per_block(mesh4):
    """The 'blocks' padding strategy (row-redistributing collectives):
    buckets count rows PER per-rank block, each block pads
    independently, and the padding is sliced out of every received
    block — the MoE capacity-bucket case."""
    comm = Communicator("x", n=N, backend="xla")
    bp = comm.plan_for("all_to_all", (N * 8, 16), jnp.float32,
                       buckets=(2, 4, 8))
    assert bp.pad_strategy == "blocks"
    assert comm.stats["compiles"] == 3          # one per capacity bucket
    for rows in (1, 2, 3, 5, 8):
        x = jnp.asarray(np.random.RandomState(rows).randn(N, N * rows, 16),
                        jnp.float32)
        y = _bucket_run(mesh4, lambda xs: bp(xs[0])[None], x)
        assert y.shape == (N, N * rows, 16)
        # device d's received block j == device j's sent block d
        want = np.swapaxes(np.asarray(x).reshape(N, N, rows, 16), 0, 1)
        np.testing.assert_allclose(
            np.asarray(y).reshape(N, N, rows, 16), want, rtol=1e-6)
    assert comm.stats["compiles"] == 3          # bucketed, not per-shape
    assert bp.hits == {2: 2, 4: 1, 8: 2}


def test_bucketed_reduce_scatter_blocks(mesh4):
    """reduce_scatter under the blocks strategy: padded rows reduce to
    zeros and slice off the output tail."""
    comm = Communicator("x", n=N, backend="xla")
    bp = comm.plan_for("reduce_scatter", (N * 4, 8), jnp.float32,
                       buckets=(2, 4))
    for rows in (1, 3, 4):
        x = jnp.asarray(np.random.RandomState(rows).randn(N, N * rows, 8),
                        jnp.float32)
        y = _bucket_run(mesh4, lambda xs: bp(xs[0])[None], x)
        assert y.shape == (N, rows, 8)
        want = np.asarray(x).reshape(N, N, rows, 8).sum(axis=0)
        np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# guard + graceful fallback satellites
# ---------------------------------------------------------------------------
def test_explicit_partial_manual_runs():
    """DP stays auto (GSPMD), only the TP axis is manual."""
    from repro.models import transformer as tf

    mesh = _mesh((2, 2), ("data", "model"))
    cfg = _cfg()
    params = _params(cfg, mesh)
    tokens = jnp.arange(4, dtype=jnp.int32)
    logits = {}
    for mode, kw in (("auto", {}), ("explicit", dict(manual_dp=False))):
        step, _ = step_mod.make_serve_step(
            cfg, mesh, shd.MeshAxes(), batch=4, max_kv=16, donate=False,
            mode=mode, **kw)
        logits[mode], _ = step(params, tf.init_cache(cfg, 4, 16), tokens,
                               jnp.int32(0))
    assert np.isfinite(np.asarray(logits["explicit"])).all()
    np.testing.assert_allclose(np.asarray(logits["explicit"]),
                               np.asarray(logits["auto"]),
                               rtol=1e-5, atol=1e-5)


def test_explicit_falls_back_gracefully_for_unsupported_family():
    """A family the manual body cannot shard (rwkv6's recurrent
    time/channel mix — the one decode family left without an explicit
    path) warns and serves via auto instead of failing."""
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = configs.reduced(configs.get_config("rwkv6-7b"))
    params = _params(cfg, mesh)
    with pytest.warns(UserWarning, match="falling back to auto"):
        eng = Engine(cfg, params, mesh, ServeConfig(batch=4, max_kv=32),
                     mode="explicit")
    assert eng.mode == "auto"
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, (4, 2)).astype(np.int32)
    toks = eng.decode(eng.prefill(prompts), num_tokens=2)
    assert toks.shape == (4, 2)


def test_explicit_supported_predicate():
    cfg = _cfg()
    mesh = _mesh((2, 4), ("data", "model"))
    ok, _ = shd.explicit_decode_supported(cfg, mesh)
    assert ok
    ok, why = shd.explicit_decode_supported(cfg, _mesh((8,), ("data",)))
    assert not ok and "TP" in why
    # MoE: supported when experts divide the axis (expert parallelism)...
    moe = configs.reduced(configs.get_config("mixtral-8x22b"))
    ok, _ = shd.explicit_decode_supported(moe, mesh)
    assert ok
    # ...but TP-in-expert (experts % axis != 0) has no explicit path
    import dataclasses

    from repro.models.config import MoEConfig
    moe6 = dataclasses.replace(moe, moe=MoEConfig(num_experts=6, top_k=2))
    ok, why = shd.explicit_decode_supported(moe6, mesh)
    assert not ok and "experts" in why
    # hybrid: supported when heads, d_ff, AND the SSM inner dim divide
    hyb = configs.reduced(configs.get_config("hymba-1.5b"))
    ok, _ = shd.explicit_decode_supported(hyb, mesh)
    assert ok
    hyb_odd = dataclasses.replace(hyb, d_model=130)   # 130 % 4 != 0
    ok, why = shd.explicit_decode_supported(hyb_odd, mesh)
    assert not ok and "SSM" in why
    # rwkv6 stays auto-only — no family-wide explicit path remains
    # unsupported besides the recurrent ones
    rwk = configs.reduced(configs.get_config("rwkv6-7b"))
    ok, why = shd.explicit_decode_supported(rwk, mesh)
    assert not ok and "family" in why
