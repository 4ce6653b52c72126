"""Communicator / ExecutionPlan layer: the compile-once contract,
plan-cache key discrimination, JSON round-trip, tuning-table override,
fitted link constants, and the init-once deployment shape of the serve
engine and train step."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import algorithms as algos
from repro.core import comm as comm_lib
from repro.core import passes
from repro.core import selector as sel
from repro.core.comm import Communicator, ExecutionPlan

N = 8


def _shard_run(mesh, fn, x):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("x", None, None),
                             out_specs=P("x", None, None),
                             check_vma=False))(x)


@pytest.fixture
def counters(monkeypatch):
    """Count every selector / pass-pipeline / executor-build invocation
    that the comm layer performs."""
    counts = {"choose": 0, "optimize": 0, "xla_exec": 0}

    real_choose = sel.choose
    real_optimize = passes.optimize
    real_xla = comm_lib.XlaExecutor

    def counting_choose(*a, **k):
        counts["choose"] += 1
        return real_choose(*a, **k)

    def counting_optimize(*a, **k):
        counts["optimize"] += 1
        return real_optimize(*a, **k)

    class CountingXla(real_xla):
        def __init__(self, *a, **k):
            counts["xla_exec"] += 1
            super().__init__(*a, **k)

    monkeypatch.setattr(sel, "choose", counting_choose)
    monkeypatch.setattr(passes, "optimize", counting_optimize)
    monkeypatch.setattr(comm_lib, "XlaExecutor", CountingXla)
    return counts


# ---------------------------------------------------------------------------
# compile-once: the acceptance contract
# ---------------------------------------------------------------------------
def test_repeated_calls_plan_zero_additional_times(mesh8, counters):
    """Repeated comm.all_reduce with an identical key must run the
    selector, the passes pipeline, and executor construction ZERO
    additional times — including across fresh jit traces."""
    comm = Communicator("x", n=N, backend="xla")
    x = jnp.asarray(np.random.RandomState(0).randn(N, 16, 32), jnp.float32)

    def f(xs):
        return comm.all_reduce(xs[0])[None]

    y1 = _shard_run(mesh8, f, x)
    after_first = dict(counters)
    assert after_first["choose"] == 1
    assert after_first["xla_exec"] == 1
    assert comm.stats == {"compiles": 1, "hits": 0}

    # a second, fresh jit of the same shape re-traces the Python but
    # must be pure plan replay
    y2 = _shard_run(mesh8, f, x)
    assert dict(counters) == after_first
    assert comm.stats == {"compiles": 1, "hits": 1}
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(x.sum(0)),
                               rtol=1e-5, atol=1e-5)


def test_cached_plan_execution_plans_zero_times(mesh8, counters):
    """Executing a prebuilt ExecutionPlan does no planning work at all."""
    comm = Communicator("x", n=N, backend="xla")
    plan = comm.compile("all_reduce", (16, 32), jnp.float32)
    baseline = dict(counters)
    x = jnp.asarray(np.random.RandomState(1).randn(N, 16, 32), jnp.float32)
    y = _shard_run(mesh8, lambda xs: plan(xs[0])[None], x)
    assert dict(counters) == baseline
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x.sum(0)),
                               rtol=1e-5, atol=1e-5)


def test_cache_keys_distinguish_shape_dtype_backend_opt_level():
    comm = Communicator("x", n=N)
    base = comm.compile("all_reduce", (16, 32), jnp.float32, backend="xla")
    assert comm.compile("all_reduce", (16, 32), jnp.float32,
                        backend="xla") is base
    distinct = [
        comm.compile("all_reduce", (32, 32), jnp.float32, backend="xla"),
        comm.compile("all_reduce", (16, 32), jnp.bfloat16, backend="xla"),
        comm.compile("all_reduce", (16, 32), jnp.float32, backend="pallas"),
        comm.compile("all_reduce", (16, 32), jnp.float32, backend="xla",
                     opt_level=0),
    ]
    assert len({id(p) for p in distinct + [base]}) == 5
    assert comm.stats["compiles"] == 5
    assert comm.stats["hits"] == 1


def test_traced_step_compiles_each_distinct_collective_once(mesh8, counters):
    """A traced train-step-like body touching several collectives and
    several shapes plans once per distinct key, not once per call."""
    comm = Communicator("x", n=N, backend="xla")
    x = jnp.asarray(np.random.RandomState(2).randn(N, 16, 32), jnp.float32)

    def step(xs):
        a = comm.all_reduce(xs[0])          # key 1
        b = comm.all_reduce(xs[0])          # same key
        c = comm.all_gather(xs[0][:2])      # key 2
        d = comm.reduce_scatter(a)          # key 3 (16 rows / 8 chunks)
        return (b + 0 * d.sum() + 0 * c.sum())[None]

    _shard_run(mesh8, step, x)
    assert comm.stats["compiles"] == 3
    assert counters["choose"] == 3
    _shard_run(mesh8, step, x)
    assert comm.stats["compiles"] == 3


# ---------------------------------------------------------------------------
# plan artifact: JSON round-trip, cost card, shape/dtype guards
# ---------------------------------------------------------------------------
def test_plan_json_roundtrip_bitwise(mesh8):
    comm = Communicator("x", n=N, backend="xla")
    # ring at 13 rows exercises the pad metadata (8-chunk input grid)
    plan = comm.compile("all_reduce", (13, 40), jnp.float32,
                        algo="allreduce_ring")
    assert plan.pad == 3
    s = plan.to_json()
    plan2 = ExecutionPlan.from_json(s)
    # the serialized artifact is stable through a round trip...
    assert plan2.to_json() == s
    assert (plan2.algo, plan2.n, plan2.pad, plan2.opt_level) == \
        (plan.algo, plan.n, plan.pad, plan.opt_level)
    assert json.loads(s)["comm_stats"] == plan.comm_stats
    # ...and the reloaded plan executes bit-identically
    x = jnp.asarray(np.random.RandomState(3).randn(N, 13, 40), jnp.float32)
    y1 = _shard_run(mesh8, lambda xs: plan(xs[0])[None], x)
    y2 = _shard_run(mesh8, lambda xs: plan2(xs[0])[None], x)
    assert jnp.array_equal(y1, y2)
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(x.sum(0)),
                               rtol=1e-5, atol=1e-5)


def test_bucketed_plan_json_roundtrip(mesh8):
    """BucketedPlan serializes like ExecutionPlan: per-bucket plans and
    metadata (buckets, padding strategy, hit counters) round-trip, and
    the reloaded family executes bit-identically at every occupancy."""
    from repro.core.comm import BucketedPlan

    comm = Communicator("x", n=N, backend="xla")
    bp = comm.plan_for("all_reduce", (8, 16), jnp.float32, buckets=(2, 4, 8))
    x = jnp.asarray(np.random.RandomState(7).randn(N, 3, 16), jnp.float32)
    y1 = _shard_run(mesh8, lambda xs: bp(xs[0])[None], x)

    s = bp.to_json()
    bp2 = BucketedPlan.from_json(s)
    # stable through a round trip (hit counters included: bp dispatched
    # once above, and the re-serialized copy must carry that state)
    assert bp2.to_json() == s
    assert (bp2.buckets, bp2.pad_strategy) == (bp.buckets, bp.pad_strategy)
    assert bp2.hits == bp.hits
    assert {b: p.algo for b, p in bp2.plans.items()} == \
        {b: p.algo for b, p in bp.plans.items()}
    y2 = _shard_run(mesh8, lambda xs: bp2(xs[0])[None], x)
    assert jnp.array_equal(y1, y2)


def test_bucketed_alltoall_plan_json_roundtrip(mesh8):
    """The new row-redistributing buckets serialize too: an all_to_all
    family under the 'blocks' strategy reloads and replays exactly."""
    from repro.core.comm import BucketedPlan

    comm = Communicator("x", n=N, backend="xla")
    bp = comm.plan_for("all_to_all", (N * 4, 8), jnp.float32, buckets=(2, 4))
    bp2 = BucketedPlan.from_json(bp.to_json())
    assert bp2.pad_strategy == "blocks"
    assert bp2.plans[4].shape == (N * 4, 8)      # full (n*block, cols) shape
    x = jnp.asarray(np.random.RandomState(8).randn(N, N * 3, 8), jnp.float32)
    y1 = _shard_run(mesh8, lambda xs: bp(xs[0])[None], x)
    y2 = _shard_run(mesh8, lambda xs: bp2(xs[0])[None], x)
    assert jnp.array_equal(y1, y2)
    want = np.swapaxes(np.asarray(x).reshape(N, N, 3, 8), 0, 1)
    np.testing.assert_allclose(np.asarray(y1).reshape(N, N, 3, 8), want,
                               rtol=1e-6)


def test_bucketed_plan_json_error_paths():
    """from_json rejects wrong formats, wrong kinds, and truncated
    payloads instead of mis-deserializing."""
    from repro.core.comm import BucketedPlan

    comm = Communicator("x", n=N, backend="xla")
    bp = comm.plan_for("all_reduce", (4, 8), jnp.float32, buckets=(2, 4))
    d = json.loads(bp.to_json())

    bad = dict(d, format=99)
    with pytest.raises(ValueError, match="format"):
        BucketedPlan.from_json(json.dumps(bad))
    # a single-plan payload is not a bucket family (and vice versa)
    single = comm.compile("all_reduce", (4, 8), jnp.float32)
    with pytest.raises(ValueError, match="kind"):
        BucketedPlan.from_json(single.to_json())
    with pytest.raises(ValueError, match="BucketedPlan.from_json"):
        ExecutionPlan.from_json(bp.to_json())
    # missing per-bucket plan
    truncated = dict(d, plans={k: v for k, v in d["plans"].items()
                               if k != "2"})
    with pytest.raises(ValueError, match="missing buckets"):
        BucketedPlan.from_json(json.dumps(truncated))
    # corrupted padding strategy must not silently fall back to 'rows'
    skewed = dict(d, pad_strategy="Blocks")
    with pytest.raises(ValueError, match="pad_strategy"):
        BucketedPlan.from_json(json.dumps(skewed))


def test_plan_shape_dtype_guards():
    comm = Communicator("x", n=N, backend="xla")
    plan = comm.compile("all_reduce", (16, 32), jnp.float32)
    with pytest.raises(ValueError, match="shape"):
        plan(jnp.zeros((8, 32), jnp.float32))
    with pytest.raises(ValueError, match="dtype"):
        plan(jnp.zeros((16, 32), jnp.bfloat16))


def test_plan_rejects_indivisible_rows():
    comm = Communicator("x", n=N, backend="xla")
    with pytest.raises(ValueError, match="not divisible"):
        comm.compile("reduce_scatter", (13, 8), jnp.float32)


def test_o3_fallback_recorded_on_plan():
    """Chunk-split fallback is visible on the artifact: requested O3,
    applied O2 when rows don't divide the split grid."""
    comm = Communicator("x", n=N, backend="xla")
    plan = comm.compile("all_gather", (3, 4), jnp.float32,
                        algo="ring_ag", opt_level=3)
    assert plan.requested_opt_level == 3
    assert plan.opt_level == 2


def test_o3_fallback_reselects_at_applied_level(monkeypatch):
    """When the chunk-split fallback lowers the level, the selector must
    re-rank candidates at the level that actually runs (not keep the
    winner of the O3 cost model)."""
    levels = []
    real = sel.choose

    def spy(*a, **k):
        levels.append(k.get("opt_level"))
        return real(*a, **k)

    monkeypatch.setattr(sel, "choose", spy)
    comm = Communicator("x", n=N, backend="xla")
    # 24 rows: divisible by ring_rs's 8-chunk grid, not the 16-chunk
    # O3 split grid -> fallback to O2 and a second selection at O2
    plan = comm.compile("reduce_scatter", (24, 4096), jnp.float32,
                        opt_level=3)
    assert (plan.requested_opt_level, plan.opt_level) == (3, 2)
    assert levels == [3, 2]


# ---------------------------------------------------------------------------
# tuning: table override + fitted constants
# ---------------------------------------------------------------------------
def test_tuning_table_on_communicator_changes_choice():
    plain = Communicator("x", n=N, backend="xla")
    assert plain.compile("all_reduce", (4, 8),
                         jnp.float32).algo == "allreduce_1pa"
    tuned = Communicator("x", n=N, backend="xla", table=sel.TuningTable(
        entries=[("all_reduce", 1 << 30, "allreduce_ring")]))
    assert tuned.compile("all_reduce", (4, 8),
                         jnp.float32).algo == "allreduce_ring"
    # installing a table invalidates previously cached choices
    plain.set_tuning_table(sel.TuningTable(
        entries=[("all_reduce", 1 << 30, "allreduce_2pa")]))
    assert plain.compile("all_reduce", (4, 8),
                         jnp.float32).algo == "allreduce_2pa"


def test_fit_link_model_recovers_known_constants():
    """A synthetic bench payload generated FROM a known LinkModel fits
    back to (approximately) the same α/β."""
    truth = sel.LinkModel(alpha_us=3.0, beta_GBps=20.0, torus=True)
    points = []
    for algo in ("allreduce_1pa", "allreduce_2pa", "allreduce_ring"):
        for nbytes in (1 << 12, 1 << 16, 1 << 20):
            prog = passes.optimize(algos.REGISTRY[algo](N),
                                   passes.DEFAULT_OPT_LEVEL, N)
            st = prog.comm_stats(N, max(nbytes // prog.chunks[prog.in_buffer],
                                        1))
            wall = truth.time_us(st["comm_rounds"] + st["barriers"],
                                 st["wire_bytes_per_rank"])
            points.append(dict(bench="allreduce", backend="xla", algo=algo,
                               nbytes=nbytes, wall_us=wall))
    fitted = sel.fit_link_model(dict(n=N, opt_default=2, points=points))
    assert fitted.alpha_us == pytest.approx(truth.alpha_us, rel=1e-3)
    assert fitted.beta_GBps == pytest.approx(truth.beta_GBps, rel=1e-3)


def test_fit_link_model_rejects_degenerate_payload():
    """Anti-correlated wall times (bigger message -> faster) cannot be
    explained by alpha-beta; the fit must refuse, not clamp-and-install."""
    points = [dict(bench="allreduce", backend="xla", algo="allreduce_ring",
                   nbytes=nb, wall_us=w)
              for nb, w in [(1 << 12, 1000.0), (1 << 16, 100.0),
                            (1 << 20, 1.0)]]
    with pytest.raises(ValueError, match="degenerate"):
        sel.fit_link_model(dict(n=N, opt_default=2, points=points))


def test_tuning_table_from_bench_prefers_measured_fastest():
    payload = dict(n=N, points=[
        dict(bench="opt_compare", algo="allreduce_1pa", nbytes=1 << 14,
             wall_us_opt=5.0),
        dict(bench="opt_compare", algo="allreduce_2pa", nbytes=1 << 14,
             wall_us_opt=2.0),
        # all_gather is measured per-shard but selected on the gathered
        # message: its bracket must scale by n
        dict(bench="opt_compare", algo="allpairs_ag", nbytes=1 << 14,
             wall_us_opt=4.0),
        dict(bench="opt_compare", algo="ring_ag", nbytes=1 << 14,
             wall_us_opt=3.0),
        # single-candidate size carries no preference -> no entry
        dict(bench="opt_compare", algo="alltoall", nbytes=1 << 14,
             wall_us_opt=1.0),
    ])
    table = sel.TuningTable.from_bench(payload)
    assert sorted(table.entries) == [
        ("all_gather", N << 14, "ring_ag"),
        ("all_reduce", 1 << 14, "allreduce_2pa"),
    ]
    assert table.lookup("all_reduce", 1 << 10) == "allreduce_2pa"
    assert table.lookup("all_gather", N << 14) == "ring_ag"
    assert table.lookup("all_to_all", 1 << 10) is None


def test_api_honors_communicator_link_and_table():
    """A fitted link / table installed on the default communicator must
    flow through the module-level api wrappers (their link default may
    not shadow it)."""
    from repro.core import api

    comm = api.communicator("x")
    saved_link, saved_table = comm.link, comm.table
    try:
        comm.link = sel.LinkModel(alpha_us=500.0, beta_GBps=0.001)
        comm.set_tuning_table(sel.TuningTable(
            entries=[("all_reduce", 1 << 30, "allreduce_2pa")]))
        plan = api.compile_plan("all_reduce", (4, 8), jnp.float32, "x",
                                backend="xla", n=N)
        assert plan.algo == "allreduce_2pa"       # table applied
        assert plan.link.alpha_us == 500.0        # fitted link applied
    finally:
        comm.link = saved_link
        comm.set_tuning_table(saved_table)


# ---------------------------------------------------------------------------
# satellites: algo routing + opt_level threading into selection
# ---------------------------------------------------------------------------
def test_all_to_all_algo_kwarg_routed_and_validated(mesh8):
    from repro.core import api

    x = jnp.asarray(np.random.RandomState(4).randn(N, N * 2, 8), jnp.float32)
    y = _shard_run(mesh8, lambda xs: api.all_to_all(
        xs[0], "x", backend="xla", algo="alltoall")[None], x)
    want = np.swapaxes(np.asarray(x).reshape(N, N, 2, 8), 0, 1)
    np.testing.assert_allclose(np.asarray(y).reshape(N, N, 2, 8), want,
                               rtol=1e-5)
    with pytest.raises(ValueError, match="unknown algorithm"):
        Communicator("x", n=N).compile("all_to_all", (16, 8), jnp.float32,
                                       algo="ring_ag")


def test_opt_level_threads_into_selection(monkeypatch):
    seen = {}
    real = sel.choose

    def spy(*a, **k):
        seen.update(k)
        return real(*a, **k)

    monkeypatch.setattr(sel, "choose", spy)
    Communicator("x", n=N).compile("all_reduce", (16, 32), jnp.float32,
                                   backend="xla", opt_level=0)
    assert seen["opt_level"] == 0
    # and choose() at an explicit level is argmin of that level's costs
    for level in (0, 2):
        pick = real("all_reduce", n=N, nbytes=1 << 10, opt_level=level)
        est = {a: sel.estimate_us(a, N, 1 << 10, opt_level=level)
               for a in sel.CANDIDATES["all_reduce"]}
        assert est[pick] == min(est.values())


# ---------------------------------------------------------------------------
# deployment shape: engine plans at init, module API stays drop-in
# ---------------------------------------------------------------------------
def test_engine_builds_decode_plans_at_init():
    from jax.sharding import Mesh

    from repro import configs
    from repro.distributed import sharding as shd
    from repro.distributed.step import init_sharded
    from repro.serve.engine import Engine, ServeConfig

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    cfg = configs.reduced(configs.get_config("qwen3-1.7b"))
    params, _ = init_sharded(cfg, mesh, shd.MeshAxes(), jax.random.key(0))
    eng = Engine(cfg, params, mesh, ServeConfig(batch=8, max_kv=32))
    assert "layer_allreduce" in eng.decode_plans
    fam = eng.decode_plans["layer_allreduce"]
    # bucketed over active-slot counts; the top bucket is the full local
    # batch (8 global / dp=2) on the per-layer hidden-state shape
    assert isinstance(fam, comm_lib.BucketedPlan)
    assert fam.buckets[-1] == 4
    plan = fam.plans[4]
    assert plan.n == 4 and plan.shape == (4, cfg.d_model)
    report = eng.plan_report()
    assert report["predicted_comm_us_per_token"] > 0
    assert set(report["plans"]["layer_allreduce"]["cards"]) == \
        set(fam.buckets)
    # every decode step replays the same plans: no further compiles
    compiles_at_init = eng.comm.stats["compiles"]
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, (8, 3)).astype(np.int32)
    logits = eng.prefill(prompts)
    eng.decode(logits, num_tokens=2)
    assert eng.comm.stats["compiles"] == compiles_at_init


def test_module_api_remains_drop_in(mesh8):
    """The module-level wrappers keep the exact seed-era semantics."""
    from repro.core import api

    x = jnp.asarray(np.random.RandomState(5).randn(N, 13, 40), jnp.float32)
    y = _shard_run(mesh8, lambda xs: api.all_reduce(
        xs[0], "x", backend="xla")[None], x)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x.sum(0)),
                               rtol=1e-5, atol=1e-5)
    assert api.communicator("x") is comm_lib.default_communicator("x")


# ---------------------------------------------------------------------------
# backend selection at plan time: VMEM gate + tile padding
# ---------------------------------------------------------------------------
def test_oversize_pallas_payload_is_planned_on_xla():
    """The kernel holds its payload whole in VMEM: a payload past the
    budget is planned on the xla lowering at compile time, and the plan
    (and its cost card) say so — no exception is caught and served
    around."""
    comm = Communicator("x", n=4, backend="pallas")
    small = comm.compile("all_reduce", (8, 2048), jnp.bfloat16)
    big = comm.compile("all_reduce", (4096, 2048), jnp.bfloat16)
    assert small.backend == "pallas"
    assert big.backend == "xla" and big.cost_card()["backend"] == "xla"
    assert comm.health == {"verified": 2, "verify_failures": 0,
                           "recompiles": 0}


@pytest.mark.parametrize("collective", ["all_reduce", "all_gather"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_pads_unaligned_payload_exactly(mesh4, collective, dtype):
    """Rows off the sublane tile and columns off the 128-lane tile (the
    qwen3 logits gather has 37984) are zero-padded at dispatch and
    sliced back: the result equals the unpadded reference."""
    comm = Communicator("x", n=4, backend="pallas")
    plan = comm.compile(collective, (3, 200), dtype)
    assert plan.backend == "pallas"
    x = jnp.asarray(np.random.RandomState(0).randn(4, 3, 200), dtype)
    y = _shard_run(mesh4, lambda xs: plan(xs[0])[None], x)
    if collective == "all_reduce":
        want = np.broadcast_to(np.asarray(x, np.float32).sum(0), (4, 3, 200))
    else:
        want = np.broadcast_to(np.asarray(x, np.float32).reshape(12, 200),
                               (4, 12, 200))
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32), want,
                               rtol=tol, atol=tol)
