"""Trace capture: schema round-trip, determinism, zero-overhead
guarantee, both backends, and the collector/engine surfaces
(docs/profiling.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.core import algorithms as algos
from repro.core import simulate, trace
from repro.core import verify as verify_mod
from repro.core.comm import Communicator

N = 8


def _shard_run(mesh, fn, x):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=P("x", None, None),
                             out_specs=P("x", None, None),
                             check_vma=False))(x)


def _small_trace(**kw):
    plan = Communicator("x", n=N).compile(
        "all_reduce", (16, 8), jnp.float32, algo="allreduce_ring",
        opt_level=2, **kw)
    return trace.capture_plan(plan)


# ---------------------------------------------------------------------------
# schema: JSON round-trip + versioned rejection
# ---------------------------------------------------------------------------
def test_trace_json_roundtrip():
    t = _small_trace()
    rt = trace.Trace.from_json(t.to_json())
    assert rt.n == t.n and rt.shape == t.shape and rt.dtype == t.dtype
    assert rt.algo == t.algo and rt.backend == t.backend
    assert len(rt.events) == len(t.events)
    # events round-trip exactly at the serialized (4dp µs) precision
    assert [e.to_dict() for e in rt.events] == [e.to_dict() for e in t.events]
    assert abs(rt.span_us - t.span_us) < 1e-3
    # ...and a round-tripped trace replays like the original
    rep = simulate.replay(rt)
    assert rep.rel_err <= simulate.REPLAY_TOLERANCE


def test_trace_save_load_roundtrip(tmp_path):
    t = _small_trace()
    p = tmp_path / "t.json"
    t.save(p)
    rt = trace.Trace.load(p)
    assert [e.to_dict() for e in rt.events] == [e.to_dict() for e in t.events]


def test_trace_schema_rejections():
    t = _small_trace()
    good = t.to_dict()

    with pytest.raises(ValueError, match="no schema 'version'"):
        trace.Trace.from_dict({k: v for k, v in good.items()
                               if k != "version"})
    with pytest.raises(ValueError, match="unsupported trace schema"):
        trace.Trace.from_dict({**good, "version": 99})
    with pytest.raises(ValueError, match="kind"):
        trace.Trace.from_dict({**good, "kind": "plan"})
    with pytest.raises(ValueError, match="missing required field 'events'"):
        trace.Trace.from_dict({k: v for k, v in good.items()
                               if k != "events"})


# ---------------------------------------------------------------------------
# determinism: same plan -> same ids, ordering, structure
# ---------------------------------------------------------------------------
def test_capture_deterministic_ids_and_order():
    def key(t):
        return [(e.iid, e.sub, e.op, e.lowered, e.rank, e.peer,
                 e.round_id, e.chunks, e.bytes, e.wire_bytes,
                 tuple(e.deps)) for e in t.events]

    assert key(_small_trace()) == key(_small_trace())


def test_event_ids_match_program_instructions():
    plan = Communicator("x", n=N).compile("all_reduce", (16, 8),
                                          jnp.float32)
    t = trace.capture_plan(plan)
    n_instr = len(plan.program.instructions())
    assert all(0 <= e.iid < n_instr for e in t.events)
    # emission-major order: (iid, sub) non-decreasing through the stream
    pairs = [(e.iid, e.sub) for e in t.events]
    assert pairs == sorted(pairs)


# ---------------------------------------------------------------------------
# zero overhead: tracing adds NOTHING to the replayed program
# ---------------------------------------------------------------------------
def test_tracing_adds_zero_instructions(mesh8):
    x = np.ones((N, 16, 32), np.float32)
    p_on = Communicator("x", n=N, trace=True).compile(
        "all_reduce", (16, 32), jnp.float32)
    p_off = Communicator("x", n=N).compile(
        "all_reduce", (16, 32), jnp.float32)

    def wrap(p):
        return shard_map(lambda xs: p(xs[0])[None], mesh=mesh8,
                         in_specs=P("x", None, None),
                         out_specs=P("x", None, None), check_vma=False)

    j_on = jax.make_jaxpr(wrap(p_on))(x)
    j_off = jax.make_jaxpr(wrap(p_off))(x)
    assert str(j_on) == str(j_off)
    # the traced plan DID capture (host-side, at jit-trace time)...
    assert p_on.last_trace is not None
    assert p_off.last_trace is None
    # ...and its program still passes the static verifier
    assert verify_mod.verify_program(p_on.program, N,
                                     collective="all_reduce").ok


def test_traced_plan_output_identical(mesh8):
    x = np.asarray(np.random.RandomState(0).randn(N, 16, 32), np.float32)
    p_on = Communicator("x", n=N, trace=True).compile(
        "all_reduce", (16, 32), jnp.float32)
    p_off = Communicator("x", n=N).compile(
        "all_reduce", (16, 32), jnp.float32)
    y_on = _shard_run(mesh8, lambda xs: p_on(xs[0])[None], x)
    y_off = _shard_run(mesh8, lambda xs: p_off(xs[0])[None], x)
    np.testing.assert_array_equal(np.asarray(y_on), np.asarray(y_off))
    assert p_on.last_trace is not None


# ---------------------------------------------------------------------------
# both backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_capture_both_backends(backend):
    t = trace.capture(algos.REGISTRY["allreduce_ring"](N), N,
                      rows=16, cols=8, backend=backend, opt_level=2)
    assert t.backend == backend
    assert t.span_us > 0 and len(t.events) > 0
    ops = {e.op for e in t.events}
    assert "put" in ops and "wait" in ops
    # every wait's deps point at put events that exist in the stream
    put_ids = {(e.iid, e.sub, e.rank) for e in t.events if e.op == "put"}
    for e in t.events:
        if e.op == "wait":
            assert e.deps and all(d in put_ids for d in e.deps)
    rep = simulate.replay(t)
    assert rep.rel_err <= simulate.REPLAY_TOLERANCE


def test_backends_agree_on_bytes_moved():
    prog = algos.REGISTRY["allreduce_ring"](N)
    tx = trace.capture(prog, N, rows=16, cols=8, backend="xla", opt_level=2)
    tp = trace.capture(prog, N, rows=16, cols=8, backend="pallas",
                       opt_level=2)
    def total_put_bytes(t):
        return sum(e.bytes for e in t.events if e.op == "put")
    # lowering differs (one all_to_all vs per-peer DMAs) but the bytes
    # crossing the links must be identical
    assert total_put_bytes(tx) == total_put_bytes(tp)


# ---------------------------------------------------------------------------
# collector + communicator + engine surfaces
# ---------------------------------------------------------------------------
def test_collect_context_records_executions(mesh8):
    plan = Communicator("x", n=N).compile("all_reduce", (16, 32),
                                          jnp.float32)
    x = np.ones((N, 16, 32), np.float32)
    assert trace.active() is None
    with trace.collect() as col:
        _shard_run(mesh8, lambda xs: plan(xs[0])[None], x)
    assert trace.active() is None
    assert len(col.traces) == 1
    t = col.traces[0]
    assert t.backend == "xla" and t.n == N
    assert simulate.replay(t).rel_err <= simulate.REPLAY_TOLERANCE


def test_bucketed_plan_last_trace(mesh8):
    comm = Communicator("x", n=N, trace=True)
    fam = comm.plan_for("all_reduce", (16, 32), jnp.float32,
                        buckets=(8, 16))
    x = np.ones((N, 16, 32), np.float32)
    _shard_run(mesh8, lambda xs: fam(xs[0])[None], x)
    assert fam.last_trace is not None          # largest bucket executed
    traces = fam.last_traces()
    assert set(traces) == set(fam.buckets)
    assert traces[16] is not None


def test_serve_config_trace_flows_to_communicator():
    from repro.serve.engine import ServeConfig
    assert ServeConfig().trace is False
    assert ServeConfig(trace=True).trace is True


def test_plan_report_trace_key():
    from jax.sharding import Mesh

    from repro import configs
    from repro.distributed import sharding as shd
    from repro.distributed.step import init_sharded
    from repro.serve.engine import Engine, ServeConfig

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                ("data", "model"))
    cfg = configs.reduced(configs.get_config("qwen3-1.7b"))
    params, _ = init_sharded(cfg, mesh, shd.MeshAxes(), jax.random.key(0))
    eng = Engine(cfg, params, mesh, ServeConfig(batch=8, max_kv=32,
                                                mode="explicit",
                                                trace=True))
    assert eng.comm.trace is True
    prompts = np.random.RandomState(0).randint(
        0, cfg.vocab, (8, 2)).astype(np.int32)
    logits = eng.prefill(prompts)
    eng.decode(logits, num_tokens=1)
    report = eng.plan_report()
    assert set(report["trace"]) == set(eng.decode_plans)
    summ = report["trace"]["layer_allreduce"]
    assert summ is not None and summ["events"] > 0 and summ["span_us"] > 0
