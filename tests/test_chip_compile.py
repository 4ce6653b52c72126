"""Compile the served path for a described TPU v5e 2x2 host (no chip
attached): the DSL ``PallasExecutor`` behind the qwen3-1.7b TP=4 decode
plans must lower to a Mosaic kernel (``tpu_custom_call``), a payload
too large for the kernel's VMEM must be planned on the XLA backend
instead of failing to compile, and the one-chip decode steps at full
width must update their K/V cache in place.

This is the only test file that describes a TPU. The topology is
described inside a module fixture (never at import), so every xdist
worker collects the same tests and only the worker that runs this file
loads the TPU compiler.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.comm import Communicator
from repro.core.executor import PallasExecutor
from repro.distributed import sharding as shd
from repro.distributed.step import (compile_decode_plans, make_sched_step,
                                    make_serve_step)
from repro.models import transformer as tf

TP = 4
BATCH = 8
SEQ_BUCKETS = (64, 128, 512)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(1, TP), ("data", "model"))


@pytest.fixture(scope="module")
def plans():
    """qwen3-1.7b's decode plan set at TP=4, as the engine compiles it
    on a TPU (pallas backend), with fused-prefill sequence buckets."""
    cfg = configs.get_config("qwen3-1.7b")
    comm = Communicator("model", n=TP, backend="pallas")
    return compile_decode_plans(cfg, comm, batch_local=BATCH, tp=TP,
                                seq_buckets=SEQ_BUCKETS)


def _compiled_text(mesh, plan) -> str:
    rows, cols = plan.shape
    spec = P("data", "model")
    f = jax.jit(shard_map(lambda x: plan(x[0, 0])[None, None], mesh=mesh,
                          in_specs=spec, out_specs=spec, check_vma=False))
    x = jax.ShapeDtypeStruct((1, TP, rows, cols), jnp.dtype(plan.dtype),
                             sharding=NamedSharding(mesh, spec))
    return f.lower(x).compile().as_text()


@pytest.mark.parametrize("rows", [1, 2, 4, 8])
def test_decode_allreduce_compiles_to_mosaic(mesh, plans, rows):
    """layer_allreduce at every decode slot bucket (rows x 2048 bf16)."""
    plan = plans["layer_allreduce"].plans[rows]
    assert plan.backend == "pallas"
    assert "tpu_custom_call" in _compiled_text(mesh, plan)


@pytest.mark.parametrize("rows", [1, 8])
def test_logits_allgather_compiles_to_mosaic(mesh, plans, rows):
    """logits_allgather at vocab // tp = 37984 f32 columns — not a
    multiple of the 128-lane tile; the executor pads at dispatch."""
    plan = plans["logits_allgather"].plans[rows]
    assert plan.shape == (rows, 37984) and plan.backend == "pallas"
    assert "tpu_custom_call" in _compiled_text(mesh, plan)


@pytest.mark.parametrize("seq", SEQ_BUCKETS)
def test_prefill_allreduce_compiles_or_is_planned_on_xla(mesh, plans, seq):
    """Fused-prefill AllReduce buckets (8 * seq rows x 2048 bf16): a
    payload within the kernel's VMEM budget compiles to Mosaic; the
    largest (4096 rows, 16 MiB per buffer) is planned on XLA at plan
    time and compiles there, with no Mosaic kernel."""
    plan = plans["layer_allreduce"].plans[BATCH * seq]
    rows, cols = plan.shape
    fits = PallasExecutor(plan.program, "model").fits(rows + plan.pad, cols,
                                                      plan.dtype)
    assert plan.backend == ("pallas" if fits else "xla")
    text = _compiled_text(mesh, plan)
    assert ("tpu_custom_call" in text) == fits
    if seq == max(SEQ_BUCKETS):
        assert not fits


def _abstract(shapes, specs, mesh):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shd.shardings_for(specs, mesh))


@pytest.mark.parametrize("step,slots,max_kv", [
    ("sched", 1, 2048), ("sched", 8, 2048), ("serve", 8, 1024)])
def test_decode_step_updates_cache_in_place(topo, step, slots, max_kv):
    """qwen3-1.7b at full width on one chip: the Scheduler's auto step,
    donated as ``Scheduler._step_fn`` donates it, at slot buckets 1 and
    8 of the benchmark's 8 x 2048 cache, and the Engine's scalar-position
    step at chip_smoke's 8 x 1024. The optimized program copies and
    selects no whole cache leaf, keeps no temporary the size of one, and
    aliases every cache parameter to its output."""
    cfg = configs.get_config("qwen3-1.7b")
    ax = shd.MeshAxes()
    mesh = Mesh(np.asarray(topo.devices[:1])
                .reshape(1, 1), ("data", "model"))
    rep = NamedSharding(mesh, P(None))
    rows = jax.ShapeDtypeStruct((slots,), np.int32, sharding=rep)
    if step == "sched":
        fn, cspecs = make_sched_step(cfg, mesh, ax, batch=slots,
                                     max_kv=max_kv)
        fn = jax.jit(fn, donate_argnums=(1,))
        tail = (rows, rows, jax.ShapeDtypeStruct((slots,), np.bool_,
                                                 sharding=rep))
    else:
        fn, cspecs = make_serve_step(cfg, mesh, ax, batch=slots,
                                     max_kv=max_kv, donate=True)
        tail = (rows, jax.ShapeDtypeStruct((), np.int32))
    params = _abstract(
        jax.eval_shape(lambda: tf.init_params(cfg, jax.random.key(0))),
        shd.param_pspecs(cfg, mesh, ax), mesh)
    cache = _abstract(
        jax.eval_shape(lambda: tf.init_cache(cfg, slots, max_kv)),
        cspecs, mesh)
    compiled = fn.lower(params, cache, *tail).compile()
    text = compiled.as_text()

    leaves = jax.tree.leaves(cache)
    for shape in {a.shape for a in leaves}:
        dims = re.escape("[%s]" % ",".join(map(str, shape)))
        whole = re.findall(r"%s\{[^}]*\} (?:copy|copy-start|select)\(" % dims,
                           text)
        assert not whole, (shape, whole[:1])
    leaf_bytes = min(a.size * a.dtype.itemsize for a in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes // 100

    n_params = len(jax.tree.leaves(params))
    head = text.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\{\d+\}: \((\d+), \{\}", head)}
    assert aliased >= set(range(n_params, n_params + len(leaves)))
