"""Expert-parallel MoE (all_to_all dispatch) vs the dense-einsum oracle."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed.moe_parallel import moe_layer_ep
from repro.models import blocks


def test_ep_matches_dense(mesh4):
    cfg = configs.reduced(configs.get_config("phi3.5-moe-42b-a6.6b"))
    # 4 experts over a 4-device expert axis, ample capacity => exact
    p = blocks.init_moe(jax.random.key(0), cfg)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, cfg.d_model),
                    jnp.float32)
    want = blocks.moe_layer(p, x, cfg)

    def run(router, wg, wu, wd, xs):
        lp = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        return moe_layer_ep(lp, xs, cfg, axis="x", capacity_factor=8.0,
                            backend="xla")

    f = jax.jit(shard_map(
        run, mesh=mesh4,
        in_specs=(P(None, None), P("x", None, None), P("x", None, None),
                  P("x", None, None), P(None, None, None)),
        out_specs=P(None, None, None), check_vma=False))
    got = f(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ep_through_bucketed_plan_matches_dense(mesh4):
    """moe_layer_ep with plan=: dispatch AND combine replay one
    init-compiled capacity-bucketed all_to_all plan — zero compiles
    inside the traced layer, output matches the dense oracle."""
    from repro.core.comm import Communicator
    from repro.distributed.moe_parallel import ep_capacity

    cfg = configs.reduced(configs.get_config("phi3.5-moe-42b-a6.6b"))
    p = blocks.init_moe(jax.random.key(2), cfg)
    x = jnp.asarray(np.random.RandomState(2).randn(2, 8, cfg.d_model),
                    jnp.float32)
    want = blocks.moe_layer(p, x, cfg)

    ep = 4
    e_total = cfg.moe.num_experts
    e_local = e_total // ep
    cap = ep_capacity(2 * 8, cfg.moe.top_k, e_total)       # lossless
    comm = Communicator("x", n=ep, backend="xla")
    plan = comm.plan_for("all_to_all", (e_total * cap, cfg.d_model),
                         jnp.float32, buckets=(e_local * cap,))
    compiles = comm.stats["compiles"]

    def run(router, wg, wu, wd, xs):
        lp = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        return moe_layer_ep(lp, xs, cfg, axis="x", capacity_factor=None,
                            comm=comm, plan=plan)

    f = jax.jit(shard_map(
        run, mesh=mesh4,
        in_specs=(P(None, None), P("x", None, None), P("x", None, None),
                  P("x", None, None), P(None, None, None)),
        out_specs=P(None, None, None), check_vma=False))
    got = f(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    # pure replay: tracing the layer compiled nothing new, and both
    # all_to_alls dispatched through the plan's bucket counters
    assert comm.stats["compiles"] == compiles
    assert plan.hits[e_local * cap] == 2                   # dispatch+combine


def test_ep_capacity_lossless_default():
    from repro.distributed.moe_parallel import ep_capacity

    # None -> worst case (all assignments to one expert): T*k slots
    assert ep_capacity(16, 2, 8, None) == 32
    # a factor reproduces the Switch-style formula
    assert ep_capacity(16, 2, 8, 2.0) == int(2.0 * 16 * 2 / 8) + 1


def test_ep_capacity_drops_gracefully(mesh4):
    """Tiny capacity must not crash or corrupt — dropped tokens get zero
    expert contribution (Switch-style)."""
    cfg = configs.reduced(configs.get_config("phi3.5-moe-42b-a6.6b"))
    p = blocks.init_moe(jax.random.key(1), cfg)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 16, cfg.d_model),
                    jnp.float32)

    def run(router, wg, wu, wd, xs):
        lp = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        return moe_layer_ep(lp, xs, cfg, axis="x", capacity_factor=0.25,
                            backend="xla")

    f = jax.jit(shard_map(
        run, mesh=mesh4,
        in_specs=(P(None, None), P("x", None, None), P("x", None, None),
                  P("x", None, None), P(None, None, None)),
        out_specs=P(None, None, None), check_vma=False))
    got = np.asarray(f(p["router"], p["w_gate"], p["w_up"], p["w_down"], x))
    assert np.isfinite(got).all()
    dense = np.asarray(blocks.moe_layer(p, x, cfg))
    # dropped-capacity output has smaller norm than the full compute
    assert np.linalg.norm(got) <= np.linalg.norm(dense) * 1.5
