"""Integration: train loop end-to-end (auto + explicit modes),
checkpoint/restart determinism, elastic re-mesh, compression."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh

from repro import configs
from repro.distributed import sharding as shd
from repro.train import checkpoint as ckpt
from repro.train import compression as comp
from repro.train import loop as train_loop
from repro.train import optimizer as opt


def _mesh(shape, names):
    devs = np.asarray(jax.devices()[: int(np.prod(shape))]).reshape(shape)
    return Mesh(devs, names)


def _cfg():
    return configs.reduced(configs.get_config("llama3.2-3b"))


def test_loss_decreases_auto(tmp_path):
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = _cfg()
    res = train_loop.run(cfg, mesh, train_loop.TrainConfig(
        steps=20, global_batch=8, seq_len=32, log_every=100,
        fixed_batch=True))
    assert res["losses"][-1] < res["losses"][0] - 0.5  # overfits one batch
    assert np.isfinite(res["losses"]).all()


def test_explicit_mode_matches_auto():
    """The paper-technique DP path must be numerically equivalent."""
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = _cfg()
    r1 = train_loop.run(cfg, mesh, train_loop.TrainConfig(
        steps=6, global_batch=8, seq_len=32, mode="auto", log_every=100))
    r2 = train_loop.run(cfg, mesh, train_loop.TrainConfig(
        steps=6, global_batch=8, seq_len=32, mode="explicit", log_every=100))
    np.testing.assert_allclose(r1["losses"], r2["losses"], rtol=2e-3, atol=1e-4)


def test_explicit_hierarchical_two_axis():
    """2-axis DP: grads reduced by the 2PH program across (pod, data)."""
    mesh = _mesh((2, 2, 2), ("pod", "data", "model"))
    ax = shd.MeshAxes(data=("pod", "data"))
    cfg = _cfg()
    r = train_loop.run(cfg, mesh, train_loop.TrainConfig(
        steps=4, global_batch=8, seq_len=32, mode="explicit", log_every=100),
        ax=ax)
    assert np.isfinite(r["losses"]).all()


def test_checkpoint_restart_exact(tmp_path):
    """Stop at step 10, restart, final params identical to uninterrupted."""
    mesh = _mesh((2, 4), ("data", "model"))
    cfg = _cfg()
    tc = dict(global_batch=8, seq_len=32, log_every=100, ckpt_every=5)
    oc = opt.AdamWConfig(total_steps=10, warmup_steps=2)  # same schedule

    r_full = train_loop.run(cfg, mesh, train_loop.TrainConfig(
        steps=10, **tc), opt_cfg=oc)
    d = tmp_path / "ck"
    train_loop.run(cfg, mesh, train_loop.TrainConfig(
        steps=5, ckpt_dir=str(d), **tc), opt_cfg=oc)
    ckpt.wait_pending()
    r_resumed = train_loop.run(cfg, mesh, train_loop.TrainConfig(
        steps=10, ckpt_dir=str(d), **tc), opt_cfg=oc)
    for a, b in zip(jax.tree.leaves(r_full["params"]),
                    jax.tree.leaves(r_resumed["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


def test_elastic_remesh(tmp_path):
    """Train on 8 devices, 'lose' half the pod, resume on 4."""
    cfg = _cfg()
    d = str(tmp_path / "ck")
    mesh8 = _mesh((2, 4), ("data", "model"))
    train_loop.run(cfg, mesh8, train_loop.TrainConfig(
        steps=4, global_batch=8, seq_len=32, ckpt_dir=d, ckpt_every=2,
        log_every=100))
    ckpt.wait_pending()
    mesh4 = _mesh((2, 2), ("data", "model"))
    r = train_loop.run(cfg, mesh4, train_loop.TrainConfig(
        steps=8, global_batch=8, seq_len=32, ckpt_dir=d, log_every=100))
    assert np.isfinite(r["losses"]).all()


def test_compression_error_feedback():
    g = jnp.asarray(np.random.RandomState(0).randn(64, 33), jnp.float32)
    r = jnp.zeros_like(g)
    total = jnp.zeros_like(g)
    # over steps, EF ensures the accumulated transmitted value tracks the
    # accumulated true gradient
    for _ in range(20):
        wire, r = comp.ef_roundtrip(g, r, method="int8")
        total = total + wire
    np.testing.assert_allclose(np.asarray(total / 20), np.asarray(g),
                               rtol=0.02, atol=0.02)


def test_compression_bf16_wire_dtype():
    g = jnp.ones((8, 8), jnp.float32)
    payload, meta = comp.compress(g, "bf16")
    assert payload.dtype == jnp.bfloat16
    back = comp.decompress(payload, meta, "bf16")
    np.testing.assert_allclose(np.asarray(back), np.asarray(g))
