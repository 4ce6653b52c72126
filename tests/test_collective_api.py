"""Collective API layer: selection plumbing, padding, pytree bucket
fusion, hierarchical 2PH — all against jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import api


def _run(mesh, fn, x, in_specs, out_specs):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False))(x)


@pytest.mark.parametrize("backend", ["xla_native", "xla"])
def test_all_reduce_padding_path(mesh8, backend):
    """Rows not divisible by the chunk count exercise the pad/unpad."""
    n = 8
    x = jnp.asarray(np.random.RandomState(0).randn(n, 13, 40), jnp.float32)

    def f(xs):
        return api.all_reduce(xs[0], "x", backend=backend)[None]

    y = _run(mesh8, f, x, P("x", None, None), P("x", None, None))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x.sum(0)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla_native", "xla"])
def test_reduce_scatter_api(mesh8, backend):
    n = 8
    x = jnp.asarray(np.random.RandomState(1).randn(n, n * 4, 16), jnp.float32)

    def f(xs):
        return api.reduce_scatter(xs[0], "x", backend=backend)[None]

    y = _run(mesh8, f, x, P("x", None, None), P("x", None, None))
    want = x.sum(0).reshape(n, 4, 16)
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(want)[:, 0],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", ["xla_native", "xla"])
def test_all_to_all_api(mesh8, backend):
    n = 8
    x = jnp.asarray(np.random.RandomState(2).randn(n, n * 2, 8), jnp.float32)

    def f(xs):
        return api.all_to_all(xs[0], "x", backend=backend)[None]

    y = _run(mesh8, f, x, P("x", None, None), P("x", None, None))
    want = np.swapaxes(np.asarray(x).reshape(n, n, 2, 8), 0, 1)
    np.testing.assert_allclose(np.asarray(y).reshape(n, n, 2, 8), want,
                               rtol=1e-5)


def test_tree_all_reduce_bucket_fusion(mesh8):
    """Mixed-shape pytree reduced in ONE fused buffer."""
    tree = {
        "a": jnp.ones((3, 5), jnp.float32),
        "b": {"c": jnp.full((7,), 2.0, jnp.float32),
              "d": jnp.zeros((2, 2, 2), jnp.float32)},
    }

    def f(_):
        local = jax.tree.map(
            lambda l: l * (1.0 + jax.lax.axis_index("x")), tree)
        return jax.tree.map(
            lambda l: l[None], api.tree_all_reduce(local, "x", backend="xla"))

    out = jax.jit(shard_map(
        f, mesh=mesh8, in_specs=P("x"),
        out_specs=jax.tree.map(lambda _: P("x"), tree), check_vma=False))(
        jnp.zeros((8,)))
    total = sum(range(1, 9))  # Σ (1 + idx)
    np.testing.assert_allclose(np.asarray(out["a"][0]), 3 * 5 * 0 + total,
                               rtol=1e-6, atol=1e-5, err_msg="a")
    np.testing.assert_allclose(np.asarray(out["b"]["c"][0]),
                               2.0 * total, rtol=1e-6)


def test_hierarchical_2ph_matches_flat(mesh2x4):
    """2PH over (node, local) == flat sum over all 8 devices."""
    x = jnp.asarray(np.random.RandomState(3).randn(8, 16, 24), jnp.float32)

    def f(xs):
        return api.hierarchical_all_reduce(
            xs[0, 0], local_axis="local", node_axis="node",
            backend="xla")[None, None]

    y = jax.jit(shard_map(
        f, mesh=mesh2x4, in_specs=P("node", "local", None, None),
        out_specs=P("node", "local", None, None), check_vma=False))(
        x.reshape(2, 4, 16, 24))
    np.testing.assert_allclose(np.asarray(y[0, 0]), np.asarray(x.sum(0)),
                               rtol=1e-4, atol=1e-5)


def test_broadcast_api(mesh8):
    x = jnp.asarray(np.random.RandomState(4).randn(8, 8, 16), jnp.float32)

    def f(xs):
        return api.broadcast(xs[0], "x", root=3, backend="xla")[None]

    y = _run(mesh8, f, x, P("x", None, None), P("x", None, None))
    for d in range(8):
        np.testing.assert_allclose(np.asarray(y[d]), np.asarray(x[3]),
                                   rtol=1e-6)
