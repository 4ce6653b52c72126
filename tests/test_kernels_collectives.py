"""Per-kernel allclose validation against ref.py oracles: shape/dtype
sweeps of every Pallas collective, run in interpret mode over emulated
devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.allgather_ring import all_gather_ring
from repro.kernels.allreduce_1pa import all_reduce_1pa
from repro.kernels.reducescatter_2pa import (
    all_gather_2pa,
    all_reduce_2pa,
    reduce_scatter_2pa,
)

SHAPES = [(8, 128), (16, 256), (8, 384)]
DTYPES = [jnp.float32, jnp.bfloat16, jnp.int32]


def _rand(shape, dtype, seed=0):
    r = np.random.RandomState(seed)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(r.randint(-100, 100, size=shape), dtype)
    return jnp.asarray(r.randn(*shape), dtype)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_all_gather_ring(mesh8, shape, dtype):
    n = mesh8.shape["x"]
    x = _rand((n,) + shape, dtype)  # (N, rows, cols): per-device chunks

    def run(xs):  # xs: (rows, cols) local
        return all_gather_ring(xs, axis="x", axis_size=n)[None]

    f = shard_map(run, mesh=mesh8, in_specs=P("x", None),
                  out_specs=P("x", None, None), check_vma=False)
    y = f(x.reshape(n * shape[0], shape[1]))  # (N, N*rows, cols)
    want = ref.all_gather_ref(x).reshape(n, n * shape[0], shape[1])
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_reduce_scatter_2pa(mesh8, shape, dtype):
    n = mesh8.shape["x"]
    x = _rand((n, n) + shape, dtype)  # x[d, c]: device d's contribution to chunk c

    def run(xs):  # xs: (1, N*rows, cols)
        return reduce_scatter_2pa(xs[0], axis="x", axis_size=n)[None]

    f = shard_map(run, mesh=mesh8, in_specs=P("x", None, None),
                  out_specs=P("x", None, None), check_vma=False)
    y = f(x.reshape(n, n * shape[0], shape[1]))  # (N, rows, cols)
    want = ref.reduce_scatter_ref(x)
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES[:2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_all_gather_2pa(mesh8, shape, dtype):
    n = mesh8.shape["x"]
    x = _rand((n,) + shape, dtype)

    def run(xs):
        return all_gather_2pa(xs, axis="x", axis_size=n)[None]

    f = shard_map(run, mesh=mesh8, in_specs=P("x", None),
                  out_specs=P("x", None, None), check_vma=False)
    y = f(x.reshape(n * shape[0], shape[1]))
    want = ref.all_gather_ref(x).reshape(n, n * shape[0], shape[1])
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES[:2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_all_reduce_2pa(mesh8, shape, dtype):
    n = mesh8.shape["x"]
    rows = n * shape[0]
    x = _rand((n, rows, shape[1]), dtype)

    def run(xs):
        return all_reduce_2pa(xs[0], axis="x", axis_size=n)[None]

    f = shard_map(run, mesh=mesh8, in_specs=P("x", None, None),
                  out_specs=P("x", None, None), check_vma=False)
    y = f(x)
    want = ref.all_reduce_ref(x)
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


@pytest.mark.parametrize("use_ll", [True, False])
@pytest.mark.parametrize("shape", SHAPES[:2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_all_reduce_1pa(mesh8, shape, dtype, use_ll):
    n = mesh8.shape["x"]
    x = _rand((n,) + shape, dtype)

    def run(xs):
        return all_reduce_1pa(xs[0], axis="x", axis_size=n, use_ll=use_ll)[None]

    f = shard_map(run, mesh=mesh8, in_specs=P("x", None, None),
                  out_specs=P("x", None, None), check_vma=False)
    y = f(x)
    want = ref.all_reduce_ref(x)
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               np.asarray(want, np.float64), **_tol(dtype))


def test_all_reduce_1pa_distinct_steps(mesh8):
    """LL flags must be distinct across steps: run twice on the same data."""
    n = mesh8.shape["x"]
    x = _rand((n, 8, 128), jnp.float32)

    def run(xs):
        y1 = all_reduce_1pa(xs[0], axis="x", axis_size=n, use_ll=True, step=0)
        y2 = all_reduce_1pa(y1, axis="x", axis_size=n, use_ll=True, step=1)
        return y2[None]

    f = shard_map(run, mesh=mesh8, in_specs=P("x", None, None),
                  out_specs=P("x", None, None), check_vma=False)
    y = f(x)
    want = ref.all_reduce_ref(ref.all_reduce_ref(x))
    # chained reductions associate differently in-kernel vs the oracle
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=5e-4)
