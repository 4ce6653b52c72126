"""Fused allgather+matmul overlap kernel vs oracle."""
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.collective_matmul import allgather_matmul


def _rand(shape, dtype, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("rows,k,f", [(8, 128, 128), (16, 256, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_allgather_matmul(mesh8, rows, k, f, dtype):
    n = mesh8.shape["x"]
    x = _rand((n, rows, k), dtype, 0)
    w = _rand((k, f), dtype, 1)

    def run(xs, ws):
        return allgather_matmul(xs, ws, axis="x", axis_size=n,
                                out_dtype=jnp.float32)[None]

    fmap = shard_map(run, mesh=mesh8, in_specs=(P("x", None), P(None, None)),
                     out_specs=P("x", None, None), check_vma=False)
    y = fmap(x.reshape(n * rows, k), w)  # (n, n*rows, f)
    want = ref.allgather_matmul_ref(x.astype(jnp.float32),
                                    w.astype(jnp.float32))
    tol = dict(atol=2e-1, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               np.asarray(want, np.float64), **tol)


def test_allgather_matmul_twice(mesh8):
    """Two sequential fused calls (TP layer stack) must not race."""
    n = mesh8.shape["x"]
    rows, k = 8, 128
    x = _rand((n, rows, k), jnp.float32, 0)
    w1 = _rand((k, k), jnp.float32, 1)

    def run(xs, ws):
        y1 = allgather_matmul(xs, ws, axis="x", axis_size=n)  # (n*rows, k)
        me_rows = y1[: rows]  # take my row block back
        y2 = allgather_matmul(me_rows, ws, axis="x", axis_size=n)
        return y2[None]

    fmap = shard_map(run, mesh=mesh8, in_specs=(P("x", None), P(None, None)),
                     out_specs=P("x", None, None), check_vma=False)
    y = fmap(x.reshape(n * rows, k), w1)
    full1 = ref.allgather_matmul_ref(x, w1)[0]          # (n*rows, k)
    # y1 is replicated, so every device feeds the same first row-block into
    # the second gather: expectation = n stacked copies of that block @ w1.
    gathered = jnp.concatenate([full1[:rows]] * n, axis=0)
    want = gathered @ w1
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), rtol=1e-3, atol=1e-3)
