"""Validation for the hierarchical (2PH) allreduce and all-to-all kernels."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.alltoall import all_to_all_pallas
from repro.kernels.allreduce_2ph import all_reduce_2ph


def _rand(shape, dtype, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


@pytest.mark.parametrize("shape", [(8, 128), (16, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_all_to_all(mesh8, shape, dtype):
    n = mesh8.shape["x"]
    x = _rand((n, n) + shape, dtype)  # x[d, c] goes device d -> device c

    def run(xs):  # xs: (1, n, rows, cols)
        flat = xs.reshape(n * shape[0], shape[1])
        out = all_to_all_pallas(flat, axis="x", axis_size=n)
        return out.reshape(1, n, shape[0], shape[1])

    f = shard_map(run, mesh=mesh8, in_specs=P("x", None, None, None),
                  out_specs=P("x", None, None, None), check_vma=False)
    y = f(x)
    want = ref.all_to_all_ref(x)
    np.testing.assert_allclose(np.asarray(y, np.float64),
                               np.asarray(want, np.float64), atol=1e-2)


@pytest.mark.parametrize("rows_per_chunk", [8, 16])
def test_all_reduce_2ph(mesh2x4, rows_per_chunk):
    nn, ln = mesh2x4.shape["node"], mesh2x4.shape["local"]
    total = nn * ln
    cols = 128
    x = _rand((total, ln * rows_per_chunk, cols), jnp.float32)

    def run(xs):  # xs: (1, 1, L*rows, cols)
        out = all_reduce_2ph(xs[0, 0], local_axis="local", local_size=ln,
                             node_axis="node", node_size=nn)
        return out[None, None]

    f = shard_map(run, mesh=mesh2x4, in_specs=P("node", "local", None, None),
                  out_specs=P("node", "local", None, None), check_vma=False)
    y = f(x.reshape(nn, ln, ln * rows_per_chunk, cols))
    want = ref.hierarchical_all_reduce_ref(x).reshape(
        nn, ln, ln * rows_per_chunk, cols)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-3, atol=1e-5)


def test_all_reduce_2ph_twice(mesh2x4):
    """Back-to-back invocations in one jit must not race (exit barrier)."""
    nn, ln = 2, 4
    total = nn * ln
    x = _rand((total, ln * 8, 128), jnp.float32)

    def run(xs):
        y1 = all_reduce_2ph(xs[0, 0], local_axis="local", local_size=ln,
                            node_axis="node", node_size=nn)
        y2 = all_reduce_2ph(y1, local_axis="local", local_size=ln,
                            node_axis="node", node_size=nn)
        return y2[None, None]

    f = shard_map(run, mesh=mesh2x4, in_specs=P("node", "local", None, None),
                  out_specs=P("node", "local", None, None), check_vma=False)
    y = f(x.reshape(nn, ln, ln * 8, 128))
    want = ref.all_reduce_ref(ref.all_reduce_ref(x)).reshape(nn, ln, ln * 8, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-3, atol=1e-5)
