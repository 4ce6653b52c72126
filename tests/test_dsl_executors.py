"""DSL programs run on BOTH executors must match the jnp oracles —
the paper's core claim that declaration and implementation separate
cleanly. Also: program validation, comm stats, and the selector policy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import algorithms as algos
from repro.core import selector as sel
from repro.core.dsl import PEER, RANK, Program
from repro.core.executor import execute
from repro.kernels import ref

N = 8
BACKENDS = ["xla", "pallas"]


def _rand(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), jnp.float32)


def _run_sharded(prog, x_global, mesh, backend, in_chunks, out_chunks):
    """x_global: (N, in_chunks*rows, cols) per-device buffers."""

    def run(xs):
        return execute(prog, xs[0], axis="x", backend=backend)[None]

    f = shard_map(run, mesh=mesh, in_specs=P("x", None, None),
                  out_specs=P("x", None, None), check_vma=False)
    return f(x_global)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", BACKENDS)
def test_allpairs_rs(mesh8, backend):
    prog = algos.allpairs_rs(N)
    prog.validate(N)
    x = _rand((N, N * 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, N, 1)
    want = ref.reduce_scatter_ref(x.reshape(N, N, 8, 128)).reshape(N, 8, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_allpairs_ag(mesh8, backend):
    prog = algos.allpairs_ag(N)
    prog.validate(N)
    x = _rand((N, 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, 1, N)
    want = ref.all_gather_ref(x).reshape(N, N * 8, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_allreduce_1pa(mesh8, backend):
    prog = algos.allreduce_1pa(N)
    prog.validate(N)
    x = _rand((N, 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, 1, 1)
    want = ref.all_reduce_ref(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_allreduce_2pa(mesh8, backend):
    prog = algos.allreduce_2pa(N)
    prog.validate(N)
    x = _rand((N, N * 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, N, N)
    want = ref.all_reduce_ref(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_ring_ag(mesh8, backend):
    prog = algos.ring_ag(N)
    prog.validate(N)
    x = _rand((N, 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, 1, N)
    want = ref.all_gather_ref(x).reshape(N, N * 8, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_ring_rs(mesh8, backend):
    prog = algos.ring_rs(N)
    prog.validate(N)
    x = _rand((N, N * 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, N, 1)
    want = ref.reduce_scatter_ref(x.reshape(N, N, 8, 128)).reshape(N, 8, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_allreduce_ring(mesh8, backend):
    prog = algos.allreduce_ring(N)
    prog.validate(N)
    x = _rand((N, N * 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, N, N)
    want = ref.all_reduce_ref(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_alltoall(mesh8, backend):
    prog = algos.alltoall(N)
    prog.validate(N)
    x = _rand((N, N * 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, N, N)
    want = ref.all_to_all_ref(x.reshape(N, N, 8, 128)).reshape(N, N * 8, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("root", [0, 3])
def test_broadcast(mesh8, backend, root):
    prog = algos.broadcast_allpairs(N, root)
    prog.validate(N)
    x = _rand((N, 8, 128))
    y = _run_sharded(prog, x, mesh8, backend, 1, 1)
    want = ref.broadcast_ref(x, root)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5)


# ---------------------------------------------------------------------------
# Pallas slab lowering: a coalesced multi-chunk put is ONE strided DMA
# descriptor per peer, not k per-chunk descriptors (ROADMAP item)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["allreduce_ring", "ring_ag", "ring_rs"])
def test_pallas_slab_put_one_descriptor_per_peer(mesh4, name):
    from repro.core import passes
    from repro.core.executor import PallasExecutor, XlaExecutor

    n = 4
    # O3 chunk-split ring: each round's coalesced put carries 2 adjacent
    # sub-chunk streams — a contiguous slab, so one descriptor moves both
    prog = passes.optimize(algos.REGISTRY[name](n), 3, n)
    ex = PallasExecutor(prog, "x").prepare(n)
    assert ex.chunk_put_count() == 2 * ex.descriptor_count(n)

    n_in = prog.chunks[prog.in_buffer]
    x = _rand((n, n_in * 2, 16), seed=7)

    def run(xs):
        return ex(xs[0])[None]

    y = shard_map(run, mesh=mesh4, in_specs=P("x", None, None),
                  out_specs=P("x", None, None), check_vma=False)(x)
    # the traced kernel issued exactly the planned descriptor count
    assert ex.last_trace_descriptors == ex.descriptor_count(n)

    ex0 = XlaExecutor(prog, "x", vectorize=False)

    def run0(xs):
        return ex0(xs[0])[None]

    y0 = shard_map(run0, mesh=mesh4, in_specs=P("x", None, None),
                   out_specs=P("x", None, None), check_vma=False)(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))


def test_pallas_noncontiguous_put_keeps_per_chunk_descriptors():
    """A coalesced fan-out round (different shifts per chunk) has no
    slab: the descriptor count stays one per chunk put."""
    from repro.core import passes
    from repro.core.executor import PallasExecutor

    n = 4
    prog = passes.optimize(algos.allreduce_1pa(n), 2, n)
    ex = PallasExecutor(prog, "x")
    assert ex.descriptor_count(n) == ex.chunk_put_count() == n - 1


def test_validate_catches_bad_buffer():
    p = Program("bad", chunks=dict(input=1, output=1))
    p.put(src=("input", 0), dst=("nope", RANK), to=PEER(1))
    with pytest.raises(ValueError, match="unknown buffer"):
        p.freeze().validate(4)


def test_validate_catches_unmatched_wait():
    p = Program("bad2", chunks=dict(input=4, output=4))
    p.wait(("output", RANK), frm=PEER(1))
    with pytest.raises(ValueError, match="no matching put"):
        p.freeze().validate(4)


def test_comm_stats():
    prog = algos.allreduce_2pa(4)
    stats = prog.comm_stats(4, chunk_bytes=1024)
    assert stats["puts_per_rank"] == 6          # 3 RS + 3 AG
    assert stats["bytes_per_rank"] == 6 * 1024
    assert stats["comm_rounds"] == 2


def test_selector_policy_matches_paper():
    """Paper §5.1: 1PA tiny → 2PA medium → ring large."""
    assert sel.choose("all_reduce", n=8, nbytes=1 << 10) == "allreduce_1pa"
    assert sel.choose("all_reduce", n=8, nbytes=1 << 15) == "allreduce_2pa"
    assert sel.choose("all_reduce", n=8, nbytes=1 << 30) == "allreduce_ring"
    # monotone regions: algorithm never flips back as size grows
    seen, order = [], []
    for exp in range(8, 31):
        a = sel.choose("all_reduce", n=8, nbytes=1 << exp)
        if not order or order[-1] != a:
            assert a not in order, f"non-monotone selection at 2^{exp}"
            order.append(a)
    assert order.index("allreduce_1pa") < order.index("allreduce_ring")
