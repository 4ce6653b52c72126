"""Property test: RANDOM DSL programs produce identical results on the
ppermute executor and the Pallas channel executor — the paper's central
separation-of-concerns claim, checked beyond the curated algorithm set."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # not in the minimal CI image
from hypothesis import given, settings, strategies as st
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.dsl import PEER, RANK, Program
from repro.core.executor import execute

N = 4


def _subset_program(offsets: tuple[int, ...]) -> Program:
    """Subset all-pairs reduce: out[r] = in[r,r] + Σ_{i∈O} in[r-i, r].

    Note the duality this test pinned down: a put issued to PEER(+i)
    *arrives* from PEER(-i), landing in slot PEER(-i) (= the sender's
    RANK). The library's full-set algorithms are invariant to this
    (offset sets are symmetric); arbitrary subsets are not — validate()
    rejects the naive formulation.
    """
    p = Program(f"subset_{'_'.join(map(str, offsets))}",
                chunks=dict(input=N, scratch=N, output=1))
    with p.round():
        for i in offsets:
            p.put(src=("input", PEER(+i)), dst=("scratch", RANK), to=PEER(+i))
    with p.round():
        for i in offsets:
            p.wait(("scratch", PEER(-i)), frm=PEER(-i))
    p.local_reduce(("output", 0),
                   [("input", RANK)] + [("scratch", PEER(-i)) for i in offsets])
    return p.freeze()


@settings(deadline=None, max_examples=8)
@given(st.sets(st.integers(1, N - 1), min_size=1, max_size=N - 1))
def test_random_subset_programs_equivalent(offs):
    offsets = tuple(sorted(offs))
    prog = _subset_program(offsets)
    prog.validate(N)

    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:N]), ("x",))
    x = jnp.asarray(np.random.RandomState(sum(offsets)).randn(N, N * 4, 8),
                    jnp.float32)

    outs = {}
    for backend in ("xla", "pallas"):
        f = jax.jit(shard_map(
            lambda xs, b=backend: execute(prog, xs[0], axis="x", backend=b)[None],
            mesh=mesh, in_specs=P("x", None, None),
            out_specs=P("x", None, None), check_vma=False))
        outs[backend] = np.asarray(f(x))

    # both executors agree...
    np.testing.assert_allclose(outs["xla"], outs["pallas"], rtol=1e-5)
    # ...and match the declared semantics
    chunks = np.asarray(x).reshape(N, N, 4, 8)
    for r in range(N):
        want = chunks[r, r].copy()
        for i in offsets:
            want += chunks[(r - i) % N, r]
        np.testing.assert_allclose(outs["xla"][r], want, rtol=1e-5)
