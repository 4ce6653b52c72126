"""HierarchicalCommunicator / HierarchicalPlan: bit-equivalence of the
composed RS(local) -> AR(node) -> AG(local) replay against the flat
single-axis AllReduce on a 4x4 mesh, JSON round-trip through
api.load_plan, the padding path, the single-axis fallback, and the
compile-once cache contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import api
from repro.core import selector as sel
from repro.core.comm import (Communicator, HierarchicalCommunicator,
                             HierarchicalPlan)

L, M = 4, 4  # local x node


def _data(rows, cols, seed=7):
    """Integer-valued float32 payloads: sums are exact, so reduction
    order cannot blur the bit-for-bit hier-vs-flat comparison."""
    return jnp.asarray(np.random.default_rng(seed).integers(
        -8, 8, (M, L, rows, cols)).astype(np.float32))


def _run_hier(plan, x, mesh4x4):
    f = jax.jit(shard_map(
        lambda xs: plan(xs[0, 0])[None, None], mesh=mesh4x4,
        in_specs=P("node", "local", None, None),
        out_specs=P("node", "local", None, None), check_vma=False))
    return np.asarray(f(x))[0, 0]


def _run_flat(plan, x, mesh16):
    f = jax.jit(shard_map(
        lambda xs: plan(xs[0])[None], mesh=mesh16,
        in_specs=P("x", None, None), out_specs=P("x", None, None),
        check_vma=False))
    return np.asarray(f(x.reshape(L * M, *x.shape[2:])))[0]


# ---------------------------------------------------------------------------
# the acceptance property: hierarchical == flat single-axis, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows", [8, 13])   # 13: pad path (not % L == 0)
def test_hierarchical_matches_flat_single_axis(mesh4x4, mesh16, rows):
    cols = 32
    hc = HierarchicalCommunicator("local", "node", local_n=L, node_n=M)
    plan = hc.compile((rows, cols), jnp.float32)
    x = _data(rows, cols)
    want = np.asarray(x).sum(axis=(0, 1))

    got = _run_hier(plan, x, mesh4x4)
    np.testing.assert_array_equal(got, want)
    assert plan.pad == (-rows) % L

    flat = Communicator("x", n=L * M).compile(
        "all_reduce", (rows, cols), jnp.float32)
    ref = _run_flat(flat, x, mesh16)
    np.testing.assert_array_equal(got, ref)


def test_hierarchical_plan_json_round_trip(mesh4x4):
    """The serialized artifact (kind="hierarchical_plan") reloads via
    api.load_plan, verifies clean, and replays bit-identically."""
    hc = HierarchicalCommunicator("local", "node", local_n=L, node_n=M)
    compiled = hc.compile((8, 16), jnp.float32)
    loaded = api.load_plan(compiled.to_json())
    assert isinstance(loaded, HierarchicalPlan)
    assert loaded.algo == compiled.algo
    assert sorted(loaded.phases) == ["ag", "ar", "rs"]
    assert not api.verify_plan(loaded).findings

    x = _data(8, 16, seed=11)
    got = _run_hier(loaded, x, mesh4x4)
    np.testing.assert_array_equal(got, np.asarray(x).sum(axis=(0, 1)))


def test_single_axis_fallback_is_flat_plan(mesh4x4):
    """node_axis=None (and node_n=1) degrade to ONE flat plan on the
    local communicator — and still round-trip through load_plan."""
    flat_hc = HierarchicalCommunicator("local", local_n=L)
    plan = flat_hc.compile((8, 16), jnp.float32)
    assert list(plan.phases) == ["flat"]
    assert plan.flat_plan is not None and plan.pad == 0

    hc1 = HierarchicalCommunicator("local", "node", local_n=L, node_n=1)
    assert list(hc1.compile((8, 16), jnp.float32).phases) == ["flat"]

    loaded = api.load_plan(plan.to_json())
    assert list(loaded.phases) == ["flat"]
    x = _data(8, 16, seed=3)

    def f(xs):
        return loaded(xs[0, 0])[None, None]

    y = jax.jit(shard_map(
        f, mesh=mesh4x4, in_specs=P("node", "local", None, None),
        out_specs=P("node", "local", None, None), check_vma=False))(x)
    # flat over the LOCAL axis only: sums within each node row
    np.testing.assert_array_equal(
        np.asarray(y)[0, 0], np.asarray(x).sum(axis=1)[0])


def test_compile_once_cache_and_shape_guard():
    hc = HierarchicalCommunicator("local", "node", local_n=L, node_n=M)
    p1 = hc.compile((8, 16), jnp.float32)
    p2 = hc.compile((8, 16), jnp.float32)
    assert p1 is p2
    assert hc.stats == {"compiles": 1, "hits": 1}
    with pytest.raises(ValueError, match="compiled for shape"):
        p1(jnp.zeros((4, 16), jnp.float32))


def test_modeled_fabric_hierarchy_beats_flat_dcn():
    """On the ICI x DCN model the composition crosses DCN with 1/L of
    the bytes — the analytic estimate must beat the flat plan that pays
    DCN end-to-end (the cross_hw.py acceptance point)."""
    hc = HierarchicalCommunicator("local", "node", local_n=L, node_n=M)
    hier = hc.compile((1024, 256), jnp.float32)
    flat = Communicator("fx", n=L * M, link=sel.DCN).compile(
        "all_reduce", (1024, 256), jnp.float32)
    assert hier.estimate_us < flat.estimate_us
    card = hier.cost_card()
    assert card["axes"] == ["local", "node"]
    assert set(card["phases"]) == {"rs", "ar", "ag"}
