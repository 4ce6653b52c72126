"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # not in the minimal CI image
from hypothesis import given, settings, strategies as st
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import algorithms as algos
from repro.core import selector as sel
from repro.core.dsl import CONST, PEER, RANK, IndexExpr
from repro.train import compression as comp
from repro.train import data as data_lib
from repro.train import optimizer as opt


# ---------------------------------------------------------------------------
# DSL index algebra
# ---------------------------------------------------------------------------
@given(st.integers(-64, 64), st.integers(0, 63), st.integers(2, 64))
def test_peer_eval_in_range(off, rank, n):
    assert 0 <= PEER(off)(rank % n, n) < n


@given(st.integers(-64, 64), st.integers(2, 64))
def test_peer_inverse(off, n):
    """PEER(+i) followed by PEER(-i) returns to the original rank."""
    for r in range(min(n, 8)):
        mid = PEER(off)(r, n)
        back = PEER(-off)(mid, n)
        assert back == r


@given(st.integers(0, 1000), st.integers(2, 64))
def test_const_ignores_rank(c, n):
    assert CONST(c)(0, n) == CONST(c)(n - 1, n) == c


# ---------------------------------------------------------------------------
# Algorithm programs: structural invariants for every size
# ---------------------------------------------------------------------------
@settings(deadline=None, max_examples=20)
@given(st.sampled_from(list(algos.REGISTRY)), st.integers(2, 16))
def test_programs_validate_at_any_size(name, n):
    if not sel.supports(name, n):
        # geometry-restricted entries refuse cleanly (the selector never
        # offers them at such sizes — choose() falls back to ring)
        with pytest.raises(ValueError, match="power-of-two"):
            algos.REGISTRY[name](n)
        return
    prog = algos.REGISTRY[name](n)
    prog.validate(n)


@settings(deadline=None, max_examples=20)
@given(st.integers(2, 16))
def test_allreduce_wire_bytes_ring_optimal(n):
    """Ring AllReduce wire bytes = 2(n-1)/n · message — the bandwidth
    lower bound; all-pairs must be ≥ ring for n > 2 on a torus."""
    msg = n * 1024
    ring = algos.allreduce_ring(n).comm_stats(n, msg // n)
    assert ring["wire_bytes_per_rank"] == 2 * (n - 1) * (msg // n)
    pairs = algos.allreduce_2pa(n).comm_stats(n, msg // n)
    assert pairs["wire_bytes_per_rank"] >= ring["wire_bytes_per_rank"]


@settings(deadline=None, max_examples=30)
@given(st.integers(8, 30), st.integers(2, 16))
def test_selector_is_argmin(exp, n):
    nbytes = 1 << exp
    pick = sel.choose("all_reduce", n=n, nbytes=nbytes)
    est = {a: sel.estimate_us(a, n, nbytes)
           for a in sel.CANDIDATES["all_reduce"] if sel.supports(a, n)}
    assert est[pick] == min(est.values())


def test_tuning_table_overrides_model():
    table = sel.TuningTable(entries=[("all_reduce", 1 << 20, "allreduce_ring")])
    assert sel.choose("all_reduce", n=8, nbytes=1024, table=table) == "allreduce_ring"
    # beyond the table limit, the cost model resumes
    assert sel.choose("all_reduce", n=8, nbytes=1 << 30) == "allreduce_ring"


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------
@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2**31 - 1))
def test_int8_quantization_error_bounded(seed):
    g = jnp.asarray(np.random.RandomState(seed).randn(32, 64), jnp.float32)
    payload, meta = comp.compress(g, "int8")
    back = comp.decompress(payload, meta, "int8")
    scale = np.asarray(meta[0]).max()
    assert float(jnp.max(jnp.abs(back - g))) <= scale * 0.500001


@settings(deadline=None, max_examples=10)
@given(st.integers(0, 2**31 - 1))
def test_error_feedback_residual_bounded(seed):
    """EF residual stays bounded (doesn't accumulate unboundedly)."""
    g = jnp.asarray(np.random.RandomState(seed).randn(16, 32), jnp.float32)
    r = jnp.zeros_like(g)
    for _ in range(50):
        _, r = comp.ef_roundtrip(g, r, method="int8")
    assert float(jnp.max(jnp.abs(r))) < 1.0


# ---------------------------------------------------------------------------
# data pipeline determinism (the restart contract)
# ---------------------------------------------------------------------------
@settings(deadline=None, max_examples=10)
@given(st.integers(0, 10_000), st.integers(0, 100))
def test_pipeline_is_pure_function_of_step(seed, step):
    cfg = data_lib.DataConfig(vocab=128, batch=2, seq_len=16, seed=seed)
    a = data_lib.SyntheticLM(cfg).batch_at(step)
    b = data_lib.SyntheticLM(cfg).batch_at(step)
    assert jnp.array_equal(a["tokens"], b["tokens"])
    assert jnp.array_equal(a["labels"], b["labels"])
    if step > 0:
        c = data_lib.SyntheticLM(cfg).batch_at(step - 1)
        assert not jnp.array_equal(a["tokens"], c["tokens"])


# ---------------------------------------------------------------------------
# optimizer sanity
# ---------------------------------------------------------------------------
def test_adamw_descends_quadratic():
    cfg = opt.AdamWConfig(lr=0.1, weight_decay=0.0, warmup_steps=1,
                          total_steps=100, clip_norm=1e9)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.adamw_init(params)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}
        params, state, _ = opt.adamw_update(cfg, params, grads, state)
    assert float(jnp.abs(params["w"]).max()) < 0.5


@settings(deadline=None, max_examples=10)
@given(st.floats(0.1, 10.0))
def test_clip_by_global_norm(scale):
    tree = {"a": jnp.full((4, 4), scale), "b": jnp.full((2,), -scale)}
    clipped, norm = opt.clip_by_global_norm(tree, 1.0)
    new_norm = float(opt.global_norm(clipped))
    assert new_norm <= 1.0 + 1e-4
