"""The reductions from traces to metrics, and the operation and byte
counts, on small synthetic inputs and small shapes."""
import functools
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import counts  # noqa: E402
import harness as hz  # noqa: E402
import tracing  # noqa: E402
from small import small_cell  # noqa: E402


def _model(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())["model"]


# -- trace reductions ------------------------------------------------------
OPS = [(0.0, 1.0, "a"), (0.5, 2.0, "b"), (3.0, 4.0, "a"), (6.0, 7.0, "c")]


def test_union_of_intervals():
    assert tracing.union_seconds(OPS, 0.0, 8.0) == pytest.approx(4.0)
    assert tracing.union_seconds(OPS, 1.5, 3.5) == pytest.approx(1.0)
    assert tracing.union_seconds([], 0.0, 1.0) == 0.0


def test_idle_gaps_and_attribution():
    gaps = tracing.idle_gaps(OPS, 0.0, 8.0)
    assert gaps == [(2.0, 3.0), (4.0, 6.0), (7.0, 8.0)]
    spans = [(1.9, 3.2, "tick"), (3.9, 6.1, "wait_arrival"),
             (4.5, 5.5, "submit")]
    named = tracing.attribute_gaps(gaps, spans)
    # the 4-6 gap's midpoint lies in both spans: the innermost wins
    assert named == pytest.approx({"tick": 1.0, "submit": 2.0,
                                   "other": 1.0})


def test_top_ops_clipped_to_window():
    assert tracing.top_ops(OPS, 0.0, 3.5, k=2) == [["b", 1.5], ["a", 1.5]] \
        or tracing.top_ops(OPS, 0.0, 3.5, k=2) == [["a", 1.5], ["b", 1.5]]
    assert tracing.top_ops(OPS, 0.0, 8.0)[0] == ["a", 2.0]


def test_per_program_device_time_by_issue_order():
    mods = [(0.0, 0.3, "jit_step"), (0.3, 0.31, "jit_scatter"),
            (0.4, 0.5, "jit_step"), (0.6, 0.9, "jit_step(7)")]
    got = tracing.label_modules(mods, 0.0, 1.0,
                                ["prefill", "decode", "prefill"], "jit_step")
    assert [k for k, _ in got] == ["prefill", "decode", "prefill"]
    assert [s for _, s in got] == pytest.approx([0.3, 0.1, 0.3])
    assert tracing.label_modules(mods, 0.0, 1.0, ["decode"], "jit_step") is None


# -- counts ----------------------------------------------------------------
def test_published_parameter_counts():
    m = _model("qwen3-1.7b")
    assert m["tie_embeddings"]
    assert counts.param_count(m) == 1_720_574_976
    # an untied head adds a second vocabulary x width table
    assert counts.param_count(dict(m, tie_embeddings=False)) == 2_031_739_904


#: the cell's own configuration (tied), and with a separate head
@pytest.mark.parametrize("tied", [True, False],
                         ids=["qwen3-1.7b.chat", "untied"])
def test_counts_match_the_served_tree(tied):
    """Parameter count against the program's own tree at a small size."""
    from repro.models import transformer as tf

    cell = small_cell("qwen3-1.7b.chat", tie_embeddings=tied)
    m = cell.config["model"]
    shapes = jax.eval_shape(functools.partial(
        tf.init_params, hz.model_config(m)), jax.random.key(0))
    assert counts.param_count(m) == sum(x.size for x in jax.tree.leaves(shapes))


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(getattr(x, "jaxpr", None), "eqns"):
                yield x.jaxpr


def _dot_flops(fn, *args):
    """2 x contracted x output elements, summed over the dot_generals of
    the traced function (scan bodies counted once per iteration)."""
    def walk(jaxpr, mult):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (lc, _), _ = eqn.params["dimension_numbers"]
                a = eqn.invars[0].aval
                k = int(np.prod([a.shape[i] for i in lc]))
                n += 2 * k * int(np.prod(eqn.outvars[0].aval.shape)) * mult
            for sub in _subjaxprs(eqn):
                m = eqn.params.get("length", 1) if eqn.primitive.name == "scan" else 1
                n += walk(sub, mult * m)
        return n
    return walk(jax.make_jaxpr(fn)(*args).jaxpr, 1)


#: the cell's own configuration (tied), and with a separate head
@pytest.mark.parametrize("tied", [True, False],
                         ids=["qwen3-1.7b.chat", "untied"])
def test_token_flops_match_the_reference_matmuls(tied):
    """One token (context 1, with the head) through the reference: its
    matrix products are what ``token_flops`` counts."""
    cell = small_cell("qwen3-1.7b.chat", tie_embeddings=tied)
    m = cell.config["model"]
    ref = hz.reference(cell.config)
    from repro.models import transformer as tf
    shapes = jax.eval_shape(functools.partial(
        tf.init_params, hz.model_config(m)), jax.random.key(0))
    w = jax.jit(lambda k: ref.make_weights(shapes, k))(jax.random.key(0))
    got = _dot_flops(lambda w, t: ref.forward(w, m, t), w,
                     jnp.zeros((1,), jnp.int32))
    assert counts.token_flops(m, 1, True) == got


def test_prefill_flops_sum_token_flops():
    m = small_cell("qwen3-1.7b.chat").config["model"]
    want = sum(counts.token_flops(m, c, False) for c in range(5, 12))
    assert counts.prefill_flops(m, 4, 7) == want
    # attention grows with the context, one head-dim product per key
    d = counts.token_flops(m, 10, False) - counts.token_flops(m, 9, False)
    assert d == 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]


def test_decode_bytes_small():
    m = small_cell("qwen3-1.7b.chat").config["model"]
    kv = m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * 2
    assert counts.decode_bytes(m, [3, 10]) == counts.param_count(m) * 2 + 13 * kv
    # a tied embedding is read once, as the head
    assert m["tie_embeddings"]
    untied = dict(m, tie_embeddings=False)
    assert counts.decode_bytes(untied, [3]) - counts.decode_bytes(m, [3]) == \
        m["vocab"] * m["d_model"] * 2
    # a window caps the positions a row reads
    assert counts.decode_bytes(dict(m, window=8), [3, 10]) == \
        counts.param_count(m) * 2 + 11 * kv
