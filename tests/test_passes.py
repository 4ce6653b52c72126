"""Optimizer pass pipeline: semantics preservation (bit-equivalence of
every REGISTRY program at every opt_level), per-pass instruction-count
contracts, and the vectorized executor's collective trace counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import algorithms as algos
from repro.core import passes
from repro.core import selector as sel
from repro.core.dsl import Op, PEER, RANK, Program
from repro.core.executor import execute

LEVELS = [0, 1, 2, 3]


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("x",))


def _run_xla(prog, x, mesh, opt_level):
    def run(xs):
        return execute(prog, xs[0], axis="x", backend="xla",
                       opt_level=opt_level)[None]

    f = jax.jit(shard_map(run, mesh=mesh, in_specs=P("x", None, None),
                          out_specs=P("x", None, None), check_vma=False))
    return np.asarray(f(x))


def _count_collectives(f, *args):
    """Occurrences of each jax.lax collective primitive in the jaxpr."""
    names = ("ppermute", "all_to_all", "all_gather")
    cnt = dict.fromkeys(names, 0)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in cnt:
                cnt[eqn.primitive.name] += 1
            for sub in eqn.params.values():
                for s in (sub if isinstance(sub, (list, tuple)) else [sub]):
                    if hasattr(s, "eqns"):
                        walk(s)
                    elif hasattr(s, "jaxpr"):
                        walk(s.jaxpr)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return cnt


# ---------------------------------------------------------------------------
# semantics: every program, every level, n in {2, 4, 8} — bit-equivalent
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("name", sorted(algos.REGISTRY))
def test_optimized_bit_equivalent(name, n):
    prog = algos.REGISTRY[name](n)
    mesh = _mesh(n)
    n_in = prog.chunks[prog.in_buffer]
    # rows divisible by the level-3 split factor
    rows = n_in * 2 * passes.SPLIT_FACTOR
    x = jnp.asarray(np.random.RandomState(n).randn(n, rows, 8), jnp.float32)

    base = _run_xla(prog, x, mesh, opt_level=0)
    for level in LEVELS[1:]:
        opt = passes.optimize(prog, level, n)
        opt.validate(n)
        got = _run_xla(prog, x, mesh, opt_level=level)
        np.testing.assert_array_equal(
            got, base, err_msg=f"{name} O{level} vs O0 (n={n})")


# ---------------------------------------------------------------------------
# widened registry (PR 8): log-step algorithms vs their ring baselines,
# n in {2, 4, 8, 16}, every opt level
# ---------------------------------------------------------------------------
NEW_VS_BASELINE = [
    ("halving_rs", "ring_rs"),
    ("doubling_ag", "ring_ag"),
    ("allreduce_rd", "allreduce_ring"),
    ("swing_allreduce", "allreduce_ring"),
]


@pytest.mark.parametrize("n", [2, 4, 8, 16])
@pytest.mark.parametrize("name,baseline", NEW_VS_BASELINE)
def test_new_algorithms_match_ring_baselines(name, baseline, n):
    """Each log-step algorithm computes the same collective as the ring
    family it competes against in the selector. Integer-valued payloads
    keep float sums exact, so a different reduction order cannot blur
    the bit-for-bit comparison at any opt level."""
    prog, ref = algos.REGISTRY[name](n), algos.REGISTRY[baseline](n)
    assert ref.chunks[ref.in_buffer] == prog.chunks[prog.in_buffer]
    mesh = _mesh(n)
    n_in = prog.chunks[prog.in_buffer]
    rows = n_in * 2 * passes.SPLIT_FACTOR
    x = jnp.asarray(np.random.RandomState(n).randint(
        -8, 8, (n, rows, 8)), jnp.float32)

    want = _run_xla(ref, x, mesh, opt_level=0)
    for level in LEVELS:
        got = _run_xla(prog, x, mesh, opt_level=level)
        np.testing.assert_array_equal(
            got, want, err_msg=f"{name} O{level} vs {baseline} O0 (n={n})")


# ---------------------------------------------------------------------------
# per-pass instruction-count contracts
# ---------------------------------------------------------------------------
def test_coalesce_merges_allpairs_round():
    """allpairs_rs(8): the 7-put fan-out round fuses into ONE
    multi-chunk put instruction."""
    p = passes.coalesce_puts(algos.allpairs_rs(8), 8)
    puts = [i for i in p.instructions() if i.op is Op.PUT]
    assert len(puts) == 1
    assert len(puts[0].put_triples()) == 7
    assert p.comm_stats(8, 1)["put_instrs"] == 1
    assert p.comm_stats(8, 1)["puts_per_rank"] == 7  # bytes unchanged


def test_coalesce_2pa_both_phases():
    p = passes.coalesce_puts(algos.allreduce_2pa(8), 8)
    assert p.comm_stats(8, 1)["put_instrs"] == 2       # RS + AG rounds
    assert p.comm_stats(8, 1)["puts_per_rank"] == 14


def test_coalesce_leaves_ring_alone():
    """Ring rounds hold one put each — nothing to fuse at O2."""
    p = passes.coalesce_puts(algos.ring_rs(8), 8)
    st = algos.ring_rs(8).comm_stats(8, 1)
    assert p.comm_stats(8, 1)["put_instrs"] == st["put_instrs"]


def test_batch_syncs_one_wait_per_round():
    p = passes.batch_syncs(algos.allpairs_rs(8))
    st = p.comm_stats(8, 1)
    assert st["sync_steps"] == 1                       # was 7
    assert algos.allpairs_rs(8).comm_stats(8, 1)["sync_steps"] == 7
    waits = [i for i in p.instructions() if i.op is Op.WAIT]
    assert len(waits[0].wait_chunks()) == 7


def test_eliminate_dead_copy_and_scratch():
    p = Program("dead", chunks=dict(input=2, scratch=2, junk=2, output=1))
    p.local_copy(("junk", 0), ("input", 0))        # never read -> dead
    p.local_copy(("scratch", 0), ("scratch", 0))   # self-copy -> dead
    p.local_copy(("output", 0), ("input", 1))      # live
    p.freeze()
    q = passes.eliminate_dead(p)
    assert len(q.instructions()) == 1
    assert "junk" not in q.chunks                  # buffer dropped too
    assert q.chunks["output"] == 1


def test_eliminate_dead_cascades():
    """Killing a dead buffer's writer can orphan its producer chain."""
    p = Program("chain", chunks=dict(input=1, a=1, b=1, output=1))
    p.local_copy(("a", 0), ("input", 0))
    p.local_copy(("b", 0), ("a", 0))               # b never read
    p.local_copy(("output", 0), ("input", 0))
    p.freeze()
    q = passes.eliminate_dead(p)
    assert len(q.instructions()) == 1
    assert set(q.chunks) == {"input", "output"}


def test_split_chunks_ring_shape():
    S = passes.SPLIT_FACTOR
    base = algos.ring_ag(4)
    p = passes.split_chunks(base, S)
    p.validate(4)
    assert p.chunks == {b: k * S for b, k in base.chunks.items()}
    st0, st1 = base.comm_stats(4, 2 * S), p.comm_stats(4, 2)
    assert st1["puts_per_rank"] == st0["puts_per_rank"] * S
    assert st1["wire_bytes_per_rank"] == st0["wire_bytes_per_rank"]
    # round structure is preserved (streams interleave, not serialize)
    assert st1["comm_rounds"] == st0["comm_rounds"]


def test_split_then_coalesce_refuses_instruction_growth():
    """O3 = split + coalesce: sub-chunk streams fuse back into one
    multi-chunk put per round — finer DMAs at the same instr count."""
    base = algos.ring_ag(8)
    p = passes.optimize(base, 3, 8)
    st0 = base.comm_stats(8, 2)
    st = p.comm_stats(8, 1)
    assert st["put_instrs"] == st0["put_instrs"]
    assert st["puts_per_rank"] == st0["puts_per_rank"] * passes.SPLIT_FACTOR
    assert st["sync_steps"] <= st0["sync_steps"]


def test_optimize_levels_are_monotone_in_instrs():
    for name in algos.REGISTRY:
        base = len(algos.REGISTRY[name](8).instructions())
        l1 = len(passes.optimize(algos.REGISTRY[name](8), 1, 8).instructions())
        l2 = len(passes.optimize(algos.REGISTRY[name](8), 2, 8).instructions())
        assert base >= l1 >= l2, name


def _run_custom(prog, n, opt_level, seed=0):
    mesh = _mesh(n)
    n_in = prog.chunks[prog.in_buffer]
    x = jnp.asarray(
        np.random.RandomState(seed).randn(n, n_in * 2, 4), jnp.float32)
    return _run_xla(prog, x, mesh, opt_level)


def test_coalesce_refuses_static_src_aliasing_fanout():
    """A fan-out round whose puts READ a statically-indexed chunk of the
    buffer the round WRITES must not fuse into one all_gather: the
    reference lowering forwards values delivered earlier in the round."""
    n = 4
    p = Program("alias_fanout", chunks=dict(input=1, b=n, output=n))
    p.local_copy(("b", 0), ("input", 0))
    with p.round():
        for i in range(1, n):
            p.put(src=("b", 0), dst=("b", RANK), to=PEER(+i))
    for c in range(n):
        p.local_copy(("output", c), ("b", c))
    p.freeze()
    np.testing.assert_array_equal(_run_custom(p, n, 2), _run_custom(p, n, 0))


def test_coalesce_refuses_same_shift_read_after_write():
    """Consecutive same-shift puts where put k+1 reads the chunk put k
    delivers must stay sequential (one stacked ppermute would send the
    stale pre-round value)."""
    n = 4
    p = Program("alias_chain", chunks=dict(input=n, b=n, output=n))
    p.local_copy(("b", 0), ("input", 0))
    with p.round():
        p.put(src=("b", 0), dst=("b", 1), to=PEER(+1))
        p.put(src=("b", 1), dst=("b", 2), to=PEER(+1))  # reads put 1's dst
    for c in range(n):
        p.local_copy(("output", c), ("b", c))
    p.freeze()
    np.testing.assert_array_equal(_run_custom(p, n, 2), _run_custom(p, n, 0))
    # disjoint chunks DO still fuse
    q = Program("no_alias", chunks=dict(input=n, output=n))
    with q.round():
        q.put(src=("input", 0), dst=("output", 0), to=PEER(+1))
        q.put(src=("input", 1), dst=("output", 1), to=PEER(+1))
    q.freeze()
    opt = passes.coalesce_puts(q, n)
    assert opt.comm_stats(n, 1)["put_instrs"] == 1


# ---------------------------------------------------------------------------
# trace counts: the acceptance contract for the vectorized lowering
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["allpairs_rs", "allreduce_1pa"])
def test_vectorized_lowering_collective_counts(name, mesh8):
    prog = algos.REGISTRY[name](8)
    n_in = prog.chunks[prog.in_buffer]
    x = jnp.ones((8, n_in * 4, 8), jnp.float32)

    def make(level):
        def run(xs):
            return execute(prog, xs[0], axis="x", backend="xla",
                           opt_level=level)[None]
        return jax.jit(shard_map(run, mesh=mesh8,
                                 in_specs=P("x", None, None),
                                 out_specs=P("x", None, None),
                                 check_vma=False))

    seed = _count_collectives(make(0), x)
    opt = _count_collectives(make(2), x)
    assert seed["ppermute"] == 7                  # one per chunk-put
    assert opt["ppermute"] <= 2                   # fused fan-out round
    assert sum(opt.values()) <= 2                 # ... into ONE collective


def test_vectorized_ring_stacks_subchunk_ppermutes(mesh8):
    """O3 ring: S sub-chunk puts per round ride ONE stacked ppermute —
    the ppermute count must not grow with the split factor."""
    prog = algos.ring_ag(8)
    x = jnp.ones((8, 4 * passes.SPLIT_FACTOR, 8), jnp.float32)

    def make(level):
        def run(xs):
            return execute(prog, xs[0], axis="x", backend="xla",
                           opt_level=level)[None]
        return jax.jit(shard_map(run, mesh=mesh8,
                                 in_specs=P("x", None, None),
                                 out_specs=P("x", None, None),
                                 check_vma=False))

    assert _count_collectives(make(0), x)["ppermute"] == 7
    assert _count_collectives(make(3), x)["ppermute"] == 7


# ---------------------------------------------------------------------------
# cost model sees the post-fusion program
# ---------------------------------------------------------------------------
def test_estimate_us_uses_post_fusion_stats():
    # sync batching is visible in the α term: the unoptimized 1PA pays
    # sync_us for each of its 7 per-chunk waits, the batched form pays
    # one round cost only
    a0 = sel.estimate_us("allreduce_1pa", 8, 1 << 10, opt_level=0)
    a2 = sel.estimate_us("allreduce_1pa", 8, 1 << 10, opt_level=2)
    assert a0 > a2
    assert a0 - a2 == pytest.approx(6 * sel.ICI.sync_us)
    # paper §5.1 policy unchanged under the default pipeline
    assert sel.choose("all_reduce", n=8, nbytes=1 << 10) == "allreduce_1pa"
    assert sel.choose("all_reduce", n=8, nbytes=1 << 30) == "allreduce_ring"


def test_o3_falls_back_when_rows_not_divisible(mesh8):
    """all_gather at O3 with rows not divisible by the split chunk
    count must fall back to the un-split pipeline, not crash: the
    gathered output layout embeds the chunk grid, so it cannot pad."""
    from repro.core import api

    x = jnp.asarray(np.random.RandomState(9).randn(8, 3, 4), jnp.float32)

    def f(xs):
        return api.all_gather(xs[0], "x", backend="xla",
                              algo="ring_ag", opt_level=3)[None]

    y = jax.jit(shard_map(f, mesh=mesh8, in_specs=P("x", None, None),
                          out_specs=P("x", None, None), check_vma=False))(x)
    want = np.asarray(x).reshape(24, 4)
    np.testing.assert_allclose(np.asarray(y)[0], want, rtol=1e-6)


def test_split_program_validates_and_pads_through_api(mesh8):
    """all_reduce at O3 with rows not divisible by the split chunk
    count exercises the post-optimization padding path."""
    from repro.core import api

    x = jnp.asarray(np.random.RandomState(7).randn(8, 13, 16), jnp.float32)

    def f(xs):
        return api.all_reduce(xs[0], "x", backend="xla",
                              algo="allreduce_ring", opt_level=3)[None]

    y = jax.jit(shard_map(f, mesh=mesh8, in_specs=P("x", None, None),
                          out_specs=P("x", None, None), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(x.sum(0)),
                               rtol=1e-5, atol=1e-5)
