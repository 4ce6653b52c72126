"""Paper Fig. 8/9 analogue: AllReduce / AllGather across message sizes,
algorithms (1PA / 2PA / ring) and backends — plus the optimizer
before/after breakdown this repo's pass pipeline adds.

Three backends per point:
  xla_native — jax.lax collectives (the NCCL-role baseline),
  xla        — our DSL algorithms lowered via the vectorized executor,
  pallas     — our DSL algorithms as channel-primitive TPU kernels
               (interpret-emulated here; CPU wall time is NOT TPU time).

Because the container has no TPU, each point reports BOTH the measured
emulation wall time (relative structure only) and the α-β model
prediction for v5e ICI (the number the selector uses). The selection
column shows which algorithm the tuning layer picks — reproducing the
paper's size-dependent crossovers is the point of the figure.

``bench_opt_levels`` measures the same DSL program twice on the xla
backend — reference per-chunk lowering (opt_level=0) vs the optimizer
pipeline (opt_level=2) — and reports wall time, DSL instruction
counts, and lowered collective-primitive counts per point, i.e. the
"gain breakdown" of the pass pipeline itself. ``json_payload``
packages everything for ``benchmarks/run.py --json`` →
``BENCH_collectives.json``.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import algorithms as algos
from repro.core import api as coll_api
from repro.core import passes
from repro.core import selector as sel
from repro.core.executor import execute

SIZES = [1 << 10, 1 << 14, 1 << 17, 1 << 20, 1 << 24]  # bytes
OPT_SIZES = [1 << 14, 1 << 17, 1 << 20]                # opt A/B points
# all-pairs family (drives the O0->O2 geomean headline) + the ring
# variants, so every selectable collective has >= 2 measured candidates
# per size — the coverage TuningTable.from_bench needs to build entries
# for all_gather / reduce_scatter, not just all_reduce.
ALLPAIRS_ALGOS = ["allpairs_rs", "allpairs_ag", "allreduce_1pa",
                  "allreduce_2pa", "alltoall"]
OPT_ALGOS = ALLPAIRS_ALGOS + ["ring_rs", "ring_ag"]
N = 8


def _mesh():
    return Mesh(np.asarray(jax.devices()[:N]), ("x",))


def _time(fn, *args, iters=3):
    jax.block_until_ready(fn(*args))    # one warmup call (compile+run)
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def _count_collectives(f, *args) -> int:
    """Total jax.lax collective primitives in the traced jaxpr."""
    names = {"ppermute", "all_to_all", "all_gather", "psum", "psum_scatter"}
    cnt = 0

    def walk(jaxpr):
        nonlocal cnt
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in names:
                cnt += 1
            for sub in eqn.params.values():
                for s in (sub if isinstance(sub, (list, tuple)) else [sub]):
                    if hasattr(s, "eqns"):
                        walk(s)
                    elif hasattr(s, "jaxpr"):
                        walk(s.jaxpr)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return cnt


def bench_allreduce(rows: list, points=None):
    mesh = _mesh()
    for nbytes in SIZES:
        cols = max(nbytes // 4 // 128, 1)
        x = jnp.ones((N, 128, cols), jnp.float32)

        for backend in ("xla_native", "xla", "pallas"):
            if backend == "pallas" and nbytes > (1 << 20):
                continue  # interpret emulation too slow beyond 1MB
            def run(xs, backend=backend):
                return coll_api.all_reduce(xs[0], "x", backend=backend)[None]

            f = jax.jit(shard_map(run, mesh=mesh, in_specs=P("x", None, None),
                                  out_specs=P("x", None, None),
                                  check_vma=False))
            us = _time(f, x)
            algo = sel.choose("all_reduce", n=N, nbytes=nbytes)
            pred = sel.estimate_us(algo, N, nbytes)
            rows.append(("allreduce", nbytes, backend, algo,
                         round(us, 1), round(pred, 2)))
            if points is not None:
                points.append(dict(bench="allreduce", nbytes=nbytes,
                                   backend=backend, algo=algo,
                                   opt_level=passes.DEFAULT_OPT_LEVEL,
                                   wall_us=round(us, 1),
                                   predicted_us=round(pred, 2)))


def bench_allgather(rows: list, points=None):
    mesh = _mesh()
    for nbytes in SIZES:
        cols = max(nbytes // 4 // 128 // N, 1)
        x = jnp.ones((N, 128, cols), jnp.float32)

        for backend in ("xla_native", "xla", "pallas"):
            if backend == "pallas" and nbytes > (1 << 20):
                continue
            def run(xs, backend=backend):
                return coll_api.all_gather(xs[0], "x", backend=backend)[None]

            f = jax.jit(shard_map(run, mesh=mesh, in_specs=P("x", None, None),
                                  out_specs=P("x", None, None),
                                  check_vma=False))
            us = _time(f, x)
            algo = sel.choose("all_gather", n=N, nbytes=nbytes)
            pred = sel.estimate_us(algo, N, nbytes)
            rows.append(("allgather", nbytes, backend, algo,
                         round(us, 1), round(pred, 2)))
            if points is not None:
                points.append(dict(bench="allgather", nbytes=nbytes,
                                   backend=backend, algo=algo,
                                   opt_level=passes.DEFAULT_OPT_LEVEL,
                                   wall_us=round(us, 1),
                                   predicted_us=round(pred, 2)))


def bench_opt_levels(rows: list, points=None, opt_level: int = 2):
    """Before/after the optimizer pipeline: same DSL program, xla
    backend, reference (O0) vs optimized (O`opt_level`) lowering."""
    mesh = _mesh()
    speedups = []
    for name in OPT_ALGOS:
        prog = algos.REGISTRY[name](N)
        n_in = prog.chunks[prog.in_buffer]
        for nbytes in OPT_SIZES:
            rows_pc = 8
            cols = max(nbytes // 4 // (n_in * rows_pc), 1)
            x = jnp.ones((N, n_in * rows_pc, cols), jnp.float32)

            def make(level):
                def run(xs, level=level):
                    return execute(prog, xs[0], axis="x", backend="xla",
                                   opt_level=level)[None]
                return jax.jit(shard_map(
                    run, mesh=mesh, in_specs=P("x", None, None),
                    out_specs=P("x", None, None), check_vma=False))

            f0, f1 = make(0), make(opt_level)
            us0, us1 = _time(f0, x), _time(f1, x)
            popt = passes.optimize(prog, opt_level, N)
            point = dict(
                bench="opt_compare", algo=name, nbytes=nbytes,
                backend="xla", opt_level=opt_level,
                wall_us_ref=round(us0, 1), wall_us_opt=round(us1, 1),
                speedup=round(us0 / us1, 3),
                instrs_ref=len(prog.instructions()),
                instrs_opt=len(popt.instructions()),
                collectives_ref=_count_collectives(f0, x),
                collectives_opt=_count_collectives(f1, x),
                predicted_us=round(sel.estimate_us(name, N, nbytes), 2),
            )
            if name in ALLPAIRS_ALGOS:
                speedups.append(us0 / us1)
            rows.append((f"opt_{name}", nbytes, "xla",
                         f"O0:{point['collectives_ref']}c"
                         f"->O{opt_level}:{point['collectives_opt']}c",
                         round(us0, 1), round(us1, 1)))
            if points is not None:
                points.append(point)
    geomean = float(np.exp(np.mean(np.log(speedups)))) if speedups else 1.0
    rows.append(("opt_geomean_allpairs", N, "xla",
                 f"O0->O{opt_level}", round(geomean, 3), ""))
    if points is not None:
        points.append(dict(bench="opt_geomean", n=N, opt_level=opt_level,
                           geomean_speedup=round(geomean, 3)))
    return geomean


def gain_breakdown(rows: list, points=None):
    """Paper §5.1 'Gain Breakdown': same ALGORITHM, different stacks —
    sync-step and wire-byte counts per algorithm from the DSL analyzer
    (the structural quantities behind the 1PA/2PA latency wins), shown
    pre- and post-optimizer."""
    for name in ("allreduce_1pa", "allreduce_2pa", "allreduce_ring"):
        prog = algos.REGISTRY[name](N)
        st = prog.comm_stats(N, chunk_bytes=1)
        opt = passes.optimize(prog, passes.DEFAULT_OPT_LEVEL, N)
        sto = opt.comm_stats(N, chunk_bytes=1)
        rows.append((f"stats_{name}", st["comm_rounds"], "rounds",
                     f"puts={st['puts_per_rank']}",
                     st["wire_bytes_per_rank"], st["bytes_per_rank"]))
        rows.append((f"stats_{name}_opt", sto["comm_rounds"], "rounds",
                     f"put_instrs={sto['put_instrs']}"
                     f" syncs={sto['sync_steps']}",
                     sto["wire_bytes_per_rank"], sto["bytes_per_rank"]))
        if points is not None:
            points.append(dict(bench="stats", algo=name,
                               pre=st, post=sto))


def plan_smoke(sizes=(1 << 10, 1 << 14)) -> dict:
    """Fast plan-path smoke (``run.py --smoke`` / ``check.sh --smoke``):
    drives the Communicator/ExecutionPlan pipeline end-to-end at two
    tiny sizes and asserts the compile-once contract — one selector/
    passes run per distinct key, cache hits on re-trace — so plan-path
    regressions surface per PR in seconds, not the full bench's minutes.
    """
    from repro.core import comm as comm_lib

    mesh = _mesh()
    comm = comm_lib.Communicator("x", n=N)
    points = []
    for nbytes in sizes:
        cols = max(nbytes // 4 // 128, 1)
        x = jnp.ones((N, 128, cols), jnp.float32)

        def run(xs):
            return comm.all_reduce(xs[0], backend="xla")[None]

        def jit_run():
            return jax.jit(shard_map(run, mesh=mesh,
                                     in_specs=P("x", None, None),
                                     out_specs=P("x", None, None),
                                     check_vma=False))

        us = _time(jit_run(), x)
        # a fresh jit of the same shape must hit the plan cache
        jax.block_until_ready(jit_run()(x))
        plan = comm.compile("all_reduce", (128, cols), jnp.float32,
                            backend="xla")
        points.append(dict(bench="plan_smoke", nbytes=nbytes, backend="xla",
                           algo=plan.algo, opt_level=plan.opt_level,
                           wall_us=round(us, 1),
                           predicted_us=round(plan.estimate_us, 2)))
    compiles, hits = comm.stats["compiles"], comm.stats["hits"]
    assert compiles == len(sizes), \
        f"expected {len(sizes)} plan compiles, got {compiles}"
    assert hits >= 2 * len(sizes), \
        f"expected >= {2 * len(sizes)} plan-cache hits, got {hits}"
    return dict(n=N, compiles=compiles, hits=hits, points=points)


def main(rows=None, points=None):
    rows = rows if rows is not None else []
    bench_allreduce(rows, points)
    bench_allgather(rows, points)
    bench_opt_levels(rows, points)
    gain_breakdown(rows, points)
    return rows


def json_payload() -> dict:
    """Everything ``benchmarks/run.py --json`` writes to
    ``BENCH_collectives.json``."""
    points: list = []
    main([], points)
    geo = [p for p in points if p["bench"] == "opt_geomean"]
    return dict(
        n=N,
        sizes=SIZES,
        opt_default=passes.DEFAULT_OPT_LEVEL,
        geomean_speedup_allpairs=geo[0]["geomean_speedup"] if geo else None,
        points=points,
    )
