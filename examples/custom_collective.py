"""The paper's core user story (§4.3): author a *custom* collective for
your workload in the DSL, validate it, and register it with the
selector — without touching the library.

Here: a broadcast-reduce ("one-shot AllReduce with a root hop") that
performs better than ring for tiny messages on a 2-hop-max topology:
every rank puts to the root's slots, the root reduces, then puts the
result back to every rank. Two rounds total, root-bottlenecked — a
deliberately non-library algorithm to show the declaration surface.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import selector
from repro.core.dsl import CONST, PEER, RANK, Program
from repro.core.executor import execute

N = 8


def rooted_allreduce(n: int, root: int = 0) -> Program:
    p = Program("rooted_ar", chunks=dict(input=1, scratch=n, output=1))
    # round 1: everyone (incl. root's self-copy) stages into root's slots
    p.local_copy(("scratch", RANK), ("input", 0))
    with p.round():
        for i in range(1, n):
            p.put(src=("input", 0), dst=("scratch", RANK), to=PEER(+i))
    with p.round():
        for i in range(1, n):
            p.wait(("scratch", PEER(+i)), frm=PEER(+i))
    # every rank reduces its gathered slots (symmetric keeps the program
    # SPMD; a root-only reduce + result broadcast is equally expressible)
    del root
    p.local_reduce(("output", 0),
                   [("scratch", RANK)] +
                   [("scratch", PEER(+i)) for i in range(1, n)])
    return p.freeze()


def main():
    mesh = Mesh(np.asarray(jax.devices()[:N]), ("x",))
    prog = rooted_allreduce(N)
    prog.validate(N)
    print(prog)
    print("stats:", prog.comm_stats(N, chunk_bytes=1024))

    x = jnp.asarray(np.random.RandomState(0).randn(N, 16, 128), jnp.float32)
    want = x.sum(axis=0)
    for backend in ("xla", "pallas"):
        f = jax.jit(shard_map(
            lambda xs, b=backend: execute(prog, xs[0], axis="x", backend=b)[None],
            mesh=mesh, in_specs=P("x", None, None),
            out_specs=P("x", None, None), check_vma=False))
        y = f(x)
        err = float(jnp.max(jnp.abs(y[0] - want)))
        print(f"executor={backend:7s} max_err={err:.2e}")

    # compare against the library algorithms under the α-β model
    for nbytes in (1 << 10, 1 << 16, 1 << 20):
        st = prog.comm_stats(N, max(nbytes, 1))
        mine = selector.ICI.time_us(st["comm_rounds"], st["wire_bytes_per_rank"])
        lib = selector.choose("all_reduce", n=N, nbytes=nbytes)
        lib_t = selector.estimate_us(lib, N, nbytes)
        print(f"{nbytes:>8d}B  rooted={mine:8.1f}us  library[{lib}]={lib_t:8.1f}us")


if __name__ == "__main__":
    main()
