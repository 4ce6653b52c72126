"""Quickstart: the MSCCL++ API levels on an emulated 8-chip node.

    python examples/quickstart.py

1. Collective API  — drop-in all_reduce, algorithm auto-selected
                     (thin wrapper over a process-default Communicator);
2. Communicator    — the production surface: compile an ExecutionPlan
                     once, inspect its cost card, replay it every step
                     (see examples/communicator.py for the full tour);
3. DSL API         — the same algorithm declared in 20 lines and run on
                     both executors (ppermute and Pallas channels);
4. Primitive API   — the raw put/signal/wait kernel (see
                     src/repro/kernels/ for production versions).
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import api, selector
from repro.core.algorithms import allreduce_2pa
from repro.core.dsl import PEER, RANK, Program
from repro.core.executor import execute

N = 8
mesh = Mesh(np.asarray(jax.devices()[:N]), ("x",))
x = jnp.asarray(np.random.RandomState(0).randn(N, 128, 256), jnp.float32)
want = x.sum(axis=0)

# -- 1. Collective API ------------------------------------------------------
for backend in ("xla_native", "xla", "pallas"):
    f = jax.jit(shard_map(
        lambda xs, b=backend: api.all_reduce(xs[0], "x", backend=b)[None],
        mesh=mesh, in_specs=P("x", None, None), out_specs=P("x", None, None),
        check_vma=False))
    out = f(x)
    err = float(jnp.max(jnp.abs(out[0] - want)))
    algo = selector.choose("all_reduce", n=N, nbytes=x[0].nbytes)
    print(f"[collective] backend={backend:10s} algo={algo:16s} max_err={err:.2e}")

# -- 2. Communicator: compile once, execute many -----------------------------
from repro.core.comm import Communicator

comm = Communicator("x", n=N, backend="xla")
plan = comm.compile("all_reduce", (128, 256), x.dtype)
print(f"[comm] compiled {plan}")
f = jax.jit(shard_map(lambda xs: plan(xs[0])[None], mesh=mesh,
                      in_specs=P("x", None, None),
                      out_specs=P("x", None, None), check_vma=False))
for _ in range(3):
    out = f(x)                      # pure plan replay — no re-planning
err = float(jnp.max(jnp.abs(out[0] - want)))
print(f"[comm] 3 executions, max_err={err:.2e}, stats={comm.stats}")

# -- 3. DSL API: declare a custom one-hop reduce-scatter ---------------------
prog = Program("my_rs", chunks=dict(input=N, scratch=N, output=1))
with prog.round():
    for i in range(1, N):
        prog.put(src=("input", PEER(+i)), dst=("scratch", RANK), to=PEER(+i))
with prog.round():
    for i in range(1, N):
        prog.wait(("scratch", PEER(+i)), frm=PEER(+i))
prog.local_reduce(("output", 0),
                  [("input", RANK)] + [("scratch", PEER(+i)) for i in range(1, N)])
prog.freeze().validate(N)
print(f"[dsl] program:\n{prog}")
print(f"[dsl] comm stats @1KB chunks: {prog.comm_stats(N, 1024)}")

for backend in ("xla", "pallas"):
    f = jax.jit(shard_map(
        lambda xs, b=backend: execute(prog, xs[0], axis="x", backend=b)[None],
        mesh=mesh, in_specs=P("x", None, None), out_specs=P("x", None, None),
        check_vma=False))
    y = f(x.reshape(N, N * 16, 256))          # (N, 16, 256): rank's chunk
    ref = x.reshape(N, N, 16, 256).sum(axis=0)  # (N, 16, 256)
    err = float(jnp.max(jnp.abs(y - ref)))
    print(f"[dsl] executor={backend:7s} reduce-scatter max_err={err:.2e}")

# -- 4. algorithm selection table --------------------------------------------
print("\n[selector] AllReduce policy (v5e ICI):")
for exp in (10, 13, 16, 19, 22, 26, 30):
    algo = selector.choose("all_reduce", n=N, nbytes=1 << exp)
    print(f"   {1 << exp:>12d} B -> {algo}")
