"""Communicator + ExecutionPlan walkthrough — compile once, execute many.

The paper's production story (§4.4, §5.2): a deployment sets up a
communicator, compiles its collective plans ONCE, and replays them
every step. This example walks the whole surface on an emulated 8-chip
node:

1. build a Communicator (axis, link model, defaults);
2. compile an ExecutionPlan and inspect its cost card;
3. execute the plan inside shard_map (pure replay — no re-planning);
4. dump the plan to JSON and reload it (MSCCL++ plan-file shape);
5. install a TuningTable and watch the algorithm choice change;
6. fit α/β link constants from BENCH_collectives.json, if present.

    python examples/communicator.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import selector as sel
from repro.core.comm import Communicator, ExecutionPlan

N = 8
mesh = Mesh(np.asarray(jax.devices()[:N]), ("x",))
x = jnp.asarray(np.random.RandomState(0).randn(N, 128, 256), jnp.float32)
want = x.sum(axis=0)

# -- 1. a communicator: the init-once planning object ------------------------
comm = Communicator("x", n=N, backend="xla")
print(f"[comm] {comm}")

# -- 2. compile a plan, inspect the cost card --------------------------------
plan = comm.compile("all_reduce", (128, 256), jnp.float32)
print(f"[plan] {plan}")
print(f"[plan] cost card: {plan.cost_card()}")

# -- 3. execute it (inside shard_map) — zero re-planning ---------------------
f = jax.jit(shard_map(lambda xs: plan(xs[0])[None], mesh=mesh,
                      in_specs=P("x", None, None),
                      out_specs=P("x", None, None), check_vma=False))
for step in range(3):           # "every decode step" in miniature
    out = f(x)
err = float(jnp.max(jnp.abs(out[0] - want)))
print(f"[plan] executed 3x, max_err={err:.2e}, cache stats={comm.stats}")

# comm.all_reduce is compile-or-hit-cache: same key -> same plan object
g = jax.jit(shard_map(lambda xs: comm.all_reduce(xs[0])[None], mesh=mesh,
                      in_specs=P("x", None, None),
                      out_specs=P("x", None, None), check_vma=False))
g(x)
print(f"[comm] after comm.all_reduce with the same key: stats={comm.stats} "
      f"(hits grew, compiles did not)")

# -- 4. serialize / reload (the MSCCL++ execution-plan-file shape) -----------
plan_path = pathlib.Path("/tmp/repro_allreduce_plan.json")
plan_path.write_text(plan.to_json())
plan2 = ExecutionPlan.from_json(plan_path.read_text())
f2 = jax.jit(shard_map(lambda xs: plan2(xs[0])[None], mesh=mesh,
                       in_specs=P("x", None, None),
                       out_specs=P("x", None, None), check_vma=False))
same = bool(jnp.array_equal(f2(x), out))
print(f"[json] wrote {plan_path} ({plan_path.stat().st_size} bytes); "
      f"reloaded plan bit-identical: {same}")

# -- 5. deployment tuning: a table overrides the cost model ------------------
tuned = Communicator("x", n=N, backend="xla", table=sel.TuningTable(
    entries=[("all_reduce", 1 << 30, "allreduce_ring")]))
p_tuned = tuned.compile("all_reduce", (128, 256), jnp.float32)
print(f"[tuning] table forces {p_tuned.algo} where the model picked "
      f"{plan.algo}")

# -- 6. fitted link constants from the bench record --------------------------
bench_path = pathlib.Path(__file__).resolve().parent.parent \
    / "BENCH_collectives.json"
if bench_path.exists():
    payload = json.loads(bench_path.read_text())
    fitted = sel.fit_link_model(payload)
    print(f"[fit] measured constants from {bench_path.name}: "
          f"alpha={fitted.alpha_us:.2f}us beta={fitted.beta_GBps:.2f}GB/s "
          f"(guessed: alpha={sel.ICI.alpha_us}us beta={sel.ICI.beta_GBps}GB/s)")
    comm.load_bench_tuning(payload)
    print(f"[fit] installed on communicator: {len(comm.table.entries)} "
          f"table entries, plan cache invalidated -> {comm}")
else:
    print(f"[fit] no {bench_path.name}; run benchmarks/run.py --json first")
