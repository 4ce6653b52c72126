"""Hierarchical (multi-axis) AllReduce walkthrough — docs/hierarchical.md.

The 2PH composition on an emulated 4x4 (node x local) mesh:

1. build a HierarchicalCommunicator (per-axis link models: ICI intra,
   DCN inter) and compile the RS(local) -> AR(node) -> AG(local) plan;
2. execute it inside shard_map over BOTH axes and check the sum;
3. serialize / reload via api.load_plan (kind="hierarchical_plan") and
   re-verify every nested phase program;
4. compare the modeled cost against the flat single-axis plan that
   pays DCN for every byte;
5. watch the single-axis fallback degrade to one flat plan;
6. peek at the widened n=16 registry the phases select from.

    python examples/hierarchical.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import api
from repro.core import selector as sel
from repro.core.comm import Communicator, HierarchicalCommunicator

L, M = 4, 4                      # local (intra) x node (inter)
ROWS, COLS = 128, 64

devs = jax.devices()
mesh = Mesh(np.asarray(devs[:L * M]).reshape(M, L), ("node", "local"))

# integer-valued payloads: the sum is exact in f32, so the replay can
# be compared bit-for-bit
x = jnp.asarray(np.random.default_rng(0).integers(
    -8, 8, (M, L, ROWS, COLS)).astype(np.float32))
want = np.asarray(x).sum(axis=(0, 1))

# -- 1. compile the composed plan --------------------------------------------
hc = HierarchicalCommunicator("local", "node", local_n=L, node_n=M)
plan = hc.compile((ROWS, COLS), jnp.float32)
print(f"[plan] {plan}")
print(f"[plan] phases: { {k: p.algo for k, p in plan.phases.items()} } "
      f"pad={plan.pad}")

# -- 2. execute inside shard_map over both axes ------------------------------
f = jax.jit(shard_map(lambda xs: plan(xs[0, 0])[None, None], mesh=mesh,
                      in_specs=P("node", "local", None, None),
                      out_specs=P("node", "local", None, None),
                      check_vma=False))
out = np.asarray(f(x))[0, 0]
print(f"[exec] bit-equal to the 16-rank sum: {np.array_equal(out, want)}; "
      f"cache stats={hc.stats}")

# -- 3. serialize / reload / re-verify ---------------------------------------
loaded = api.load_plan(plan.to_json())       # verifies nested programs
report = api.verify_plan(loaded)
out2 = np.asarray(jax.jit(shard_map(
    lambda xs: loaded(xs[0, 0])[None, None], mesh=mesh,
    in_specs=P("node", "local", None, None),
    out_specs=P("node", "local", None, None), check_vma=False))(x))[0, 0]
print(f"[json] round-tripped plan verifies clean ({report.summary()}) and "
      f"replays bit-identical: {np.array_equal(out2, out)}")

# -- 4. why bother: the modeled ICI x DCN comparison -------------------------
flat = Communicator("fx", n=L * M, link=sel.DCN).compile(
    "all_reduce", (ROWS, COLS), jnp.float32)
print(f"[model] flat n={L * M} on DCN: {flat.estimate_us:.1f}us "
      f"({flat.algo}) vs hierarchical {plan.estimate_us:.1f}us "
      f"({plan.algo}) -> {flat.estimate_us / plan.estimate_us:.2f}x "
      f"(only 1/{L} of the bytes cross DCN)")

# -- 5. the single-axis fallback ---------------------------------------------
flat_hc = HierarchicalCommunicator("local", local_n=L)   # no node axis
print(f"[fallback] node_axis=None -> phases="
      f"{list(flat_hc.compile((ROWS, COLS), jnp.float32).phases)}")

# -- 6. the widened registry the phases select from --------------------------
for nbytes in (1 << 17, 1 << 30):
    pick = sel.choose("all_reduce", n=16, nbytes=nbytes)
    print(f"[registry] n=16 {nbytes >> 10}KiB -> {pick}")
