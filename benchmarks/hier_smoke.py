"""n=16 multi-axis smoke: hierarchical plan compile + JSON round-trip +
replay on an emulated 4x4 (node x local) mesh, bit-checked against the
flat single-axis AllReduce at n=16.

Run as its own process (``scripts/check.sh --smoke`` does) so it owns
the device-count flag::

    XLA_FLAGS=--xla_force_host_platform_device_count=16 \
        python benchmarks/hier_smoke.py

Asserts, in seconds:

* the composed RS(local) -> AR(node) -> AG(local) replay is bit-equal
  to the flat n=16 plan AND to the plain sum (integer-valued payloads,
  so float reduction order cannot blur the comparison);
* the replayed artifact is the JSON-round-tripped plan (load_plan
  dispatch on ``kind="hierarchical_plan"``), not the compiled object;
* on the modeled ICI x DCN fabric the hierarchical estimate beats the
  flat single-axis estimate (the cross_hw.py acceptance point).
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"

import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map
from repro.core import api
from repro.core import selector as sel
from repro.core.comm import Communicator, HierarchicalCommunicator

L, M = 4, 4
ROWS, COLS = 8, 64


def main() -> dict:
    devs = jax.devices()
    assert len(devs) >= L * M, \
        f"need {L * M} host devices, got {len(devs)} — set XLA_FLAGS"
    mesh2d = Mesh(np.asarray(devs[:L * M]).reshape(M, L), ("node", "local"))
    mesh1d = Mesh(np.asarray(devs[:L * M]), ("x",))

    hc = HierarchicalCommunicator("local", "node", local_n=L, node_n=M)
    compiled = hc.compile((ROWS, COLS), jnp.float32)
    # replay the serialized artifact, not the in-memory object: the
    # smoke covers the load_plan trust boundary too
    plan = api.load_plan(compiled.to_json())
    assert not api.verify_plan(plan).findings

    x = jnp.asarray(np.random.default_rng(7).integers(
        -8, 8, (M, L, ROWS, COLS)).astype(np.float32))
    want = np.asarray(x).sum(axis=(0, 1))

    hier = jax.jit(shard_map(
        lambda xs: plan(xs[0, 0])[None, None], mesh=mesh2d,
        in_specs=P("node", "local", None, None),
        out_specs=P("node", "local", None, None), check_vma=False))(x)
    assert np.array_equal(np.asarray(hier)[0, 0], want), \
        "hierarchical replay != sum"

    flat16 = Communicator("x", n=L * M).compile(
        "all_reduce", (ROWS, COLS), jnp.float32)
    flat = jax.jit(shard_map(
        lambda xs: flat16(xs[0])[None], mesh=mesh1d,
        in_specs=P("x", None, None), out_specs=P("x", None, None),
        check_vma=False))(x.reshape(L * M, ROWS, COLS))
    assert np.array_equal(np.asarray(flat)[0], want), "flat replay != sum"
    assert np.array_equal(np.asarray(hier)[0, 0], np.asarray(flat)[0])

    # modeled fabric: flat pays DCN end-to-end, hierarchy crosses DCN
    # with 1/L of the bytes
    flat_dcn = Communicator("fx", n=L * M, link=sel.DCN).compile(
        "all_reduce", (1024, 256), jnp.float32)
    hier_2d = hc.compile((1024, 256), jnp.float32)
    assert hier_2d.estimate_us < flat_dcn.estimate_us, (
        f"hierarchical {hier_2d.estimate_us:.1f}us not faster than flat "
        f"{flat_dcn.estimate_us:.1f}us on the ICIxDCN model")

    return dict(
        bench="hier_smoke", n=L * M, axes=dict(local=L, node=M),
        algo=plan.algo, flat_algo=flat16.algo,
        bit_equal=True,
        predicted_us=round(hier_2d.estimate_us, 2),
        flat_predicted_us=round(flat_dcn.estimate_us, 2),
        speedup_vs_flat=round(
            flat_dcn.estimate_us / hier_2d.estimate_us, 3))


if __name__ == "__main__":
    summary = main()
    print(f"hier_smoke n={summary['n']} {summary['algo']}: bit-equal to "
          f"flat n=16 OK; modeled ICIxDCN "
          f"{summary['flat_predicted_us']}us flat -> "
          f"{summary['predicted_us']}us hier "
          f"({summary['speedup_vs_flat']}x)")
    print(json.dumps(summary))
