"""Two-phase hierarchical AllReduce (2PH) over a 2-level mesh.

Paper §4.4-2PH: cross-node traffic is the scarce resource, so reduce
locally first, cross the slow boundary with 1/L of the data, then gather
locally. On TPU the two levels are the pod-internal ICI mesh (fast,
'local' axis) and the inter-pod DCN ('node' axis — the paper's IB links).

    phase 1: all-pairs ReduceScatter along `local`   (fast links, full data)
    phase 2: all-pairs AllReduce     along `node`    (slow links, 1/L data)
    phase 3: all-pairs AllGather     along `local`   (fast links, full data)

The cross-boundary phase moves only ``bytes/L`` per device — the
bandwidth argument of the paper, identical on TPU.

Phase 2 is pipelined with phase 1 per sub-chunk in the DSL executor
version; this standalone kernel keeps the canonical three-phase
structure for clarity and as the oracle-checked baseline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import primitives as prim
from repro.core.channels import MemoryChannel
from repro.kernels import comm_utils

__all__ = ["all_reduce_2ph"]


def ar_2ph_kernel(x_ref, out_ref, local_scratch, node_scratch,
                  send_sem, recv_sem, send_sem2, recv_sem2,
                  send_sem3, recv_sem3, bar_sem,
                  *, local_axis: str, node_axis: str):
    """x_ref: (1, L, rows, cols) — local buffer viewed as L chunks.
    out_ref: (L, rows, cols) — fully reduced buffer.
    """
    prim.start_barrier((local_axis, node_axis))
    lnum = jax.lax.axis_size(local_axis)
    lme = jax.lax.axis_index(local_axis)
    nnum = jax.lax.axis_size(node_axis)
    nme = jax.lax.axis_index(node_axis)

    # ---- phase 1: ReduceScatter along `local` (all-pairs) ----------------
    def p1_send(i, _):
        peer = jax.lax.rem(lme + i, lnum)
        chan = MemoryChannel(local_axis, peer, send_sem, recv_sem)
        chan.put(x_ref.at[0, peer], local_scratch.at[lme]).flush()
        return ()

    jax.lax.fori_loop(1, lnum, p1_send, ())

    def p1_wait(i, _):
        peer = jax.lax.rem(lme + i, lnum)
        prim.wait_recv_into(local_scratch.at[peer], send_sem, recv_sem,
                            {local_axis: lme})
        return ()

    jax.lax.fori_loop(1, lnum, p1_wait, ())

    acc = x_ref[0, lme]

    def p1_red(i, acc):
        peer = jax.lax.rem(lme + i, lnum)
        return acc + local_scratch[peer]

    acc = jax.lax.fori_loop(1, lnum, p1_red, acc)  # node-local sum of my chunk

    # ---- phase 2: AllReduce along `node` on the 1/L shard ----------------
    out_ref[lme] = acc  # stage my shard for cross-node puts

    def p2_send(i, _):
        peer = jax.lax.rem(nme + i, nnum)
        chan = MemoryChannel(node_axis, peer, send_sem2, recv_sem2)
        chan.put(out_ref.at[lme], node_scratch.at[nme]).flush()
        return ()

    jax.lax.fori_loop(1, nnum, p2_send, ())

    def p2_wait(i, _):
        peer = jax.lax.rem(nme + i, nnum)
        prim.wait_recv_into(node_scratch.at[peer], send_sem2, recv_sem2,
                            {node_axis: nme})
        return ()

    jax.lax.fori_loop(1, nnum, p2_wait, ())

    def p2_red(i, acc):
        peer = jax.lax.rem(nme + i, nnum)
        return acc + node_scratch[peer]

    acc = jax.lax.fori_loop(1, nnum, p2_red, acc)  # global sum of my chunk
    out_ref[lme] = acc

    # ---- phase 3: AllGather along `local` (all-pairs) --------------------
    # Dedicated semaphore pair: reusing the phase-1 pair would let a fast
    # peer's phase-3 put satisfy a slow device's phase-1 byte-wait (the
    # cross-round consistency hazard the paper describes in §2.2.2
    # 'Inflexible Synchronization' — here solved with sem separation
    # instead of a full barrier, which is the cheaper MSCCL++-style fix).
    def p3_send(i, _):
        peer = jax.lax.rem(lme + i, lnum)
        chan = MemoryChannel(local_axis, peer, send_sem3, recv_sem3)
        chan.put(out_ref.at[lme], out_ref.at[lme]).flush()
        return ()

    jax.lax.fori_loop(1, lnum, p3_send, ())

    def p3_wait(i, _):
        peer = jax.lax.rem(lme + i, lnum)
        prim.wait_recv_into(out_ref.at[peer], send_sem3, recv_sem3,
                            {local_axis: lme})
        return ()

    jax.lax.fori_loop(1, lnum, p3_wait, ())
    prim.device_barrier(bar_sem, (local_axis, node_axis))


def all_reduce_2ph(x, *, local_axis: str, local_size: int,
                   node_axis: str, node_size: int, interpret=None):
    """x: (L*rows, cols) local buffer -> same, reduced over both axes."""
    comm_utils.check_2d(x)
    interpret = comm_utils.interpret_mode() if interpret is None else interpret
    lnum = local_size
    rows = x.shape[0] // lnum
    cols = x.shape[1]
    out = pl.pallas_call(
        functools.partial(ar_2ph_kernel, local_axis=local_axis,
                          node_axis=node_axis),
        out_shape=jax.ShapeDtypeStruct((lnum, rows, cols), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((lnum, rows, cols), x.dtype),   # phase-1 slots
            pltpu.VMEM((node_size, rows, cols), x.dtype),  # phase-2 slots
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(collective_id=5),
    )(x.reshape(1, lnum, rows, cols))
    return out.reshape(lnum * rows, cols)
