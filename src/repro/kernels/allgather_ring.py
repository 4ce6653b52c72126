"""Ring AllGather as a Pallas TPU kernel on MSCCL++ channel primitives.

The bandwidth-optimal algorithm for large messages (paper §5.1: "the ring
algorithm works better for large data sizes"). Each step, device ``d``
forwards the chunk it received last step to ``d+1``; after ``N-1`` steps
every device holds all chunks. All transfers ride a MemoryChannel (HB
protocol): bulk remote DMA, DMA-completion semaphore as the fused
putWithSignal.
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

__all__ = ["all_gather_ring", "ag_ring_kernel"]


def ag_ring_kernel(x_ref, out_ref, send_sem, recv_sem, bar_sem, *, axis: str):
    """out_ref: (N, rows, cols) VMEM; x_ref: (1, rows, cols) local shard."""
    prim.start_barrier(axis)
    num = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    out_ref[me] = x_ref[0]

    _, nxt = comm_utils.ring_neighbors(axis)
    chan = MemoryChannel(axis, nxt, send_sem, recv_sem)

    def step(i, _):
        slot = jax.lax.rem(me - i + num, num)
        copy = chan.put(out_ref.at[slot], out_ref.at[slot])
        # HB protocol: wait = recv-side DMA semaphore; also flushes send.
        copy.wait()
        return ()

    jax.lax.fori_loop(0, num - 1, step, ())
    prim.device_barrier(bar_sem, axis)


def all_gather_ring(x, *, axis: str, axis_size: int, interpret=None):
    """Per-shard entry point — call *inside* shard_map.

    x: (rows, cols) local shard -> (N*rows, cols) fully gathered.
    """
    comm_utils.check_2d(x)
    interpret = comm_utils.interpret_mode() if interpret is None else interpret
    n = axis_size
    rows, cols = x.shape
    out = pl.pallas_call(
        functools.partial(ag_ring_kernel, axis=axis),
        out_shape=jax.ShapeDtypeStruct((n, rows, cols), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.REGULAR],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(collective_id=0),
    )(x[None])
    return out.reshape(n * rows, cols)
