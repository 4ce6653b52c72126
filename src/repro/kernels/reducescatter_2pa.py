"""All-pairs ReduceScatter / AllGather — the 2PA building blocks.

Paper §4.4 (2PA): AllReduce = all-pairs ReduceScatter + all-pairs
AllGather. All-pairs beats ring on latency for small/medium messages
(one network hop instead of N-1), at the cost of N× fan-out bandwidth.

This file is the Pallas implementation of paper Fig. 5 (all-pairs
ReduceScatter), with two of the paper's primitive-level optimizations:

* one-sided puts with *receiver-side* waits (no sender/receiver
  rendezvous — impossible with NCCL's self-synchronous send/recv);
* a single thread of control reads all peers' chunks for the reduction
  in one loop ("let a single thread group read data from multiple other
  GPUs at the same time", §4.4-2PA).
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

__all__ = ["reduce_scatter_2pa", "all_gather_2pa", "all_reduce_2pa"]


def rs_allpairs_kernel(x_ref, out_ref, scratch, send_sem, recv_sem, bar_sem, *, axis: str):
    """x_ref: (1, N, rows, cols) — my contribution to every chunk.
    out_ref: (rows, cols) — reduced chunk owned by me."""
    prim.start_barrier(axis)
    num = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)

    def send_body(i, _):
        peer = jax.lax.rem(me + i, num)
        chan = MemoryChannel(axis, peer, send_sem, recv_sem)
        chan.put(x_ref.at[0, peer], scratch.at[me]).flush()
        return ()

    jax.lax.fori_loop(1, num, send_body, ())

    def wait_body(i, _):
        peer = jax.lax.rem(me + i, num)
        prim.wait_recv_into(scratch.at[peer], send_sem, recv_sem, {axis: me})
        return ()

    jax.lax.fori_loop(1, num, wait_body, ())

    acc = x_ref[0, me]

    def red_body(i, acc):
        peer = jax.lax.rem(me + i, num)
        return acc + scratch[peer]

    out_ref[...] = jax.lax.fori_loop(1, num, red_body, acc)
    prim.device_barrier(bar_sem, axis)


def ag_allpairs_kernel(x_ref, out_ref, send_sem, recv_sem, bar_sem, *, axis: str):
    """x_ref: (1, rows, cols) my chunk; out_ref: (N, rows, cols) gathered."""
    prim.start_barrier(axis)
    num = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    out_ref[me] = x_ref[0]

    def send_body(i, _):
        peer = jax.lax.rem(me + i, num)
        chan = MemoryChannel(axis, peer, send_sem, recv_sem)
        chan.put(out_ref.at[me], out_ref.at[me]).flush()
        return ()

    jax.lax.fori_loop(1, num, send_body, ())

    def wait_body(i, _):
        peer = jax.lax.rem(me + i, num)
        prim.wait_recv_into(out_ref.at[peer], send_sem, recv_sem, {axis: me})
        return ()

    jax.lax.fori_loop(1, num, wait_body, ())
    prim.device_barrier(bar_sem, axis)


def reduce_scatter_2pa(x, *, axis: str, axis_size: int, interpret=None):
    """x: (N*rows, cols) local contribution -> (rows, cols) reduced chunk."""
    comm_utils.check_2d(x)
    interpret = comm_utils.interpret_mode() if interpret is None else interpret
    n = axis_size
    rows = x.shape[0] // n
    cols = x.shape[1]
    return pl.pallas_call(
        functools.partial(rs_allpairs_kernel, axis=axis),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n, rows, cols), x.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(collective_id=1),
    )(x.reshape(1, n, rows, cols))


def all_gather_2pa(x, *, axis: str, axis_size: int, interpret=None):
    """x: (rows, cols) local chunk -> (N*rows, cols) gathered."""
    comm_utils.check_2d(x)
    interpret = comm_utils.interpret_mode() if interpret is None else interpret
    n = axis_size
    rows, cols = x.shape
    out = pl.pallas_call(
        functools.partial(ag_allpairs_kernel, axis=axis),
        out_shape=jax.ShapeDtypeStruct((n, rows, cols), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.REGULAR],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(collective_id=2),
    )(x[None])
    return out.reshape(n * rows, cols)


def all_reduce_2pa(x, *, axis: str, axis_size: int, interpret=None):
    """Two-phase all-pairs AllReduce (paper §4.4-2PA).

    x: (N*rows, cols) -> (N*rows, cols) fully reduced on every device.
    """
    shard = reduce_scatter_2pa(x, axis=axis, axis_size=axis_size, interpret=interpret)
    return all_gather_2pa(shard, axis=axis, axis_size=axis_size, interpret=interpret)
