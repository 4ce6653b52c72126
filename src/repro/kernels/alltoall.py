"""All-pairs AllToAll — the MoE dispatch/combine collective.

Every device sends chunk ``c`` of its buffer to device ``c`` (paper §2.1
lists AllToAll among the core AI collectives; MoE expert-parallel
dispatch is its dominant user). Implemented one-sided: N-1 puts into
peers' row slots + receiver-side waits — no rendezvous, which is the
primitive-level advantage MSCCL++ has over NCCL send/recv chains.
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

__all__ = ["all_to_all_pallas"]


def a2a_kernel(x_ref, out_ref, send_sem, recv_sem, bar_sem, *, axis: str):
    """x_ref: (1, N, rows, cols); out_ref: (N, rows, cols) with
    out[p] = chunk received from peer p."""
    prim.start_barrier(axis)
    num = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    out_ref[me] = x_ref[0, me]

    def send_body(i, _):
        peer = jax.lax.rem(me + i, num)
        chan = MemoryChannel(axis, peer, send_sem, recv_sem)
        chan.put(x_ref.at[0, peer], out_ref.at[me]).flush()
        return ()

    jax.lax.fori_loop(1, num, send_body, ())

    def wait_body(i, _):
        peer = jax.lax.rem(me + i, num)
        prim.wait_recv_into(out_ref.at[peer], send_sem, recv_sem, {axis: me})
        return ()

    jax.lax.fori_loop(1, num, wait_body, ())
    prim.device_barrier(bar_sem, axis)


def all_to_all_pallas(x, *, axis: str, axis_size: int, interpret=None):
    """x: (N*rows, cols) -> (N*rows, cols), row-block transpose across
    devices (block b of my input lands as my block <my_id> on device b)."""
    comm_utils.check_2d(x)
    interpret = comm_utils.interpret_mode() if interpret is None else interpret
    n = axis_size
    rows = x.shape[0] // n
    cols = x.shape[1]
    out = pl.pallas_call(
        functools.partial(a2a_kernel, axis=axis),
        out_shape=jax.ShapeDtypeStruct((n, rows, cols), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.REGULAR],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(collective_id=4),
    )(x.reshape(1, n, rows, cols))
    return out.reshape(n * rows, cols)
