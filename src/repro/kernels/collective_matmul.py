"""Collective matmul: AllGather overlapped with GEMM (compute/comm fusion).

The paper cites compute/communication overlap (§1 [13], Wang et al.
ASPLOS'23) as a key optimization class its primitives enable: because
``put`` is asynchronous and one-sided, a kernel can interleave DMA
issue with MXU work — impossible with NCCL's blocking send/recv.

This kernel computes ``all_gather(x, axis) @ w`` for row-sharded
activations ``x`` and a fully-replicated (per-TP-rank) weight ``w``,
the tensor-parallel forward pattern. Structure per step ``i``:

    issue put of chunk (me - i)  ->  next neighbor      [ICI DMA engines]
    matmul chunk (me - i) @ w    ->  out rows           [MXU]
    wait for chunk (me - i - 1) arrival                 [semaphore]

so the DMA of step i rides under the matmul of step i — the classic
ring-overlap schedule, expressed in ~30 lines of primitives.

VMEM/tiling note: the wrapper tiles ``w`` columns with BlockSpec when F
is large so each grid step keeps (chunk + w_tile + out_tile) within
VMEM; the MXU dims are kept at multiples of 128 by construction of the
model configs.
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

__all__ = ["allgather_matmul", "ag_matmul_kernel"]


def ag_matmul_kernel(x_ref, w_ref, out_ref, xbuf, send_sem, recv_sem, bar_sem,
                     *, axis: str):
    """x_ref: (1, rows, K) my shard; w_ref: (K, F); out_ref: (N, rows, F).

    xbuf: (N, rows, K) rotating gather buffer (chunk slots).
    """
    prim.start_barrier(axis)
    num = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    _, nxt = comm_utils.ring_neighbors(axis)
    chan = MemoryChannel(axis, nxt, send_sem, recv_sem)

    xbuf[me] = x_ref[0]

    def step(i, _):
        slot = jax.lax.rem(me - i + num, num)

        # 1) issue the forward put of the chunk we just finished receiving
        #    (it overlaps with this step's matmul below)
        @pl.when(i < num - 1)
        def _issue():
            chan.put(xbuf.at[slot], xbuf.at[slot])  # async; no flush yet

        # 2) MXU: matmul this chunk while the DMA flies
        out_ref[slot] = jnp.dot(
            xbuf[slot], w_ref[...], preferred_element_type=out_ref.dtype
        )

        # 3) completion: wait for this step's send + next chunk's arrival
        @pl.when(i < num - 1)
        def _complete():
            prim.wait_recv_into(
                xbuf.at[jax.lax.rem(slot - 1 + num, num)],
                send_sem, recv_sem, {axis: me})
            # drain my own send credit so sends never back up
            desc = pltpu.make_async_remote_copy(
                src_ref=xbuf.at[slot], dst_ref=xbuf.at[slot],
                send_sem=send_sem, recv_sem=recv_sem,
                device_id={axis: nxt},
                device_id_type=pltpu.DeviceIdType.MESH)
            desc.wait_send()

        return ()

    jax.lax.fori_loop(0, num, step, ())
    prim.device_barrier(bar_sem, axis)


def allgather_matmul(x, w, *, axis: str, axis_size: int, interpret=None,
                     out_dtype=None):
    """x: (rows, K) shard, w: (K, F) -> (N*rows, F) = all_gather(x) @ w."""
    comm_utils.check_2d(x)
    comm_utils.check_2d(w)
    interpret = comm_utils.interpret_mode() if interpret is None else interpret
    n = axis_size
    rows, k = x.shape
    f = w.shape[1]
    out_dtype = out_dtype or x.dtype
    out = pl.pallas_call(
        functools.partial(ag_matmul_kernel, axis=axis),
        out_shape=jax.ShapeDtypeStruct((n, rows, f), out_dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n, rows, k), x.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(collective_id=6),
    )(x[None], w)
    return out.reshape(n * rows, f)
