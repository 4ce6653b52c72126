"""One-phase all-pairs AllReduce (1PA) with the LL protocol.

Paper §4.4-1PA: for very small messages, every device broadcasts its
*entire* buffer to all peers and every device reduces all N buffers
locally. Redundant compute and N× traffic, but the fewest possible
synchronization steps — latency-optimal.

The LL (low-latency) protocol (paper §4.2.2) removes even the semaphore
wait: the payload carries an inline flag tile, and the receiver *polls*
the flag in VMEM. On GPUs this is an 8-byte atomic data+flag word; on
TPU we adapt to vreg-tile granularity (DESIGN.md §4): a (1, 128) int32
flag row delivered by a second descriptor on the same ordered ICI path.

``flag_value`` must differ between consecutive invocations reusing the
same scratch (the paper: "flag values are decided such that all are
distinct"); the wrapper derives it from a step counter argument.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import primitives as prim
from repro.core.channels import MemoryChannel, Protocol
from repro.kernels import comm_utils

__all__ = ["all_reduce_1pa", "ar_1pa_kernel"]


def ar_1pa_kernel(x_ref, flag_val_ref, out_ref, scratch, flags, flag_src,
                  send_sem, recv_sem, bar_sem, *, axis: str, use_ll: bool):
    prim.start_barrier(axis)
    num = jax.lax.axis_size(axis)
    me = jax.lax.axis_index(axis)
    flag_value = flag_val_ref[0]

    # --- fan-out: put my buffer (+flag) into every peer's slot[me] -------
    def send_body(i, _):
        peer = jax.lax.rem(me + i, num)
        chan = MemoryChannel(axis, peer, send_sem, recv_sem,
                             protocol=Protocol.LL if use_ll else Protocol.HB)
        if use_ll:
            chan.put_ll(x_ref.at[0], scratch.at[me],
                        flag_src, flags.at[me], flag_value)
        else:
            chan.put(x_ref.at[0], scratch.at[me]).flush()
        return ()

    jax.lax.fori_loop(1, num, send_body, ())

    # --- completion: poll flags (LL) or recv-wait semaphores (HB) --------
    def wait_body(i, _):
        peer = jax.lax.rem(me + i, num)
        if use_ll:
            prim.poll_flag(flags, flag_value, index=(peer, 0, 0))
        else:
            prim.wait_recv_into(scratch.at[peer], send_sem, recv_sem, {axis: me})
        return ()

    jax.lax.fori_loop(1, num, wait_body, ())

    # --- single-pass reduction over all peers' slots ----------------------
    acc = x_ref[0]

    def red_body(i, acc):
        peer = jax.lax.rem(me + i, num)
        return acc + scratch[peer]

    out_ref[...] = jax.lax.fori_loop(1, num, red_body, acc)

    if use_ll:
        # Balance the DMA semaphore byte credits left by payload+flag
        # descriptors (they have already landed: waits return at once).
        def drain_body(i, _):
            peer = jax.lax.rem(me + i, num)
            prim.wait_recv_into(scratch.at[peer], send_sem, recv_sem, {axis: me})
            prim.wait_recv_into(flags.at[peer], send_sem, recv_sem, {axis: me})
            return ()

        jax.lax.fori_loop(1, num, drain_body, ())
    prim.device_barrier(bar_sem, axis)


def all_reduce_1pa(x, *, axis: str, axis_size: int, use_ll: bool = True,
                   step: int | jax.Array = 0, interpret=None):
    """x: (rows, cols) full local buffer -> (rows, cols) reduced.

    ``step``: invocation counter used to derive a distinct LL flag value.
    """
    comm_utils.check_2d(x)
    interpret = comm_utils.interpret_mode() if interpret is None else interpret
    n = axis_size
    rows, cols = x.shape
    # distinct, never-zero flag per step (scratch is NaN/garbage-initialized)
    flag_value = (jnp.asarray(step, jnp.int32) % jnp.int32(2**30)) * 2 + 0x5A5A5
    return pl.pallas_call(
        functools.partial(ar_1pa_kernel, axis=axis, use_ll=use_ll),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n, rows, cols), x.dtype),      # data slots
            pltpu.VMEM((n, 1, 128), jnp.int32),         # flag slots
            pltpu.VMEM((1, 128), jnp.int32),            # flag source tile
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.REGULAR,
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(collective_id=3),
    )(x[None], flag_value.reshape(1))
