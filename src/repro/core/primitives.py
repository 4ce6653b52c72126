"""MSCCL++ Primitive API, adapted to TPU (Pallas).

The paper's primitive interface is four operations — ``put``, ``signal``,
``wait``, ``flush`` — exposed *inside* device kernels, designed to be
zero-copy, one-sided and asynchronous (paper §3.2.2, Fig. 4).

On TPU this maps directly onto the hardware's native communication model:

    put    -> pltpu.make_async_remote_copy(...).start()     (ICI RDMA)
    signal -> pltpu.semaphore_signal(sem, device_id=...)
    wait   -> pltpu.semaphore_wait(sem, value)
    flush  -> descriptor.wait_send()  (source-side completion only)

Unlike the GPU implementation (paper Fig. 7), no CPU proxy thread is needed:
TPU cores enqueue ICI DMA descriptors themselves. The FIFO request queue of
the paper's PortChannel therefore has no equivalent here — its purpose
(decoupling data movement from compute threads) is inherent in the TPU DMA
engines.

These functions are meant to be called from within a ``pl.pallas_call``
kernel body. ``device_id`` arguments are logical mesh coordinates
(``dict(axis_name -> index)``), matching the paper's rank-addressing.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.experimental import pallas as pl  # noqa: F401  (re-exported for users)
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "RemoteCopy",
    "put",
    "put_with_signal",
    "signal",
    "wait",
    "flush",
    "local_copy",
    "device_barrier",
    "INTERPRET_PARAMS",
]

# Interpret-mode configuration used by every test/benchmark that emulates
# multi-device TPU kernels on CPU. ``dma_execution_mode='on_wait'`` (the
# default) exhibits cross-device delivery skew in emulation (documented in
# DESIGN.md §8); 'eager' executes the DMA at ``start()`` which matches the
# memory-consistency contract the paper's ``put`` requires.
INTERPRET_PARAMS = pltpu.InterpretParams(
    dma_execution_mode="eager", detect_races=False
)
INTERPRET_PARAMS_RACECHECK = pltpu.InterpretParams(
    dma_execution_mode="eager", detect_races=True
)


@dataclasses.dataclass
class RemoteCopy:
    """Handle for an in-flight ``put`` (one ICI DMA descriptor).

    ``flush()`` waits only for the *send* side (source buffer reusable —
    the paper's ``flush`` semantics); ``wait_recv()`` is used on the
    receiving device when the same semaphore pair is shared.
    """

    descriptor: Any

    def flush(self) -> None:
        self.descriptor.wait_send()

    def wait_recv(self) -> None:
        self.descriptor.wait_recv()

    def wait(self) -> None:
        self.descriptor.wait()


def put(
    src_ref,
    dst_ref,
    send_sem,
    recv_sem,
    device_id: Mapping[str, Any],
    *,
    start: bool = True,
) -> RemoteCopy:
    """One-sided asynchronous zero-copy transfer to a peer device.

    Writes ``src_ref`` (local) into ``dst_ref`` (peer's address space,
    same-named buffer on the peer — TPU remote DMAs are symmetric-heap
    style, like NVSHMEM/MSCCL++ registered buffers). Returns immediately;
    the data is *not* guaranteed visible on the peer until the peer waits
    on ``recv_sem`` (paper: the following ``signal``/``wait`` pair — on
    TPU the recv semaphore update is ordered after the payload, so DMA
    completion doubles as the signal: this is ``putWithSignal`` fused in
    hardware).
    """
    desc = pltpu.make_async_remote_copy(
        src_ref=src_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=dict(device_id),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    if start:
        desc.start()
    return RemoteCopy(desc)


def put_with_signal(src_ref, dst_ref, send_sem, recv_sem, device_id) -> RemoteCopy:
    """Paper's fused ``putWithSignal``.

    On TPU the receive-side DMA semaphore is updated after the payload
    lands, so a single descriptor provides both the transfer and the
    orderly signal — the fusion the paper implements in software is a
    hardware guarantee here.
    """
    return put(src_ref, dst_ref, send_sem, recv_sem, device_id)


def signal(sem, device_id: Mapping[str, Any] | None = None, inc: int = 1) -> None:
    """Increment a (possibly remote) semaphore; async, ordered after
    previously-issued DMAs to the same peer (ICI ordering)."""
    if device_id is None:
        pltpu.semaphore_signal(sem, inc)
    else:
        pltpu.semaphore_signal(
            sem,
            inc,
            device_id=dict(device_id),
            device_id_type=pltpu.DeviceIdType.MESH,
        )


def wait(sem, value: int = 1) -> None:
    """Block until the local semaphore reaches ``value``; consumes it."""
    pltpu.semaphore_wait(sem, value)


def flush(copy: RemoteCopy) -> None:
    """Source-side completion: after this, ``src_ref`` may be reused.

    (Paper Fig. 4: 'flush() //sync — safe to reuse src0'.)
    """
    copy.flush()


def wait_recv_into(dst_ref, send_sem, recv_sem, device_id: Mapping[str, Any]) -> None:
    """Receiver-side wait for a one-sided ``put`` targeting ``dst_ref``.

    The receiver did not create the sender's descriptor, so it builds a
    *matching* descriptor (same dst shape ⇒ same byte count on the DMA
    semaphore) and waits on the recv side only. This is the documented
    Pallas pattern for one-sided communication and exactly reproduces the
    paper's ``wait`` primitive: DMA semaphores count bytes, so a plain
    ``semaphore_wait(sem, n_peers)`` would be wrong.
    """
    desc = pltpu.make_async_remote_copy(
        src_ref=dst_ref,
        dst_ref=dst_ref,
        send_sem=send_sem,
        recv_sem=recv_sem,
        device_id=dict(device_id),
        device_id_type=pltpu.DeviceIdType.MESH,
    )
    desc.wait_recv()


def poll_flag(flag_ref, flag_value, *, index=(0, 0)) -> None:
    """Spin until ``flag_ref[index] == flag_value`` (LL-protocol recv)."""
    def cond(_):
        return flag_ref[index] != flag_value

    jax.lax.while_loop(cond, lambda c: c, jax.numpy.int32(0))


def local_copy(src_ref, dst_ref, sem) -> None:
    """Local async copy (the paper's ``copy`` primitive), synchronous here."""
    desc = pltpu.make_async_copy(src_ref, dst_ref, sem)
    desc.start()
    desc.wait()


def start_barrier(axis: str | Sequence[str]) -> None:
    """Kernel-entry barrier over mesh axis(es) on the global barrier
    semaphore.

    MANDATORY before the first remote DMA of any collective kernel: a
    peer must not ``put`` into buffers a device has not yet allocated
    (on hardware: not yet entered the kernel; in interpret mode this
    races as a missing-buffer error). The barrier semaphore is the only
    cross-kernel-stable semaphore, hence its use here — requires
    ``compiler_params=pltpu.CompilerParams(collective_id=...)``.

    This is the TPU equivalent of the paper's bootstrap-then-communicate
    contract (§4.1): connections (here: buffer registration) must be
    established before one-sided puts fly.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    sem = pltpu.get_barrier_semaphore()
    total = 0
    for ax in axes:
        num = jax.lax.axis_size(ax)
        me = jax.lax.axis_index(ax)

        def _signal_peer(i, _):
            peer = jax.lax.rem(me + i, num)
            pltpu.semaphore_signal(
                sem, 1, device_id={ax: peer},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            return ()

        jax.lax.fori_loop(1, num, _signal_peer, ())
        total += num - 1
    pltpu.semaphore_wait(sem, total)


def device_barrier(sem, axis: str | Sequence[str], *, my_id=None) -> None:
    """Barrier across all devices on mesh axis/axes on a *scratch regular*
    semaphore.

    Implements the paper's ``multiDeviceBarrier()`` (Fig. 5 line 18):
    every device signals every other device's barrier semaphore, then
    waits for all peers' signals. O(N) signals, one wait.

    Used as the kernel EXIT barrier: because the semaphore is allocated
    per-invocation, exit signals of call k can never alias with barriers
    of call k+1 — which, combined with the ``start_barrier`` entry on the
    global barrier semaphore, makes back-to-back collective invocations
    race-free (no put can fly into a kernel instance a peer has not yet
    entered).
    """
    del my_id
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    total = 0
    for ax in axes:
        num = jax.lax.axis_size(ax)
        me = jax.lax.axis_index(ax)

        def _signal_peer(i, _):
            peer = jax.lax.rem(me + i, num)
            pltpu.semaphore_signal(
                sem, 1, device_id={ax: peer},
                device_id_type=pltpu.DeviceIdType.MESH,
            )
            return ()

        jax.lax.fori_loop(1, num, _signal_peer, ())
        total += num - 1
    pltpu.semaphore_wait(sem, total)
