"""MSCCL++ channel abstractions on TPU.

The paper defines one channel type per hardware data-transfer mode
(§3.2.1): ``MemoryChannel`` (memory-mapped I/O / thread copy),
``PortChannel`` (port-mapped I/O / DMA engines + proxy), and
``SwitchChannel`` (switch-mapped I/O / NVLS multimem).

TPU adaptation (DESIGN.md §2):

* ``MemoryChannel``  — VMEM-resident remote DMA between a peer pair. Two
  protocols, mirroring the paper's §4.2.2:
    - ``HB``: bulk transfer, completion signalled by the DMA semaphore
      (high bandwidth, sync cost amortized over the chunk);
    - ``LL``: the transfer carries an inline *flag tile* written by the
      same descriptor; the receiver polls the flag in VMEM instead of
      waiting on a semaphore (low latency; no separate signal message).
* ``PortChannel``    — identical primitive surface but intended for
  HBM-resident buffers moved by the DMA engines while the compute core
  does other work; there is no CPU proxy on TPU (cores enqueue ICI DMAs
  directly), so the paper's request FIFO disappears.
* ``SwitchChannel``  — no ICI analogue of in-switch reduction; adapted as
  ``FusedReduceChannel``: peers push chunks, receiver reduces on arrival.
  API-compatible (``reduce`` / ``broadcast``), hardware acceleration
  honestly absent (documented).

Channels are *kernel-build-time* objects: construct them inside a
``pl.pallas_call`` body with semaphore refs from ``scratch_shapes``.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core import primitives as prim

__all__ = [
    "Protocol",
    "Channel",
    "MemoryChannel",
    "PortChannel",
    "FusedReduceChannel",
    "SwitchChannel",
]


class Protocol(enum.Enum):
    HB = "HB"  # high-bandwidth: bulk DMA + semaphore
    LL = "LL"  # low-latency: inline flag, receiver polls VMEM


@dataclasses.dataclass
class Channel:
    """Peer-to-peer channel base: a (mesh-axis, peer) address plus the
    semaphore pair backing put/signal/wait/flush."""

    axis: str
    peer: Any  # static int or traced index along `axis`
    send_sem: Any
    recv_sem: Any

    # -- primitive surface (paper Fig. 6) ---------------------------------
    def put(self, src_ref, dst_ref) -> prim.RemoteCopy:
        return prim.put(
            src_ref, dst_ref, self.send_sem, self.recv_sem, {self.axis: self.peer}
        )

    def put_with_signal(self, src_ref, dst_ref) -> prim.RemoteCopy:
        # On TPU the recv-side DMA semaphore fires after payload delivery:
        # put *is* putWithSignal (DESIGN.md §2).
        return self.put(src_ref, dst_ref)

    def signal(self, inc: int = 1) -> None:
        prim.signal(self.recv_sem, {self.axis: self.peer}, inc)

    def wait(self, value: int = 1) -> None:
        prim.wait(self.recv_sem, value)

    def flush(self, copy: prim.RemoteCopy) -> None:
        copy.flush()


class MemoryChannel(Channel):
    """Thread-copy-analogue channel for VMEM-resident buffers."""

    protocol: Protocol = Protocol.HB

    def __init__(self, axis, peer, send_sem, recv_sem, protocol: Protocol = Protocol.HB):
        super().__init__(axis, peer, send_sem, recv_sem)
        self.protocol = protocol

    # -- LL protocol -------------------------------------------------------
    # The flag tile travels in the same descriptor as (after) the payload;
    # the receiver polls it in VMEM. `flag_ref` layout: (1, 128) int32 lane
    # row per outstanding slot (TPU vreg-tile granular, adapting the
    # paper's 8-byte data+flag words — DESIGN.md §4).
    def put_ll(self, src_ref, dst_ref, flag_src_ref, flag_dst_ref, flag_value) -> None:
        if self.protocol is not Protocol.LL:
            raise ValueError("put_ll requires an LL-protocol channel")
        flag_src_ref[...] = jnp.full_like(flag_src_ref[...], flag_value)
        data = prim.put(src_ref, dst_ref, self.send_sem, self.recv_sem,
                        {self.axis: self.peer})
        # Payload first, then flag: ICI delivers descriptors to the same
        # peer in issue order, so flag visibility implies data visibility.
        flag = prim.put(flag_src_ref, flag_dst_ref, self.send_sem, self.recv_sem,
                        {self.axis: self.peer})
        data.flush()
        flag.flush()

    def read_ll(self, dst_ref, flag_ref, flag_value):
        """Poll the flag tile until `flag_value` is visible, then read.

        Returns the payload; consumes no semaphore (the LL latency win).
        """
        prim.poll_flag(flag_ref, flag_value)
        return dst_ref[...]

    def drain_ll(self, dst_ref, flag_dst_ref) -> None:
        """Drain the recv-semaphore byte credits left by an LL put pair
        (payload + flag descriptors still update the DMA semaphore on
        TPU). Call after ``read_ll`` succeeded — the waits return
        immediately — to keep the semaphore balanced for buffer reuse."""
        prim.wait_recv_into(dst_ref, self.send_sem, self.recv_sem,
                            {self.axis: self.peer})
        prim.wait_recv_into(flag_dst_ref, self.send_sem, self.recv_sem,
                            {self.axis: self.peer})


class PortChannel(Channel):
    """DMA-engine channel for HBM-resident buffers.

    Same primitive surface; ``put`` here is expected to be issued on
    large, HBM-backed refs so the ICI/DCN DMA engines stream the data
    while the compute core proceeds (the paper's 'frees GPU threads'
    benefit is structural on TPU). A `flush` is mandatory before source
    reuse, exactly as in the paper.
    """


class FusedReduceChannel:
    """SwitchChannel adaptation (DESIGN.md §2): reduce/broadcast over a
    device group, implemented as push + reduce-on-arrival because ICI has
    no in-switch computation.

    reduce():   every peer pushes its chunk into my per-peer scratch slot;
                I wait for N-1 arrivals and vector-add.
    broadcast(): I push my chunk to every peer's slot.
    """

    def __init__(self, axis: str, send_sem, recv_sem):
        self.axis = axis
        self.send_sem = send_sem
        self.recv_sem = recv_sem

    def broadcast(self, src_ref, dst_slots_ref, my_id=None) -> None:
        """Push src into `dst_slots_ref[my_id]` on every peer."""
        num = jax.lax.axis_size(self.axis)
        me = jax.lax.axis_index(self.axis) if my_id is None else my_id

        def body(i, _):
            peer = jax.lax.rem(me + i, num)
            prim.put(
                src_ref,
                dst_slots_ref.at[me],
                self.send_sem,
                self.recv_sem,
                {self.axis: peer},
            ).flush()
            return ()

        jax.lax.fori_loop(1, num, body, ())

    def recv(self, dst_ref, from_peer) -> None:
        """Receiver-side wait for one pushed chunk landing in dst_ref."""
        me = jax.lax.axis_index(self.axis)
        prim.wait_recv_into(dst_ref, self.send_sem, self.recv_sem,
                            {self.axis: me})
        del from_peer  # byte-count semantics: any matching-size arrival

    def reduce(self, out_ref, local_ref, slots_ref, my_id=None) -> None:
        """Wait for N-1 pushed chunks, then out = local + sum(slots)."""
        num = jax.lax.axis_size(self.axis)
        me = jax.lax.axis_index(self.axis) if my_id is None else my_id

        def wait_body(i, _):
            peer = jax.lax.rem(me + i, num)
            # matching-descriptor recv wait (DMA semaphores count bytes)
            prim.wait_recv_into(slots_ref.at[peer], self.send_sem,
                                self.recv_sem, {self.axis: me})
            return ()

        jax.lax.fori_loop(1, num, wait_body, ())
        acc = local_ref[...]

        def body(i, acc):
            peer = jax.lax.rem(me + i, num)
            return acc + slots_ref[peer]

        acc = jax.lax.fori_loop(1, num, body, acc)
        out_ref[...] = acc


# Alias keeping the paper's name importable.
SwitchChannel = FusedReduceChannel
