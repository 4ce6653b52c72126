"""Expert-parallel MoE dispatch over the MSCCL++ all_to_all.

The dense-einsum MoE in ``models/blocks.py`` computes every expert for
every token (simple, GSPMD-friendly — the dry-run baseline). At scale
the production path is sparse expert parallelism: tokens are routed to
the devices owning their experts with an **all_to_all** (the paper's
§2.1 headline collective for MoE), processed by the local experts, and
combined back with the inverse all_to_all.

This module provides that path as a shard_map body over the expert
axis. Capacity-factor semantics: per (device, expert) at most
``capacity`` tokens; overflow drops (standard Switch-style routing) —
exactness vs the dense path holds whenever capacity is not exceeded,
which the test pins.

Plan replay (paper §5.2, the explicit decode hot path): both the
dispatch and the combine all_to_all move an ``(e_total * capacity, d)``
buffer — the same shape — so ONE init-compiled plan serves both
directions of every MoE layer of every decode step. Pass ``plan=`` (a
:class:`~repro.core.comm.BucketedPlan` compiled over capacity buckets,
or a plain :class:`~repro.core.comm.ExecutionPlan`) to route them
through it; with ``plan=None`` the dispatch falls back to
``comm.all_to_all`` (compile-or-hit-cache on first trace).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import comm as comm_lib

__all__ = ["moe_layer_ep", "ep_capacity"]


def ep_capacity(n_tok: int, top_k: int, e_total: int,
                capacity_factor: Optional[float] = None) -> int:
    """Per-(device, expert) token capacity of the EP dispatch buffer.

    One formula shared by the layer (:func:`moe_layer_ep`) and the plan
    compiler (:func:`repro.distributed.step.compile_decode_plans`), so
    the capacity a decode step dispatches with is exactly a capacity the
    engine compiled a bucket for. ``capacity_factor=None`` means
    LOSSLESS: capacity ``n_tok * top_k`` admits the worst case (every
    assignment routed to one expert), so no token is ever dropped —
    required for the explicit decode path's bit-equivalence with the
    dense oracle."""
    if capacity_factor is None:
        return n_tok * top_k
    return int(capacity_factor * n_tok * top_k / e_total) + 1


def moe_layer_ep(p, x, cfg, *, axis: str,
                 capacity_factor: Optional[float] = 2.0,
                 backend: Optional[str] = None,
                 comm: Optional[comm_lib.Communicator] = None,
                 plan=None):
    """Sparse expert-parallel MoE. Call INSIDE shard_map with the expert
    weights sharded on ``axis`` (leading expert dim) and ``x`` the local
    token shard (b, s, d).

    p["w_gate"|"w_up"|"w_down"]: (e_local, d, f) / (e_local, f, d);
    p["router"]: (d, e_total) replicated.

    ``capacity_factor``: Switch-style per-expert capacity multiplier;
    ``None`` means lossless (see :func:`ep_capacity`).

    ``comm``: the Communicator carrying the expert axis's all_to_all
    plans (compiled once, replayed every layer/step); defaults to the
    process-default communicator for ``axis``.

    ``plan``: a precompiled all_to_all plan (``BucketedPlan`` over
    capacity buckets or plain ``ExecutionPlan``) replayed for BOTH the
    dispatch and the inverse combine — zero planning work inside traced
    code, the §5.2 deployment shape. The serve engine compiles it at
    init (``decode_plans["moe_alltoall"]``) and hands it down through
    :class:`~repro.distributed.step.TPDecodeComms`.
    """
    comm = comm if comm is not None else comm_lib.default_communicator(axis)
    b, s, d = x.shape
    ep = jax.lax.axis_size(axis)
    e_total = p["router"].shape[-1]
    e_local = e_total // ep
    k = cfg.moe.top_k
    tokens = x.reshape(b * s, d)
    n_tok = b * s
    capacity = ep_capacity(n_tok, k, e_total, capacity_factor)

    router = (tokens @ p["router"]).astype(jnp.float32)     # (T, E)
    weights, idx = jax.lax.top_k(router, k)                  # (T, k)
    weights = jax.nn.softmax(weights, axis=-1)

    # ---- build per-expert token slots (T·k assignments -> E × capacity)
    flat_expert = idx.reshape(-1)                            # (T·k,)
    flat_tok = jnp.repeat(jnp.arange(n_tok), k)
    flat_w = weights.reshape(-1)
    # position of each assignment within its expert's capacity buffer
    order = jnp.argsort(flat_expert, stable=True)
    sorted_e = flat_expert[order]
    pos_in_e = jnp.arange(n_tok * k) - jnp.searchsorted(
        sorted_e, sorted_e, side="left")
    keep = pos_in_e < capacity
    slot = jnp.where(keep, sorted_e * capacity + pos_in_e, e_total * capacity)

    # dispatch buffer: (E·capacity, d) — row r holds the token routed to
    # expert r//capacity at slot r%capacity (zeros where unfilled)
    dispatch = jnp.zeros((e_total * capacity + 1, d), x.dtype)
    dispatch = dispatch.at[slot].set(tokens[flat_tok[order]])[:-1]

    def a2a(buf):
        if plan is not None:
            return plan(buf)
        return comm.all_to_all(buf, backend=backend)

    # ---- all_to_all: expert-major blocks -> owning devices -------------
    recv = a2a(dispatch.reshape(e_total * capacity, d))
    # recv: for my e_local experts, ep blocks of (e_local·capacity) rows
    recv = recv.reshape(ep, e_local, capacity, d)

    # ---- local expert FFN ----------------------------------------------
    h = jnp.einsum("necd,edf->necf", recv, p["w_gate"])
    u = jnp.einsum("necd,edf->necf", recv, p["w_up"])
    act = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u
    out = jnp.einsum("necf,efd->necd", act, p["w_down"])

    # ---- combine: inverse all_to_all + weighted scatter-add -------------
    back = a2a(out.reshape(ep * e_local * capacity, d))
    back = back.reshape(e_total * capacity, d)
    back = jnp.concatenate([back, jnp.zeros((1, d), x.dtype)], axis=0)
    gathered = back[slot]                                    # (T·k, d)
    contrib = gathered * flat_w[order][:, None].astype(x.dtype)
    y = jnp.zeros((n_tok, d), x.dtype).at[flat_tok[order]].add(
        jnp.where(keep[:, None], contrib, 0))
    return y.reshape(b, s, d)
