"""Train/serve step builders over a device mesh.

Two modes (DESIGN.md — collective backend duality):

* ``auto``     — pjit/GSPMD: params + batch get PartitionSpecs, XLA
  chooses the collectives. The framework-level NCCL-analogue baseline,
  and the path the 512-device dry-run compiles for every cell.
* ``explicit`` — shard_map with the MSCCL++ stack on the critical path:
  DP gradient reduction runs our hierarchical 2PH program (intra-pod
  reduce-scatter → cross-pod all-reduce on 1/L shards → intra-pod
  all-gather) instead of XLA's all-reduce; TP stays inside a nested
  pjit. This is the paper's technique integrated as a first-class
  feature of the trainer.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import comm as comm_lib
from repro.core import selector as sel
from repro.distributed import sharding as shd
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.train import optimizer as opt

__all__ = ["make_train_step", "make_serve_step", "make_sched_step",
           "make_prefill_sched_step", "init_sharded",
           "make_dp_communicators", "TPDecodeComms",
           "compile_decode_plans", "local_batch", "slot_buckets",
           "seq_bucket_rows"]


def _dp_axes(mesh: Mesh, ax: shd.MeshAxes) -> tuple[str, ...]:
    return tuple(a for a in ax.data if a in mesh.shape)


def init_sharded(cfg: ModelConfig, mesh: Mesh, ax: shd.MeshAxes, key,
                 optimizer_cfg: Optional[opt.AdamWConfig] = None):
    """Initialize params (+ opt state) directly into their shardings."""
    pspecs = shd.param_pspecs(cfg, mesh, ax)
    shardings = shd.shardings_for(pspecs, mesh)

    params = jax.jit(
        functools.partial(tf.init_params, cfg),
        out_shardings=shardings)(key)
    if optimizer_cfg is None:
        return params, None
    ospec = {"mu": pspecs, "nu": pspecs, "count": P()}
    osh = shd.shardings_for(ospec, mesh)
    opt_state = jax.jit(opt.adamw_init, out_shardings=osh)(params)
    return params, opt_state


def _pspecs(cfg, mesh, ax, fsdp: bool):
    pspecs = shd.param_pspecs(cfg, mesh, ax)
    if fsdp:
        shapes = jax.eval_shape(functools.partial(tf.init_params, cfg),
                                jax.random.key(0))
        pspecs = shd.apply_fsdp(pspecs, shapes, mesh, ax)
    return pspecs


def make_dp_communicators(mesh: Mesh, ax: shd.MeshAxes) -> dict:
    """Init-once Communicators for the DP gradient-reduction axes
    (paper §5.2 deployment shape: plan at setup, replay every step).

    Two DP axes -> {'node', 'local'} for the hierarchical 2PH path
    (node hops costed on DCN); one -> {'flat'}; zero -> {}.
    """
    dp = _dp_axes(mesh, ax)
    if len(dp) == 2:
        return {
            "node": comm_lib.Communicator(
                dp[0], n=mesh.shape[dp[0]], link=sel.DCN),
            "local": comm_lib.Communicator(dp[1], n=mesh.shape[dp[1]]),
        }
    if len(dp) == 1:
        return {"flat": comm_lib.Communicator(dp[0], n=mesh.shape[dp[0]])}
    return {}


def make_train_step(cfg: ModelConfig, mesh: Mesh, ax: shd.MeshAxes,
                    opt_cfg: opt.AdamWConfig, *, mode: str = "auto",
                    global_batch: int, seq_len: int,
                    remat_policy: str = "none",
                    dp_backend: str = "xla",
                    dp_wire_dtype=None,
                    fsdp: bool = False,
                    donate: bool = True,
                    dp_comms: Optional[dict] = None):
    """Returns jit'd ``step(params, opt_state, batch) -> (params,
    opt_state, metrics)`` with shardings bound to ``mesh``.

    ``dp_comms``: explicit Communicators for the DP axes (see
    ``make_dp_communicators``) — the compile-once/execute-many planning
    objects the ``explicit`` mode reduces gradients through. Built
    automatically when omitted; pass your own to install tuning tables
    or inspect plan caches from the driver."""
    pspecs = _pspecs(cfg, mesh, ax, fsdp)
    psh = shd.shardings_for(pspecs, mesh)
    ospec = {"mu": pspecs, "nu": pspecs, "count": P()}
    osh = shd.shardings_for(ospec, mesh)
    embedded = cfg.frontend != "none"
    bspec = {
        "tokens": shd.batch_pspec(cfg, mesh, ax, global_batch=global_batch,
                                  embedded=embedded),
        "labels": shd.batch_pspec(cfg, mesh, ax, global_batch=global_batch),
    }
    bsh = shd.shardings_for(bspec, mesh)
    dp = _dp_axes(mesh, ax)

    def loss(params, batch):
        return tf.loss_fn(params, cfg, batch, remat_policy=remat_policy)

    if mode == "auto":
        def step(params, opt_state, batch):
            l, grads = jax.value_and_grad(loss)(params, batch)
            params, opt_state, metrics = opt.adamw_update(
                opt_cfg, params, grads, opt_state)
            return params, opt_state, dict(metrics, loss=l)

    elif mode == "explicit":
        # Gradients are computed per-DP-shard inside a shard_map that is
        # MANUAL over the dp axes (model stays auto/GSPMD for TP), then
        # reduced by OUR collectives: 2PH hierarchical across (pod, data)
        # — intra-pod RS, cross-pod AR on 1/L shards, intra-pod AG — the
        # paper's algorithm on the trainer's critical path. The
        # Communicators (and their plan caches) are built HERE, once per
        # step function; tracing replays cached ExecutionPlans.
        ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
        comms = dp_comms if dp_comms is not None \
            else make_dp_communicators(mesh, ax)

        def reduce_leaf(leaf):
            x2 = leaf.reshape(-1, leaf.shape[-1]) if leaf.ndim >= 2 \
                else leaf.reshape(-1, 1)
            if dp_wire_dtype is not None:
                # wire compression (train/compression.py provides the
                # int8+error-feedback variant; bf16 halves DP bytes)
                x2 = x2.astype(dp_wire_dtype)
            if len(dp) == 2:
                red = comm_lib.hierarchical_all_reduce(
                    x2, local=comms["local"], node=comms["node"],
                    backend=dp_backend)
            elif len(dp) == 1:
                red = comms["flat"].all_reduce(x2, backend=dp_backend)
            else:
                red = x2
            return (red / ndp).reshape(leaf.shape).astype(leaf.dtype)

        def local_grads(params, batch):
            l, grads = jax.value_and_grad(loss)(params, batch)
            grads = jax.tree.map(reduce_leaf, grads)
            l = jax.lax.pmean(l, dp) if dp else l
            return l, grads

        rep = jax.tree.map(lambda _: P(), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
        grad_map = shard_map(
            local_grads, mesh=mesh,
            in_specs=(rep, jax.tree.map(lambda s: s, bspec,
                                        is_leaf=lambda x: isinstance(x, P))),
            out_specs=(P(), rep),
            axis_names=set(dp),          # manual over DP; model stays auto
            check_vma=False)

        def step(params, opt_state, batch):
            l, grads = grad_map(params, batch)
            params, opt_state, metrics = opt.adamw_update(
                opt_cfg, params, grads, opt_state)
            return params, opt_state, dict(metrics, loss=l)
    else:
        raise ValueError(mode)

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(
        step,
        in_shardings=(psh, osh, bsh),
        out_shardings=(psh, osh, None),
        donate_argnums=donate_argnums,
    ), bspec


# ---------------------------------------------------------------------------
# explicit-TP decode (paper §5.2: compiled plans on the token hot path)
# ---------------------------------------------------------------------------
def local_batch(mesh: Mesh, ax: shd.MeshAxes, batch: int) -> tuple[int, bool]:
    """(per-device batch rows along the DP axes, whether the batch is
    DP-sharded at all). Mirrors the decode-cache/token sharding rule."""
    dp = _dp_axes(mesh, ax)
    ndp = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if batch % max(ndp, 1) == 0 and batch >= ndp:
        return batch // max(ndp, 1), bool(dp) and ndp > 1
    return batch, False


def slot_buckets(batch_local: int) -> tuple[int, ...]:
    """Active-slot bucket ladder for bucketed plan compilation: powers
    of two up to (and always including) the full local batch."""
    out, k = [], 1
    while k < batch_local:
        out.append(k)
        k *= 2
    out.append(batch_local)
    return tuple(out)


def seq_bucket_rows(batch_local: int, buckets, seq_buckets) -> tuple:
    """The merged row-bucket ladder a sequence-bucketed decode-plan
    family is compiled over: the active-slot buckets plus, per prefill
    sequence bucket ``s``, the ``batch_local * s`` rows a full-width
    fused prefill step pushes through the per-layer AllReduce (smaller
    slot × seq combinations pad up to the nearest bucket — the same
    padding contract slot buckets already use)."""
    rows = set(buckets)
    for s in (seq_buckets or ()):
        if s < 1:
            raise ValueError(f"sequence buckets must be >= 1, got {s}")
        rows.add(batch_local * int(s))
    return tuple(sorted(rows))


def compile_decode_plans(cfg: ModelConfig, comm, *, batch_local: int,
                         tp: int, buckets=None, seq_buckets=None) -> dict:
    """The decode-step collective plans, compiled once at init and
    replayed every generated token (paper §5.2):

    * ``layer_allreduce`` — the per-layer hidden-state AllReduce
      (attention out-proj and MLP down-proj partials; the hybrid
      family's SSM out-proj partial; also the vocab-sharded embedding
      gather-reduce), bucketed over active-slot counts so continuous
      batching replays a handful of plans instead of compiling per
      distinct shape. The int8 KV cache needs no additional plan:
      cache and scale entries are TP-replicated, so quantize/dequantize
      and the per-head scale gather are rank-local;
    * ``logits_allgather`` — the final vocab-sharded logits gather
      (only when the vocab divides the TP axis);
    * ``moe_alltoall`` — MoE family with experts divisible by the axis:
      the expert-parallel dispatch/combine all_to_all, capacity-bucketed
      (one plan per per-rank capacity derived from each slot bucket via
      :func:`~repro.distributed.moe_parallel.ep_capacity`). One plan
      family serves BOTH directions of every MoE layer — dispatch and
      combine move the same ``(e_total * capacity, d_model)`` buffer.

    ``seq_buckets`` — the fused-prefill extension: prompt-chunk lengths
    the serving layer will prefill in one step. Each adds a
    ``batch_local * s`` row bucket to the ``layer_allreduce`` family
    (and the matching capacity to ``moe_alltoall``), so a fused prefill
    micro-step replays the SAME frozen families the one-token decode
    replays, just at a bigger bucket — zero new plan kinds, and the
    exported plan set carries the buckets automatically
    (:class:`~repro.core.comm.BucketedPlan` serializes its ladder).
    The ``logits_allgather`` family needs no sequence buckets: fused
    prefill emits no logits (the final prompt token always runs through
    the combined decode step).
    """
    buckets = tuple(buckets) if buckets else slot_buckets(batch_local)
    rows = seq_bucket_rows(batch_local, buckets, seq_buckets)
    plans = {"layer_allreduce": comm.plan_for(
        "all_reduce", (batch_local, cfg.d_model), cfg.dtype,
        buckets=rows)}
    if cfg.vocab % tp == 0:
        plans["logits_allgather"] = comm.plan_for(
            "all_gather", (batch_local, cfg.vocab // tp), "float32",
            buckets=buckets)
    if cfg.family == "moe" and cfg.moe.num_experts % tp == 0:
        from repro.distributed.moe_parallel import ep_capacity

        e_total = cfg.moe.num_experts
        e_local = e_total // tp
        caps = tuple(sorted({
            e_local * ep_capacity(b, cfg.moe.top_k, e_total)
            for b in rows}))
        plans["moe_alltoall"] = comm.plan_for(
            "all_to_all", (tp * caps[-1], cfg.d_model), cfg.dtype,
            buckets=caps)
    return plans


class TPDecodeComms:
    """The per-layer TP/EP communication hook the explicit decode step
    hands to ``transformer.decode_step`` (see its docstring).

    Every method is pure plan replay inside traced code: the
    :class:`~repro.core.comm.BucketedPlan` s were compiled at engine /
    step-build time, so tracing the decode step does zero selection,
    zero pass-pipeline work, and zero executor lowering — the MSCCL++
    deployment contract, now on the token hot path.

    For the MoE family the same axis doubles as the expert-parallel
    axis: ``moe_plan`` is the capacity-bucketed dispatch/combine
    all_to_all and :meth:`moe` runs the sparse EP layer through it.
    """

    def __init__(self, cfg: ModelConfig, axis: str, tp: int, *,
                 hidden_plan, logits_plan=None, moe_plan=None):
        self.cfg = cfg
        self.axis = axis
        self.tp = tp
        self.hidden_plan = hidden_plan      # bucketed all_reduce (b, d_model)
        self.logits_plan = logits_plan      # bucketed all_gather or None
        self.moe_plan = moe_plan            # bucketed EP all_to_all or None
        self.vocab_sharded = logits_plan is not None

    def head_offset(self, nh_local: int):
        """Global index of this shard's first query head."""
        return jax.lax.axis_index(self.axis) * nh_local

    def ssm_offset(self, d_local: int):
        """Global index of this shard's first SSM ``d_inner`` row
        (hybrid family): the SSM branch computes its recurrence on
        ``d_local`` rows starting here, and its output partial is
        completed by :meth:`hidden` — the same per-layer AllReduce
        plan the attention/MLP partials replay."""
        return jax.lax.axis_index(self.axis) * d_local

    def moe(self, lp, x):
        """Expert-parallel MoE layer on a (b, s, d_model) hidden state:
        dispatch and combine are replays of the init-compiled
        capacity-bucketed all_to_all plan. Lossless capacity
        (``capacity_factor=None``) so the result matches the dense
        oracle exactly — no token ever drops on the decode hot path."""
        from repro.distributed.moe_parallel import moe_layer_ep

        return moe_layer_ep(lp, x, self.cfg, axis=self.axis,
                            capacity_factor=None, plan=self.moe_plan)

    def hidden(self, x):
        """AllReduce a (b, s, d_model) hidden-state partial over TP."""
        b, s, d = x.shape
        return self.hidden_plan(x.reshape(b * s, d)).reshape(b, s, d)

    def embed(self, table, tokens):
        """Lookup on a (possibly vocab-sharded) embedding table: mask
        out-of-shard tokens to zero rows, then the same AllReduce plan
        completes the gather (zero rows are exact under the sum)."""
        if not self.vocab_sharded:
            return table[tokens]
        vloc = table.shape[0]
        off = jax.lax.axis_index(self.axis) * vloc
        idx = tokens - off
        ok = (idx >= 0) & (idx < vloc)
        x = jnp.where(ok[:, None], table[jnp.clip(idx, 0, vloc - 1)], 0)
        return self.hidden_plan(x)

    def logits(self, params, hidden):
        """(b, 1, d_model) hidden -> (b, vocab) f32 logits, gathering
        the vocab-sharded columns through the compiled AllGather plan."""
        cfg = self.cfg
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        local = jnp.einsum("bsd,dv->bsv", hidden, w).astype(jnp.float32)[:, 0]
        if not self.vocab_sharded:
            return local
        b = local.shape[0]
        g = self.logits_plan(local)                      # (tp*b, vocab/tp)
        return g.reshape(self.tp, b, -1).transpose(1, 0, 2).reshape(b, -1)


def _explicit_tp(cfg: ModelConfig, mesh: Mesh, ax: shd.MeshAxes, *,
                 batch: int, kv_lens, kv_quant: bool, comm, plans,
                 manual_dp: bool, seq_buckets=None):
    """Set-up shared by the explicit-TP steps: the manual axis set, a
    spec filter, the explicit param/cache specs and the plan-replay
    comms. The steps' ``shard_map`` in/out specs may name manual axes
    only, so ``strip`` removes the axes left to GSPMD
    (``manual_dp=False``); the body then sees the whole batch along
    them, and the plans are bucketed for that batch."""
    ok, why = shd.explicit_decode_supported(cfg, mesh, ax)
    if not ok:
        raise ValueError(f"mode='explicit' unsupported here: {why}")
    dp = _dp_axes(mesh, ax)
    manual = {ax.model} | (set(dp) if manual_dp else set())
    auto_axes = [a for a in mesh.axis_names if a not in manual]

    def strip(specs):
        return functools.reduce(shd.strip_axis, auto_axes, specs)

    tp = int(mesh.shape[ax.model])
    pspecs_x = shd.explicit_decode_pspecs(cfg, mesh, ax)
    cspecs_x = shd.explicit_decode_cache_pspecs(
        cfg, mesh, ax, batch=batch, kv_lens=kv_lens, kv_quant=kv_quant)
    if comm is None:
        comm = comm_lib.Communicator(ax.model, n=tp,
                                     backend=comm_lib.default_backend())
    if plans is None:
        b_plan = local_batch(mesh, ax, batch)[0] if manual_dp else batch
        plans = compile_decode_plans(cfg, comm, batch_local=b_plan, tp=tp,
                                     seq_buckets=seq_buckets)
    comms = TPDecodeComms(cfg, ax.model, tp,
                          hidden_plan=plans["layer_allreduce"],
                          logits_plan=plans.get("logits_allgather"),
                          moe_plan=plans.get("moe_alltoall"))
    return manual, strip, pspecs_x, cspecs_x, comms


def make_serve_step(cfg: ModelConfig, mesh: Mesh, ax: shd.MeshAxes, *,
                    batch: int, max_kv: int, donate: bool = True,
                    fsdp: bool = False, kv_quant: bool = False,
                    mode: str = "auto", comm=None, plans=None,
                    manual_dp: bool = True):
    """jit'd one-token decode step bound to mesh shardings.

    serve_step(params, cache, tokens, pos) -> (logits, cache)
    ``kv_quant``: int8 KV cache with per-token scales (§Perf C).

    Modes (the serving analogue of ``make_train_step``'s duality):

    * ``auto``     — pjit/GSPMD partitions the decode step; XLA inserts
      the per-layer TP psum (the NCCL-role baseline).
    * ``explicit`` — the decode step runs inside a shard_map MANUAL over
      the TP (``model``) axis, and the per-layer hidden-state
      AllReduces (attention out-proj, MLP down-proj, and the hybrid
      family's SSM out-proj) + the vocab-sharded embedding/logits
      collectives are replays of init-compiled
      :class:`~repro.core.comm.ExecutionPlan` s (bucketed over
      active-slot counts) — the paper's §5.2 decode hot path. For the
      MoE family the same axis carries expert parallelism: the per-layer
      dispatch/combine run through the init-compiled capacity-bucketed
      all_to_all plan (``TPDecodeComms.moe``). The KV
      cache is kept whole along ``model`` (heads stay full per device;
      only weights shard), so attention math is local — with
      ``kv_quant`` the int8 cache and its scale entries replicate the
      same way, so quantize/dequantize is rank-local too; the hybrid
      SSM state is the one cache entry that stays model-sharded
      (``sharding.explicit_decode_cache_pspecs``). The DP axes are
      included in the manual set by default (``manual_dp=True``), which
      keeps the whole step fully manual. ``manual_dp=False`` leaves the
      DP axes to GSPMD (partial-manual shard_map).

    ``comm``: the TP :class:`~repro.core.comm.Communicator` owning the
    decode plans (the engine passes its own so init-compiled plans are
    shared); built here when omitted. ``plans``: an already-compiled
    (or plan-file-loaded, see ``comm.load_plan_set``) decode plan dict
    in the :func:`compile_decode_plans` shape — pass it so every step
    built for this engine replays the SAME plan objects (shared
    bucket-hit counters, and for replicas the §4.4 ship-the-plan-file
    deployment model); compiled here when omitted.
    """
    pspecs = _pspecs(cfg, mesh, ax, fsdp)
    psh = shd.shardings_for(pspecs, mesh)
    kv_lens = [min(w, max_kv) if w is not None else max_kv
               for w in tf.layer_windows(cfg)]
    cspecs = shd.cache_pspecs(cfg, mesh, ax, batch=batch, kv_lens=kv_lens)
    if kv_quant and "k" in cspecs:
        cspecs = dict(cspecs,
                      k_scale=list(cspecs["k"]), v_scale=list(cspecs["v"]))
    dp = _dp_axes(mesh, ax)
    d = dp if len(dp) > 1 else (dp[0] if dp else None)
    _, batch_sharded = local_batch(mesh, ax, batch)
    tok_spec = P(d) if batch_sharded else P(None)
    tsh = NamedSharding(mesh, tok_spec)

    if mode == "auto":
        csh = shd.shardings_for(cspecs, mesh)

        def step(params, cache, tokens, pos):
            return tf.decode_step(params, cfg, cache, tokens, pos)

        return jax.jit(
            step,
            in_shardings=(psh, csh, tsh, None),
            out_shardings=(None, csh),
            donate_argnums=(1,) if donate else (),
        ), cspecs

    if mode != "explicit":
        raise ValueError(mode)

    if fsdp:
        raise ValueError(
            "mode='explicit' does not support fsdp: the manual body uses "
            "the explicit-TP param layout, not the ZeRO-3 decoration")
    # cache whole along TP — except the hybrid SSM state, which stays
    # model-sharded (each rank carries its d_inner rows)
    manual, strip, pspecs_x, cspecs_x, comms = _explicit_tp(
        cfg, mesh, ax, batch=batch, kv_lens=kv_lens, kv_quant=kv_quant,
        comm=comm, plans=plans, manual_dp=manual_dp)
    csh_x = shd.shardings_for(cspecs_x, mesh)
    logit_spec = P(d if batch_sharded else None, None)

    def local_step(params, cache, tokens, pos):
        return tf.decode_step(params, cfg, cache, tokens, pos, comms=comms)

    mapped = shard_map(
        local_step, mesh=mesh,
        in_specs=strip((pspecs_x, cspecs_x, tok_spec, P())),
        out_specs=strip((logit_spec, cspecs_x)),
        axis_names=manual, check_vma=False)

    # Params deliberately carry no jit in_sharding: the engine's arrays
    # live in their auto-mode (GSPMD) placement — shard_map's in_specs
    # reshard them to the explicit layout (KV replicated) inside the jit
    # instead of rejecting the committed arrays at the boundary.
    return jax.jit(
        mapped,
        in_shardings=(None, csh_x, tsh, None),
        out_shardings=(NamedSharding(mesh, logit_spec), csh_x),
        donate_argnums=(1,) if donate else (),
    ), cspecs_x


def _mask_slots(new_cache, old_cache, active):
    """Per-slot cache select for the scheduler step: inactive slots keep
    their old recurrent state (the hybrid ``ssm`` leaves, rwkv6's state)
    bit-exactly; the computed updates for those rows are discarded. The
    attention leaves pass through: the step never wrote an inactive
    row's K/V. Every decode-cache leaf carries the batch at axis 1 —
    ``(groups, batch, ...)``, see ``transformer.init_cache``. Scoped
    ``mask_slots``: a profile charges this pass to it."""
    def sel(new, old):
        m = active.reshape((1, active.shape[0]) + (1,) * (new.ndim - 2))
        return jnp.where(m, new, old)
    with jax.named_scope("mask_slots"):
        return {n: leaf if n in tf.KV_LEAVES
                else jax.tree.map(sel, leaf, old_cache[n])
                for n, leaf in new_cache.items()}


def make_sched_step(cfg: ModelConfig, mesh: Mesh, ax: shd.MeshAxes, *,
                    batch: int, max_kv: int, kv_quant: bool = False,
                    mode: str = "auto", comm=None, plans=None):
    """jit'd continuous-batching decode step (the scheduler hot path).

    sched_step(params, cache, tokens, pos, active) -> (logits, cache)

    Differs from :func:`make_serve_step` in exactly the two ways
    continuous batching needs:

    * ``pos`` is a ``(batch,)`` int32 vector — every slot decodes (or
      chunk-prefills) at its own depth (per-row RoPE, cache write, and
      validity mask in ``blocks.decode_attention``);
    * ``active`` is a ``(batch,)`` bool mask — inactive slots' cache
      rows pass through bit-exactly, so chunked-prefill micro-steps can
      advance a subset of slots while decode slots hold still, and
      freed slots carry stale state harmlessly. The step writes K/V
      only for active slots, one row each in place
      (``transformer.decode_step``); ``_mask_slots`` selects the
      recurrent state, the only leaves computed for every row.

    The cache is not donated here: the caller that owns it decides
    (``Scheduler`` donates in auto mode, on the engine's rule).

    Because every per-row op in the decode step is row-independent
    (einsums contract within a row, softmax/rms_norm are per-row, and
    the replayed collectives are elementwise across rows — the MoE
    all_to_all is lossless-capacity so co-batched rows can never evict
    each other's tokens), a request's token stream is bit-identical no
    matter which other slots it shares a step with — the property
    ``tests/test_scheduler.py`` pins.

    The batch must NOT be DP-sharded: one scheduler owns one replica's
    slots; data-parallel scale-out is the Router's job (one replica per
    device slice, each replaying the same exported plan set).
    ``plans``: pass the engine's init-compiled plan family so every
    bucketed step function replays the SAME plans (one set of bucket
    hit counters; §5.2 compile-once contract) instead of compiling its
    own per-bucket family.
    """
    _, batch_sharded = local_batch(mesh, ax, batch)
    if batch_sharded:
        raise ValueError(
            "make_sched_step keeps the batch unsharded (slots live on one "
            "replica); fan out replicas with serve.router instead of "
            "DP-sharding the scheduler batch")
    pspecs = _pspecs(cfg, mesh, ax, False)
    psh = shd.shardings_for(pspecs, mesh)
    kv_lens = [min(w, max_kv) if w is not None else max_kv
               for w in tf.layer_windows(cfg)]
    cspecs = shd.cache_pspecs(cfg, mesh, ax, batch=batch, kv_lens=kv_lens)
    if kv_quant and "k" in cspecs:
        cspecs = dict(cspecs,
                      k_scale=list(cspecs["k"]), v_scale=list(cspecs["v"]))
    tsh = NamedSharding(mesh, P(None))

    # Named ``step_sched`` (program ``jit_step_sched``) so that a profile
    # tells it from the fused-prefill step.
    if mode == "auto":
        csh = shd.shardings_for(cspecs, mesh)

        def step_sched(params, cache, tokens, pos, active):
            logits, new_cache = tf.decode_step(params, cfg, cache,
                                               tokens, pos, active=active)
            return logits, _mask_slots(new_cache, cache, active)

        return jax.jit(
            step_sched,
            in_shardings=(psh, csh, tsh, tsh, tsh),
            out_shardings=(None, csh),
        ), cspecs

    if mode != "explicit":
        raise ValueError(mode)

    manual, strip, pspecs_x, cspecs_x, comms = _explicit_tp(
        cfg, mesh, ax, batch=batch, kv_lens=kv_lens, kv_quant=kv_quant,
        comm=comm, plans=plans, manual_dp=True)
    csh_x = shd.shardings_for(cspecs_x, mesh)

    def step_sched(params, cache, tokens, pos, active):
        logits, new_cache = tf.decode_step(params, cfg, cache, tokens, pos,
                                           comms=comms, active=active)
        return logits, _mask_slots(new_cache, cache, active)

    mapped = shard_map(
        step_sched, mesh=mesh,
        in_specs=strip((pspecs_x, cspecs_x, P(None), P(None), P(None))),
        out_specs=strip((P(None, None), cspecs_x)),
        axis_names=manual, check_vma=False)

    return jax.jit(
        mapped,
        in_shardings=(None, csh_x, tsh, tsh, tsh),
        out_shardings=(NamedSharding(mesh, P(None, None)), csh_x),
    ), cspecs_x


def make_prefill_sched_step(cfg: ModelConfig, mesh: Mesh, ax: shd.MeshAxes,
                            *, batch: int, seq: int, max_kv: int,
                            kv_quant: bool = False, mode: str = "auto",
                            comm=None, plans=None):
    """jit'd fused-prefill micro-step (the scheduler prefill hot path).

    prefill_step(params, cache, tokens, pos, n_tok) -> cache

    The chunked counterpart of :func:`make_sched_step`: ``tokens`` is
    ``(batch, seq)`` — each row's next prompt chunk, left-aligned and
    right-padded — ``pos`` is each row's write depth and ``n_tok`` its
    valid-chunk length (0 = untouched slot; rows with ``n_tok=0`` pass
    their cache through bit-exactly, subsuming ``make_sched_step``'s
    ``active`` mask). No logits come back: fused prefill only fills the
    cache, and the scheduler always runs a row's FINAL prompt token
    through the combined decode step so first-token sampling (and the
    vocab collective) stay on the decode path.

    Exactness contract (see ``blocks.prefill_attention``): for windowed
    layers a row's chunk must satisfy ``n_tok == 1`` or
    ``pos + n_tok <= kv_len`` — the scheduler sizes chunks to respect
    the ring (``serve.scheduler``). ``seq`` must not exceed the smallest
    layer kv_len for the same reason.

    ``mode='explicit'`` replays the SAME init-compiled plan families the
    decode step replays — the per-layer AllReduce just hits the
    ``batch * seq`` row bucket that :func:`compile_decode_plans` added
    for this ``seq`` (``seq_buckets``) instead of the active-slot
    bucket. Pass the engine's ``comm``/``plans`` so prefill and decode
    share one plan set (one family of bucket-hit counters).
    """
    if cfg.family not in ("dense", "moe", "hybrid"):
        raise ValueError(
            f"fused prefill covers the dense, MoE, and hybrid families; "
            f"{cfg.family!r} prefills token-by-token through the decode "
            f"path")
    _, batch_sharded = local_batch(mesh, ax, batch)
    if batch_sharded:
        raise ValueError(
            "make_prefill_sched_step keeps the batch unsharded (slots "
            "live on one replica); fan out replicas with serve.router "
            "instead of DP-sharding the scheduler batch")
    kv_lens = [min(w, max_kv) if w is not None else max_kv
               for w in tf.layer_windows(cfg)]
    if seq > min(kv_lens):
        raise ValueError(
            f"fused-prefill chunk length {seq} exceeds the smallest layer "
            f"kv_len {min(kv_lens)}: a chunk wider than the KV ring can "
            f"overwrite slots its own earlier queries still read — shrink "
            f"the sequence bucket (or raise max_kv)")
    pspecs = _pspecs(cfg, mesh, ax, False)
    psh = shd.shardings_for(pspecs, mesh)
    cspecs = shd.cache_pspecs(cfg, mesh, ax, batch=batch, kv_lens=kv_lens)
    if kv_quant and "k" in cspecs:
        cspecs = dict(cspecs,
                      k_scale=list(cspecs["k"]), v_scale=list(cspecs["v"]))
    tsh = NamedSharding(mesh, P(None))
    tok2 = NamedSharding(mesh, P(None, None))

    # Named ``step_prefill`` (program ``jit_step_prefill``), apart from
    # the decode step's ``step_sched``.
    if mode == "auto":
        csh = shd.shardings_for(cspecs, mesh)

        def step_prefill(params, cache, tokens, pos, n_tok):
            return tf.prefill_step(params, cfg, cache, tokens, pos, n_tok)

        return jax.jit(
            step_prefill,
            in_shardings=(psh, csh, tok2, tsh, tsh),
            out_shardings=csh,
        ), cspecs

    if mode != "explicit":
        raise ValueError(mode)

    manual, strip, pspecs_x, cspecs_x, comms = _explicit_tp(
        cfg, mesh, ax, batch=batch, kv_lens=kv_lens, kv_quant=kv_quant,
        comm=comm, plans=plans, manual_dp=True, seq_buckets=(seq,))
    csh_x = shd.shardings_for(cspecs_x, mesh)

    def step_prefill(params, cache, tokens, pos, n_tok):
        return tf.prefill_step(params, cfg, cache, tokens, pos, n_tok,
                               comms=comms)

    mapped = shard_map(
        step_prefill, mesh=mesh,
        in_specs=strip((pspecs_x, cspecs_x, P(None, None), P(None),
                        P(None))),
        out_specs=strip(cspecs_x),
        axis_names=manual, check_vma=False)

    return jax.jit(
        mapped,
        in_shardings=(None, csh_x, tok2, tsh, tsh),
        out_shardings=csh_x,
    ), cspecs_x


def make_prefill_step(cfg: ModelConfig, mesh: Mesh, ax: shd.MeshAxes, *,
                      global_batch: int, seq_len: int, fsdp: bool = False,
                      remat_policy: str = "none"):
    """jit'd full-sequence forward returning last-position logits (the
    prefill cost driver; cache filling is engine-side)."""
    pspecs = _pspecs(cfg, mesh, ax, fsdp)
    psh = shd.shardings_for(pspecs, mesh)
    embedded = cfg.frontend != "none"
    bspec = shd.batch_pspec(cfg, mesh, ax, global_batch=global_batch,
                            embedded=embedded)
    bsh = NamedSharding(mesh, bspec)

    def step(params, tokens):
        hidden = tf.forward(params, cfg, tokens, remat_policy=remat_policy)
        return tf.logits_fn(params, cfg, hidden[:, -1:, :])[:, 0]

    return jax.jit(step, in_shardings=(psh, bsh), out_shardings=None), bspec
