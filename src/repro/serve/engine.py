"""Batched inference engine: prefill + decode with a sharded KV cache.

Mirrors the paper's §5.2 setting (vLLM + tensor parallelism): the
decode step is dominated by the per-layer TP AllReduce, which is where
the MSCCL++ collectives plug in; prefill is compute-bound so the gain
concentrates in decode — the asymmetry Figure 10 reports.

Deployment shape (§5.2): the engine owns a :class:`Communicator` for
the TP axis and compiles the decode-step collective plans at __init__
— the per-layer hidden-state AllReduce and the vocab-sharded logits
AllGather, **bucketed** over active-slot counts
(:func:`~repro.distributed.step.compile_decode_plans`), so a
continuous-batching stack with varying slot occupancy replays a
handful of plans instead of compiling per distinct shape. Every
program is statically verified at compilation (``ServeConfig.verify``,
see :mod:`repro.core.verify`).

With ``mode="explicit"`` the decode step itself is the explicit-TP
shard_map path (:func:`~repro.distributed.step.make_serve_step`): every
generated token REPLAYS those init-compiled plans on the hot path —
compile counters stay flat across decode calls. ``mode="auto"`` keeps
the GSPMD baseline (XLA-inserted psum); the plans then remain the
cost/inspection artifact.

Runtime guardrails (the fallback ladder, docs/robustness.md): every
step call is guarded — transient executor failures retry with bounded
exponential backoff; an optional watchdog (``plan_timeout_s``) bounds
each step's wall clock; an optional numeric guard
(``guard_numerics``) rejects non-finite logits; and any unrecovered
explicit-path failure of a step that has already compiled degrades
the engine to the auto (GSPMD) path and re-runs the step there, so
serving continues on the safe path rather than crashing or emitting
wrong tokens. A step that fails to trace or compile (a Mosaic
refusal, an XLA lowering error, a bucket overflow) is not a fault to
serve around: the error propagates, so a run that reports the
explicit path really ran it. Health
counters (``verified``, ``retries``, ``fallbacks``,
``faults_detected``) are surfaced through ``plan_report()``. The
guards add **zero per-token work on the replay hot path** when the
watchdog and numeric guard are off (the default): the guarded call is
a plain ``step_fn`` invocation inside a try/except.

The engine supports continuous-batching-lite: a fixed slot count,
per-slot position counters, and slot recycling when a sequence emits
EOS.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import comm as comm_lib
from repro.core import faults
from repro.distributed import sharding as shd
from repro.distributed.step import (compile_decode_plans, local_batch,
                                    make_serve_step)
from repro.models import transformer as tf
from repro.models.config import ModelConfig

__all__ = ["ServeConfig", "Engine"]


def _check_plan_set(cfg: ModelConfig, plans: dict, *, tp: int,
                    batch_local: int, seq_buckets=None) -> None:
    """Validate a loaded decode-plan set against this engine's
    config/mesh. The §4.4 deployment failure mode is shipping plan
    files compiled for a different model, axis size, or batch — that
    must degrade visibly (auto fallback + health counter) rather than
    replay wrong programs. ``seq_buckets``: the fused-prefill sequence
    buckets this engine is configured for — each needs its
    ``batch_local * s`` row bucket in the ``layer_allreduce`` ladder,
    else the fused prefill micro-step would overflow the shipped plan
    family at trace time. Raises ValueError with the mismatch."""
    if tp <= 1:
        raise ValueError("decode plans need a TP axis of size > 1")
    ar = plans.get("layer_allreduce")
    if ar is None:
        raise ValueError(
            f"plan set has no 'layer_allreduce' (names: {sorted(plans)})")

    def dims(p):
        if isinstance(p, comm_lib.BucketedPlan):
            return p.n, p.cols, p.buckets[-1], p.dtype
        return p.n, p.shape[1], p.shape[0], p.dtype

    n, cols, top, dtype = dims(ar)
    if n != tp:
        raise ValueError(f"layer_allreduce compiled for axis size {n}; "
                         f"this mesh has tp={tp}")
    if cols != cfg.d_model:
        raise ValueError(f"layer_allreduce compiled for d_model={cols}; "
                         f"this config has {cfg.d_model}")
    if dtype != cfg.dtype:
        raise ValueError(f"layer_allreduce compiled for dtype {dtype}; "
                         f"this config computes in {cfg.dtype}")
    if top < batch_local:
        raise ValueError(
            f"layer_allreduce top bucket {top} < local batch "
            f"{batch_local}: re-export the set with the serving batch")
    for s in (seq_buckets or ()):
        need = batch_local * int(s)
        ladder = (ar.buckets if isinstance(ar, comm_lib.BucketedPlan)
                  else (ar.shape[0],))
        if need not in ladder:
            raise ValueError(
                f"layer_allreduce ladder {tuple(ladder)} is missing the "
                f"{need}-row bucket for prefill sequence bucket {s} "
                f"(batch_local={batch_local}): re-export the plan set "
                f"with prefill seq buckets "
                f"(compile_decode_plans(..., seq_buckets={tuple(seq_buckets)}))")
    if cfg.vocab % tp == 0 and "logits_allgather" not in plans:
        raise ValueError("plan set missing 'logits_allgather' for the "
                         "vocab-sharded logits path")
    if (cfg.family == "moe" and cfg.moe.num_experts % tp == 0
            and "moe_alltoall" not in plans):
        raise ValueError("plan set missing 'moe_alltoall' for the MoE "
                         "expert-parallel path")


@dataclasses.dataclass
class ServeConfig:
    batch: int = 8
    max_kv: int = 1024
    eos_id: int = 2
    temperature: float = 0.0       # 0 -> greedy
    mode: str = "auto"             # 'auto' (GSPMD) | 'explicit' (plan replay)
    kv_quant: bool = False         # int8 KV cache with per-token scales
    # fused-prefill sequence buckets (prompt-chunk lengths the scheduler
    # prefills in one micro-step); None = token-by-token prefill plans only
    prefill_seq_buckets: Optional[tuple] = None
    # -- robustness knobs (docs/robustness.md) -----------------------------
    verify: str = "strict"         # plan verification: 'off'|'warn'|'strict'
    max_retries: int = 2           # bounded retry on transient step failure
    retry_backoff_s: float = 0.05  # base of the exponential backoff
    plan_timeout_s: Optional[float] = None   # per-step watchdog (None = off)
    guard_numerics: bool = False   # reject non-finite logits, redo on auto
    # -- profiling (docs/profiling.md) -------------------------------------
    trace: bool = False            # capture per-instruction plan traces


class Engine:
    def __init__(self, cfg: ModelConfig, params, mesh, serve_cfg: ServeConfig,
                 ax: shd.MeshAxes = shd.MeshAxes(),
                 comm: Optional[comm_lib.Communicator] = None,
                 mode: Optional[str] = None,
                 decode_plans: Optional[dict] = None):
        """``decode_plans``: an already-built decode plan set — typically
        :func:`repro.core.comm.load_plan_set` output, the §4.4 replica
        deployment model (compile once on a planner host, ship the JSON
        files, every replica replays identical programs). Validated
        against this config/mesh; a rejected set degrades to auto like
        a plan-compile failure would. Omitted -> compiled here."""
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.ax = ax
        self.scfg = serve_cfg
        mode = mode if mode is not None else serve_cfg.mode
        if mode not in ("auto", "explicit"):
            raise ValueError(f"unknown serve mode {mode!r}")
        #: the mode serving was configured for; ``self.mode`` is the mode
        #: actually running — they diverge exactly when this replica is
        #: degraded (router surfaces the difference per replica)
        self.requested_mode = mode
        #: runtime guardrail counters; plan_report() merges these with
        #: the communicator's compile-side health (verified, recompiles)
        self.health = {"retries": 0, "fallbacks": 0, "faults_detected": 0,
                       "timeouts": 0}
        # exact-replay recovery (re-running a detected-bad step from its
        # pre-step state) needs the inputs alive after the call, so the
        # detecting guards disable donation; the default path keeps it
        self.donates = not (serve_cfg.guard_numerics
                            or serve_cfg.plan_timeout_s is not None)
        self._pool: Optional[concurrent.futures.ThreadPoolExecutor] = None

        # -- compile-once planning (§5.2): TP communicator + bucketed
        # decode plans, BEFORE the step function so explicit mode replays
        # exactly these artifacts. Every program is verified here —
        # compile time — so the replay hot path carries no checking.
        tp = int(mesh.shape.get(ax.model, 1))
        self.comm = comm if comm is not None else comm_lib.Communicator(
            ax.model, n=tp, backend=comm_lib.default_backend(),
            verify=serve_cfg.verify, trace=serve_cfg.trace)
        b_local, _ = local_batch(mesh, ax, serve_cfg.batch)
        self.decode_plans: dict = {}
        plan_err: Optional[Exception] = None
        if decode_plans is not None:
            try:
                _check_plan_set(cfg, decode_plans, tp=tp,
                                batch_local=b_local,
                                seq_buckets=serve_cfg.prefill_seq_buckets)
                self.decode_plans = dict(decode_plans)
            except ValueError as e:   # mismatched/incomplete shipped set
                plan_err = e
                warnings.warn(
                    f"loaded decode-plan set rejected ({e}); serving "
                    f"without plan artifacts", stacklevel=2)
        elif tp > 1:
            try:
                self.decode_plans = compile_decode_plans(
                    cfg, self.comm, batch_local=b_local, tp=tp,
                    seq_buckets=serve_cfg.prefill_seq_buckets)
            except ValueError as e:   # plan verification failure
                plan_err = e
                warnings.warn(
                    f"decode-plan compilation failed ({e}); serving "
                    f"without plan artifacts", stacklevel=2)

        self.mode = mode
        #: set once step_fn has returned: until then a failure is a
        #: trace/compile error and propagates (see _run_step)
        self._step_ran = False
        if mode == "explicit":
            if plan_err is not None:
                warnings.warn(
                    f"mode='explicit' unavailable (plan compilation "
                    f"failed: {plan_err}); falling back to auto (GSPMD) "
                    f"decode", stacklevel=2)
                self.health["fallbacks"] += 1
                self.mode = "auto"
            else:
                try:
                    self.step_fn = self._build_step("explicit")
                except (NotImplementedError, ValueError) as e:
                    warnings.warn(
                        f"mode='explicit' unavailable ({e}); falling back "
                        f"to auto (GSPMD) decode", stacklevel=2)
                    self.health["fallbacks"] += 1
                    self.mode = "auto"
        if self.mode == "auto":
            self.step_fn = self._build_step("auto")
        self.reset()

    def reset(self) -> None:
        """Start a new batch: a zeroed KV cache, position 0, no slot
        active. Compiled steps and plans are kept."""
        self.cache = tf.init_cache(
            self.cfg, self.scfg.batch, self.scfg.max_kv,
            dtype=jnp.int8 if self.scfg.kv_quant else None)
        self.pos = 0
        self.active = np.zeros(self.scfg.batch, bool)

    def _build_step(self, mode: str):
        kw = (dict(comm=self.comm, plans=self.decode_plans or None)
              if mode == "explicit" else {})
        fn, _ = make_serve_step(
            self.cfg, self.mesh, self.ax, batch=self.scfg.batch,
            max_kv=self.scfg.max_kv, donate=self.donates, mode=mode,
            kv_quant=self.scfg.kv_quant, **kw)
        return fn

    # -- guarded execution (the runtime half of the robustness layer) ------
    def _dispatch(self, args):
        """One step_fn call, under the watchdog when configured. The
        un-watched path is a plain call: zero added per-token work.
        The watchdog arms only in explicit mode — ``plan_timeout_s``
        bounds *plan replay*; the auto (GSPMD) path has no plan to
        watch, and its first trace after a fallback may legitimately
        take longer than any replay budget."""
        if self.scfg.plan_timeout_s is None or self.mode != "explicit":
            return self.step_fn(*args)
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        fut = self._pool.submit(
            lambda: jax.block_until_ready(self.step_fn(*args)))
        try:
            return fut.result(timeout=self.scfg.plan_timeout_s)
        except concurrent.futures.TimeoutError:
            # abandon the stalled worker: a fresh pool serves the next
            # step so the recovery path never queues behind the stall
            self._pool.shutdown(wait=False)
            self._pool = None
            raise TimeoutError(
                f"decode step exceeded plan_timeout_s="
                f"{self.scfg.plan_timeout_s}") from None

    def _run_step(self, tokens):
        """step_fn with the guardrail ladder: bounded retry with
        exponential backoff for failures, then degradation to the auto
        path, which re-runs the step; a watchdog timeout or the numeric
        guard degrades at once. Until the step has run once, an error
        other than an injected fault or a timeout is a trace/compile
        failure (a Mosaic refusal, an XLA lowering error): it
        propagates, never retried or served around."""
        args = (self.params, self.cache, tokens, jnp.int32(self.pos))
        attempt = 0
        while True:
            try:
                logits, cache = self._dispatch(args)
            except TimeoutError as e:
                self.health["timeouts"] += 1
                self.health["faults_detected"] += 1
                return self._fallback_to_auto(str(e), args)
            except Exception as e:
                if not (self._step_ran or isinstance(e, faults.FaultInjected)):
                    raise
                if attempt < self.scfg.max_retries:
                    attempt += 1
                    self.health["retries"] += 1
                    time.sleep(self.scfg.retry_backoff_s
                               * (2 ** (attempt - 1)))
                    continue
                return self._fallback_to_auto(
                    f"failure persisted through {attempt} retries: {e}",
                    args)
            self._step_ran = True
            if self.scfg.guard_numerics:
                if not bool(jnp.isfinite(logits).all()):
                    self.health["faults_detected"] += 1
                    return self._fallback_to_auto(
                        "non-finite logits (corrupted step output)", args)
            return logits, cache

    def _fallback_to_auto(self, reason: str, args):
        """Graceful degradation: rebuild the step on the auto (GSPMD)
        path and re-run the failed step from its pre-step state. The
        auto jit's in_shardings reshard the existing cache, so serving
        continues in place."""
        if self.mode == "auto":
            raise RuntimeError(
                f"decode step failed on the auto (GSPMD) path — no "
                f"further fallback: {reason}")
        warnings.warn(
            f"explicit decode step failed ({reason}); falling back to "
            f"auto (GSPMD) for the remainder of serving", stacklevel=3)
        self.health["fallbacks"] += 1
        self.mode = "auto"
        self.step_fn = self._build_step("auto")
        return self._dispatch(args)

    def plan_report(self) -> dict:
        """Per-bucket cost cards + dispatch hit counts of the decode-step
        plans, plus the per-token predicted communication time at full
        slot occupancy: per layer, 2 AllReduces (dense: attention
        out-proj + MLP down-proj), 3 AllReduces (hybrid: + the SSM
        out-proj), or 1 AllReduce + 2 EP all_to_alls (MoE: out-proj +
        dispatch/combine), plus the embedding gather-reduce and final
        logits gather. The int8 KV cache adds no collective (see
        ``compile_decode_plans``). ``health`` merges the runtime
        guardrail counters with the communicator's compile-side ones
        (verified programs, verification failures, recompile-once
        degradations, backend+mode fallbacks). With
        ``ServeConfig.trace=True`` the ``trace`` key carries each
        plan's latest captured timeline summary (None until that plan
        has executed; see docs/profiling.md)."""
        def top_plan(p):
            return p.plans[p.buckets[-1]] if isinstance(
                p, comm_lib.BucketedPlan) else p

        cards = {}
        per_tok = 0.0
        for name, p in self.decode_plans.items():
            if isinstance(p, comm_lib.BucketedPlan):
                cards[name] = p.report()
            else:
                cards[name] = p.cost_card()
        ar = self.decode_plans.get("layer_allreduce")
        if ar is not None:
            # dense layers replay it twice (attention out-proj + MLP
            # down-proj); hybrid adds the SSM out-proj; MoE layers once
            # — the expert block's combine happens in the all_to_all
            # pair, not an AllReduce
            ar_per_layer = {"moe": 1, "hybrid": 3}.get(self.cfg.family, 2)
            per_tok += ar_per_layer * self.cfg.n_layers * \
                top_plan(ar).estimate_us
            if "logits_allgather" in self.decode_plans:
                # vocab-sharded embed lookup reuses the AllReduce plan
                per_tok += top_plan(ar).estimate_us
        ag = self.decode_plans.get("logits_allgather")
        if ag is not None:
            per_tok += top_plan(ag).estimate_us
        a2a = self.decode_plans.get("moe_alltoall")
        if a2a is not None:
            # EP dispatch + combine all_to_all per MoE layer
            per_tok += 2 * self.cfg.n_layers * top_plan(a2a).estimate_us
        health = dict(self.health, **self.comm.health)
        traces = {
            name: (tr.summary() if (tr := top_plan(p).last_trace)
                   is not None else None)
            for name, p in self.decode_plans.items()}
        return dict(mode=self.mode, requested_mode=self.requested_mode,
                    degraded=self.mode != self.requested_mode, plans=cards,
                    predicted_comm_us_per_token=round(per_tok, 2),
                    health=health, trace=traces,
                    communicator=repr(self.comm))

    # -- prefill: feed prompts token-by-token through the decode path ------
    # (correct and simple; the fused full-sequence prefill kernel is the
    # throughput path and lives in launch/serve via make_prefill_step)
    def prefill(self, prompts: np.ndarray):
        """prompts: (batch, prompt_len) int32."""
        b, plen = prompts.shape
        assert b == self.scfg.batch
        logits = None
        for t in range(plen):
            logits, self.cache = self._run_step(
                jnp.asarray(prompts[:, t], jnp.int32))
            self.pos += 1
        self.active[:] = True
        return logits

    def _sample(self, logits, key):
        if self.scfg.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        scaled = logits / self.scfg.temperature
        return jax.random.categorical(key, scaled).astype(jnp.int32)

    def decode(self, first_logits, num_tokens: int, seed: int = 0):
        """Greedy/temperature decode for ``num_tokens`` steps; returns
        (batch, num_tokens) generated ids."""
        out = []
        key = jax.random.key(seed)
        logits = first_logits
        for t in range(num_tokens):
            key, sub = jax.random.split(key)
            tok = self._sample(logits, sub)
            out.append(np.asarray(tok))
            done = out[-1] == self.scfg.eos_id
            self.active &= ~done
            logits, self.cache = self._run_step(tok)
            self.pos += 1
        return np.stack(out, axis=1)
