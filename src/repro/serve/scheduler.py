"""Async continuous-batching scheduler over :class:`~repro.serve.engine.Engine`.

The production serving loop (ROADMAP "millions-of-users story"): a
fixed budget of decode *slots*, FIFO admission, chunked prefill
interleaved with decode so a long prompt never stalls the token
stream, per-request token streaming, and slot recycling on EOS —
driven either synchronously (:meth:`Scheduler.tick`, a deterministic
virtual-clock step the tests and load generator use) or through
:class:`AsyncServeEngine`'s async generators.

How it composes with the plan layer: every tick runs ONE batched
decode step built by :func:`~repro.distributed.step.make_sched_step`
at the smallest slot *bucket* that covers the active slots
(``slot_buckets`` ladder — the same ladder the engine's
:class:`~repro.core.comm.BucketedPlan` families were compiled over),
and in explicit mode every bucketed step function replays the
engine's ONE init-compiled plan set. Varying occupancy therefore
replays a handful of frozen plans and shows up in their per-bucket
hit counters — the continuous-batching story `BucketedPlan` was built
for, now actually driven by a scheduler.

Determinism contract (pinned by ``tests/test_scheduler.py``): every
per-row op in the decode step is row-independent — einsums contract
within a row, softmax/rms_norm are per-row, the replayed collectives
are elementwise across rows, and the MoE all_to_all uses lossless
capacity so co-batched rows can never evict each other's tokens.
Sampling keys derive from (request seed, tokens generated so far),
never from batch position or wall clock. A request's token stream is
therefore bit-identical no matter which other requests it shares
steps with — the scheduler batches for throughput without changing a
single emitted token vs. a sequential single-request run.

Virtual time: by default the scheduler never reads a wall clock.
``tick(now)`` takes the caller's clock (the load generator charges each
tick ``step_s * (1 + micro_steps)``), so traces replay exactly and TTFT
/ throughput metrics are reproducible to the bit. A caller serving on
the wall clock passes ``clock=``: emissions are then stamped when their
tokens have been sampled, not at the tick's start.

Tracing: every tick writes ``sched.*`` host spans
(``jax.profiler.TraceAnnotation``) into an active profiler trace, on
the device's clock, so each device idle gap can be named by what the
host was doing (docs/serving.md "Tracing the scheduler"). With no trace
active a span costs about a microsecond.
"""
from __future__ import annotations

import asyncio
import dataclasses
import warnings
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults
from repro.distributed import step as step_mod
from repro.models import transformer as tf

__all__ = ["Request", "Emission", "TickInfo", "Scheduler",
           "AsyncServeEngine"]

_Span = jax.profiler.TraceAnnotation


def _rids(slots) -> str:
    """Request ids as a span argument, so one request's spans share it.
    Space-joined: the trace's argument encoding splits on commas."""
    return " ".join(str(s.req.rid) for s in slots)


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival_s`` is in virtual seconds (the
    load generator's clock); ``seed`` drives temperature sampling —
    per-request, so the sample stream is schedule-independent."""
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32, non-empty
    max_new_tokens: int
    arrival_s: float = 0.0
    temperature: float = 0.0           # 0 -> greedy
    seed: int = 0


@dataclasses.dataclass(frozen=True)
class Emission:
    """One streamed token: ``done`` marks the request's final token
    (EOS or the max_new_tokens budget)."""
    rid: int
    token: int
    done: bool
    t: float             # emission time (virtual, or ``clock()`` if given)


@dataclasses.dataclass(frozen=True)
class TickInfo:
    now: float
    admitted: int
    micro_steps: int                   # prefill-only steps this tick
    bucket: int                        # slot bucket of the combined step
    n_active: int                      # active slots after completions
    queued: int
    emissions: tuple                   # Emission, in slot order


class _Slot:
    __slots__ = ("req", "pos", "consumed", "last_token", "emitted",
                 "t_admit", "t_first", "pc_handle")

    def __init__(self, req: Request, t_admit: float):
        self.req = req
        self.pos = 0          # tokens written into this slot's cache row
        self.consumed = 0     # prompt tokens stepped so far
        self.last_token = 0
        self.emitted = 0
        self.t_admit = t_admit
        self.t_first: Optional[float] = None
        self.pc_handle = None   # prefix-cache lease held while resident


def _pct(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[i]


class Scheduler:
    """Continuous-batching loop over one engine replica.

    Scheduling rules (docs/serving.md "continuous batching"):

    * **Admission** — FIFO, a request enters a free slot once its
      ``arrival_s`` has passed, never more than ``max_slots`` resident.
      The queue is unbounded by default (nothing is ever dropped);
      ``queue_limit`` opts into bounded admission with backpressure —
      :meth:`submit` returns False and the ``rejected`` counter in
      :meth:`metrics` ticks instead of queueing without bound.
    * **Fused prefill** (``fused_prefill=True``) — each prefill
      micro-step pushes a whole prompt *chunk* per slot through
      :func:`~repro.distributed.step.make_prefill_sched_step` (up to
      the largest sequence bucket, ring-capped per row so windowed
      layers stay exact) instead of one token, replaying the engine's
      sequence-bucketed plan families. Token-by-token remains the
      default and the fallback for unsupported families.
    * **Prefix reuse** (``prefix_cache=``a :class:`~repro.serve
      .prefix_cache.PrefixCache`) — admission seeds a fresh slot with
      the longest cached prompt prefix (dense/MoE attention caches
      only; recurrent state is not per-token sliceable) and the first
      sampled token triggers an insert of the completed prompt's slot
      snapshot, so later requests sharing the prefix skip those
      prefill tokens entirely. Misses and evictions fall back to the
      ordinary cold prefill — streams stay bit-identical either way.
    * **Chunked prefill** — each tick runs up to ``prefill_chunk - 1``
      prefill-only *micro-steps* (advancing ONLY slots with more than
      one prompt token left, via the step's active mask) followed by
      one *combined* step in which prefilling slots consume their next
      prompt token and decode slots consume their last sampled token.
      A slot's final prompt token always runs in a combined step, so
      its logits row immediately yields the first generated token.
    * **Streaming** — decode slots emit exactly one token per tick;
      a long co-resident prompt costs micro-steps (charged to the
      virtual clock) but never withholds decode slots from a step.
    * **Completion** — EOS (``ServeConfig.eos_id``) or the request's
      ``max_new_tokens`` budget frees the slot; the last active slot
      compacts into the freed row (one cache-row copy — an exact
      permutation, so streams are unaffected) to keep active slots a
      contiguous prefix and the step bucket minimal.
    * **Time stamps** — admission uses the tick's ``now``. First-token,
      finish and emission times are ``now`` too, unless ``clock`` (a
      function returning the caller's time) is given: then they are
      read from it once a tick's tokens are sampled.

    The batch must not be DP-sharded: one scheduler owns one replica;
    scale-out across replicas is :class:`repro.serve.router.Router`.
    """

    def __init__(self, engine, *, max_slots: Optional[int] = None,
                 prefill_chunk: int = 4, fused_prefill: bool = False,
                 queue_limit: Optional[int] = None, prefix_cache=None,
                 clock: Optional[Callable[[], float]] = None):
        self.eng = engine
        self.clock = clock
        scfg = engine.scfg
        self.max_slots = int(max_slots or scfg.batch)
        if not 1 <= self.max_slots <= scfg.batch:
            raise ValueError(
                f"max_slots={self.max_slots} must be in [1, engine batch "
                f"{scfg.batch}] (the engine's plans were bucketed for that "
                f"batch)")
        _, sharded = step_mod.local_batch(engine.mesh, engine.ax, scfg.batch)
        if sharded:
            raise ValueError(
                "Scheduler needs an unsharded batch (slots live on one "
                "replica); build one replica per DP shard and fan out "
                "with serve.router.Router")
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.prefill_chunk = int(prefill_chunk)
        self.eos_id = scfg.eos_id
        #: scheduler follows the engine's (possibly already degraded) mode
        self.mode = engine.mode
        self._buckets = [b for b in step_mod.slot_buckets(self.max_slots)]
        self._steps: Dict[tuple, Callable] = {}
        #: step functions that have returned at least once (compiled)
        self._ran: set = set()
        self.cache = tf.init_cache(
            engine.cfg, self.max_slots, scfg.max_kv,
            dtype=jnp.int8 if scfg.kv_quant else None)
        self._slots: List[_Slot] = []
        self._queue: deque = deque()
        self.streams: Dict[int, List[int]] = {}
        self._done: Dict[int, dict] = {}
        self._now = 0.0
        self._ticks = 0
        self._n_steps = 0
        self._micro_total = 0
        self._bucket_steps: Dict[int, int] = {b: 0 for b in self._buckets}
        # -- bounded admission (opt-in backpressure) -----------------------
        if queue_limit is not None and queue_limit < 1:
            raise ValueError("queue_limit must be >= 1 or None")
        self.queue_limit = queue_limit
        self._rejected = 0
        # -- fused prefill (sequence-bucketed chunk micro-steps) -----------
        kv_lens = [min(w, scfg.max_kv) if w is not None else scfg.max_kv
                   for w in tf.layer_windows(engine.cfg)]
        self._min_kv = min(kv_lens)
        self.fused_prefill = (bool(fused_prefill)
                              and engine.cfg.family in ("dense", "moe",
                                                        "hybrid"))
        ladder = (scfg.prefill_seq_buckets
                  or step_mod.slot_buckets(self.prefill_chunk))
        self._seq_buckets = tuple(sorted(
            {int(s) for s in ladder if 1 <= int(s) <= self._min_kv}))
        dropped = tuple(sorted({int(s) for s in ladder
                                if int(s) > self._min_kv}))
        if dropped and scfg.prefill_seq_buckets is not None:
            # loud degrade: the engine may have compiled plan buckets for
            # these, but no fused chunk can exceed the smallest ring
            # buffer without wrapping keys its own queries still read
            warnings.warn(
                f"prefill sequence buckets {dropped} exceed the smallest "
                f"layer kv_len {self._min_kv} and were dropped; fused "
                f"prefill chunks cap at "
                f"{max(self._seq_buckets) if self._seq_buckets else 0} "
                f"(usable ladder {self._seq_buckets})", stacklevel=2)
        if self.fused_prefill and not self._seq_buckets:
            raise ValueError(
                f"no usable prefill sequence bucket <= the smallest layer "
                f"kv_len {self._min_kv} (configured {tuple(ladder)})")
        #: explicit fused prefill replays the engine's plan set only when
        #: the engine actually compiled the sequence buckets into it
        #: (ServeConfig.prefill_seq_buckets); otherwise each (bucket, seq)
        #: step compiles its own family on the engine's communicator
        self._shared_prefill_plans = scfg.prefill_seq_buckets is not None
        self._prefill_steps: Dict[tuple, Callable] = {}
        self._prefill_bucket_steps: Dict[tuple, int] = {}
        # -- prefix/KV reuse ------------------------------------------------
        #: recurrent state (SSM/RWKV) is a running reduction, not a
        #: per-token tape — only pure-attention caches are prefix-sliceable
        self.prefix_cache = (
            prefix_cache if isinstance(self.cache, dict)
            and "k" in self.cache and "ssm" not in self.cache else None)
        self._prefix = {"hits": 0, "misses": 0, "tokens_reused": 0,
                        "inserts": 0}

    # -- clock (virtual; the caller owns it) -------------------------------
    @property
    def now(self) -> float:
        return self._now

    @property
    def n_active(self) -> int:
        return len(self._slots)

    def advance(self, dt: float) -> None:
        self._now += float(dt)

    def advance_to(self, t: float) -> None:
        self._now = max(self._now, float(t))

    def next_arrival(self) -> Optional[float]:
        return self._queue[0].arrival_s if self._queue else None

    def outstanding(self) -> int:
        return len(self._queue) + len(self._slots)

    # -- submission --------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request. Returns True when accepted; with
        ``queue_limit`` set, a full queue rejects (returns False and
        counts in ``metrics()['rejected']``) instead of growing without
        bound — the opt-in backpressure signal."""
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(f"request {req.rid}: max_new_tokens must be >=1")
        if req.rid in self.streams or any(r.rid == req.rid
                                          for r in self._queue):
            raise ValueError(f"duplicate request id {req.rid}")
        if (self.queue_limit is not None
                and len(self._queue) >= self.queue_limit):
            self._rejected += 1
            return False
        self._queue.append(dataclasses.replace(req, prompt=prompt))
        return True

    # -- step machinery ----------------------------------------------------
    def _bucket(self, k: int) -> int:
        for b in self._buckets:
            if b >= k:
                return b
        return self._buckets[-1]

    def _step_fn(self, b: int):
        key = (self.mode, b)
        fn = self._steps.get(key)
        if fn is None:
            kw = (dict(comm=self.eng.comm,
                       plans=self.eng.decode_plans or None)
                  if self.mode == "explicit" else {})
            fn, _ = step_mod.make_sched_step(
                self.eng.cfg, self.eng.mesh, self.eng.ax, batch=b,
                max_kv=self.eng.scfg.max_kv,
                kv_quant=self.eng.scfg.kv_quant, mode=self.mode, **kw)
            if self.mode == "auto" and self.eng.donates:
                # the engine's rule: no recovery re-run here needs the
                # pre-step cache, so the step takes it and updates it in
                # place (an explicit step is re-run by ``_guarded``)
                fn = jax.jit(fn, donate_argnums=(1,))
            self._steps[key] = fn
        return fn

    def _slice(self, b: int):
        if b == self.max_slots:
            return self.cache
        return jax.tree.map(lambda a: a[:, :b], self.cache)

    def _merge(self, sub, b: int) -> None:
        if b == self.max_slots:
            self.cache = sub
        else:
            self.cache = jax.tree.map(
                lambda a, s: a.at[:, :b].set(s), self.cache, sub)

    def _guarded(self, get_fn, args):
        """Run one step through the scheduler's fallback ladder. An
        explicit step that has run before and now fails degrades the
        scheduler to auto (rebuilding its steps) and re-runs from the
        same pre-step state — the scheduler analogue of the engine's
        ladder; the engine's ``fallbacks`` health counter records it so
        the router's aggregate shows the degraded replica. A step that
        has never run failed to trace or compile (a Mosaic refusal, an
        XLA lowering error): that error propagates."""
        fn = get_fn()
        try:
            out = fn(*args)
        except Exception as e:
            if self.mode == "auto" or not (
                    fn in self._ran or isinstance(e, faults.FaultInjected)):
                raise
            warnings.warn(
                f"explicit scheduler step failed ({e}); falling back to "
                f"auto (GSPMD) for the remainder of serving", stacklevel=3)
            self.eng.health["fallbacks"] += 1
            self.mode = "auto"
            self._steps.clear()
            self._prefill_steps.clear()
            self._ran.clear()
            fn = get_fn()
            out = fn(*args)
        self._ran.add(fn)
        return out

    def _run(self, b, tokens, pos, active):
        """One guarded step at bucket ``b``."""
        args = (self.eng.params, self._slice(b), jnp.asarray(tokens),
                jnp.asarray(pos), jnp.asarray(active))
        return self._guarded(lambda: self._step_fn(b), args)

    def _step_once(self, pred) -> tuple:
        """Run one masked batched step over the active-slot prefix.
        ``pred(slot)`` selects which slots advance; the rest (and the
        bucket's free rows) are masked off, so their cache rows pass
        through bit-exactly. Returns (logits rows, bucket)."""
        k = len(self._slots)
        b = self._bucket(k)
        tokens = np.zeros(b, np.int32)
        pos = np.zeros(b, np.int32)
        active = np.zeros(b, bool)
        stepped = []
        for i, s in enumerate(self._slots):
            pos[i] = s.pos
            tokens[i] = (s.req.prompt[s.consumed]
                         if s.consumed < len(s.req.prompt)
                         else s.last_token)
            if pred(s):
                active[i] = True
                stepped.append(s)
        logits, sub = self._run(b, tokens, pos, active)
        self._merge(sub, b)
        for s in stepped:
            if s.consumed < len(s.req.prompt):
                s.consumed += 1
            s.pos += 1
        self._n_steps += 1
        self._bucket_steps[b] += 1
        return logits, b

    # -- fused prefill (sequence-bucketed chunk micro-steps) ----------------
    def _prefill_fn(self, b: int, s: int):
        key = (self.mode, b, s)
        fn = self._prefill_steps.get(key)
        if fn is None:
            kw = {}
            if self.mode == "explicit":
                kw["comm"] = self.eng.comm
                if self._shared_prefill_plans:
                    kw["plans"] = self.eng.decode_plans or None
            fn, _ = step_mod.make_prefill_sched_step(
                self.eng.cfg, self.eng.mesh, self.eng.ax, batch=b, seq=s,
                max_kv=self.eng.scfg.max_kv,
                kv_quant=self.eng.scfg.kv_quant, mode=self.mode, **kw)
            self._prefill_steps[key] = fn
        return fn

    def _chunk_len(self, s: _Slot) -> int:
        """How many prompt tokens slot ``s`` may fuse into this
        micro-step: the tokens it has left before its FINAL prompt
        token (which always runs in the combined step), capped at the
        largest sequence bucket and at the ring headroom
        ``min_kv - pos`` so a windowed layer never overwrites a slot
        its own in-chunk queries still read (``blocks
        .prefill_attention``'s exactness contract; a 1-token chunk is
        the always-exact fallback once the ring is full)."""
        remaining = len(s.req.prompt) - 1 - s.consumed
        if remaining <= 0:
            return 0
        n = min(remaining, self._seq_buckets[-1], self._min_kv - s.pos)
        return max(n, 1)

    def _prefill_once(self) -> None:
        """One fused prefill micro-step: every prefilling slot advances
        by its chunk (others, and the bucket's free rows, pass their
        cache through bit-exactly via ``n_tok=0``). No logits — cache
        only."""
        k = len(self._slots)
        b = self._bucket(k)
        chunks = [self._chunk_len(s) for s in self._slots]
        S = next(sb for sb in self._seq_buckets if sb >= max(chunks))
        tokens = np.zeros((b, S), np.int32)
        pos = np.zeros(b, np.int32)
        n_tok = np.zeros(b, np.int32)
        for i, (s, n) in enumerate(zip(self._slots, chunks)):
            pos[i] = s.pos
            if n > 0:
                tokens[i, :n] = s.req.prompt[s.consumed:s.consumed + n]
                n_tok[i] = n
        args = (self.eng.params, self._slice(b), jnp.asarray(tokens),
                jnp.asarray(pos), jnp.asarray(n_tok))
        sub = self._guarded(lambda: self._prefill_fn(b, S), args)
        self._merge(sub, b)
        for s, n in zip(self._slots, chunks):
            if n > 0:
                s.consumed += n
                s.pos += n
        self._n_steps += 1
        key = (b, S)
        self._prefill_bucket_steps[key] = \
            self._prefill_bucket_steps.get(key, 0) + 1

    def _sample_row(self, slot: _Slot, row: np.ndarray) -> int:
        t = slot.req.temperature
        if t <= 0:
            return int(np.argmax(row))
        # key = f(request seed, tokens generated) — independent of slot
        # index, co-residents, and tick count, so sampled streams are
        # schedule-invariant like greedy ones
        key = jax.random.fold_in(jax.random.key(slot.req.seed), slot.emitted)
        return int(jax.random.categorical(key, jnp.asarray(row) / t))

    # -- admission / release -----------------------------------------------
    def _seed_prefix(self, slot: _Slot, i: int) -> None:
        """Seed a freshly-admitted slot's cache row with the longest
        cached prompt prefix. The lease stays pinned until the slot is
        released; reuse is capped at ``prompt_len - 1`` (the final
        prompt token always runs through the combined step so the first
        sampled token comes off live logits) and at the smallest layer
        kv_len (reused slots are written at ring positions 0..L-1)."""
        prompt = slot.req.prompt
        plen = len(prompt)
        if self.prefix_cache is None or plen < 2:
            return
        cap = min(plen - 1, self._min_kv)
        L, segs, handle = self.prefix_cache.acquire(prompt[:cap])
        if L == 0:
            self._prefix["misses"] += 1
            return
        self._prefix["hits"] += 1
        self._prefix["tokens_reused"] += L
        slot.pc_handle = handle
        upd = {}
        for kind in tf.KV_LEAVES:
            if kind in self.cache:
                upd[kind] = [
                    leaf.at[:, i, :, :L].set(
                        jnp.asarray(segs[f"{kind}{j}"], leaf.dtype))
                    for j, leaf in enumerate(self.cache[kind])]
        self.cache = dict(self.cache, **upd)
        slot.pos = slot.consumed = L

    def _snapshot_prefix(self, slot: _Slot, i: int) -> None:
        """Index the just-completed prompt: at the first sampled token
        the slot's cache row holds exactly the prompt's KV bytes
        (positions 0..plen-1), so a copy of that row seeds every later
        request sharing the prefix. Skipped when the ring wrapped
        (prompt longer than the smallest kv_len — the tape is no longer
        a pure prefix) or when the trie already holds the full prompt."""
        prompt = slot.req.prompt
        plen = len(prompt)
        if (self.prefix_cache is None or plen < 2 or plen > self._min_kv
                or self.prefix_cache.match(prompt) >= plen):
            return
        segs = {}
        for kind in tf.KV_LEAVES:
            if kind in self.cache:
                for j, leaf in enumerate(self.cache[kind]):
                    segs[f"{kind}{j}"] = np.ascontiguousarray(
                        np.asarray(leaf)[:, i, :, :plen])
        handle = self.prefix_cache.insert(prompt, segs)
        self._prefix["inserts"] += 1
        # swap the admission lease for the insert lease (deeper pin)
        self.prefix_cache.release(slot.pc_handle)
        slot.pc_handle = handle

    def _admit(self, now: float) -> int:
        admitted = 0
        while (self._queue and len(self._slots) < self.max_slots
               and self._queue[0].arrival_s <= now):
            req = self._queue.popleft()
            i = len(self._slots)
            # zero the recycled row: attention is masked by position, but
            # the SSM/RWKV recurrent state must start from the init value
            self.cache = jax.tree.map(lambda a: a.at[:, i].set(0),
                                      self.cache)
            slot = _Slot(req, now)
            self._slots.append(slot)
            self.streams[req.rid] = []
            self._seed_prefix(slot, i)
            admitted += 1
        return admitted

    def _finish(self, s: _Slot, now: float) -> None:
        self._done[s.req.rid] = dict(
            rid=s.req.rid, arrival=s.req.arrival_s, admit=s.t_admit,
            first=s.t_first, finish=now, n_tokens=s.emitted,
            prompt_len=int(len(s.req.prompt)))

    def _release(self, i: int) -> None:
        if self.prefix_cache is not None:
            self.prefix_cache.release(self._slots[i].pc_handle)
            self._slots[i].pc_handle = None
        last = len(self._slots) - 1
        if i != last:
            # compact: move the last active slot into the freed row (an
            # exact cache-row copy — a permutation of rows, so every
            # remaining stream is bitwise unaffected)
            self.cache = jax.tree.map(
                lambda a: a.at[:, i].set(a[:, last]), self.cache)
            self._slots[i] = self._slots[last]
        self._slots.pop()

    # -- the tick ----------------------------------------------------------
    def tick(self, now: Optional[float] = None) -> TickInfo:
        """Advance the world by one scheduling round at virtual time
        ``now`` (default: the internal clock): admit, run the chunked-
        prefill micro-steps, run the combined step, sample/stream, and
        recycle completed slots."""
        with _Span("sched.tick"):
            return self._tick(now)

    def _tick(self, now: Optional[float]) -> TickInfo:
        now = self._now if now is None else float(now)
        if now < self._now:
            raise ValueError(f"virtual clock moved backwards "
                             f"({now} < {self._now})")
        self._now = now
        with _Span("sched.admit") as span:
            admitted = self._admit(now)
            if admitted and _Span.is_enabled():
                span.set_metadata(rids=_rids(self._slots[-admitted:]))
        emissions: List[Emission] = []
        micro = 0
        bucket = 0
        if self._slots:
            def prefilling(s):
                return s.consumed < len(s.req.prompt) - 1

            # the slot count, and so the bucket, holds until the releases
            b = self._bucket(len(self._slots))
            while micro < self.prefill_chunk - 1 and \
                    any(prefilling(s) for s in self._slots):
                with _Span("sched.step", kind="prefill", bucket=b):
                    if self.fused_prefill:
                        self._prefill_once()
                    else:
                        self._step_once(prefilling)
                micro += 1
            with _Span("sched.step", kind="combined", bucket=b):
                logits, bucket = self._step_once(lambda s: True)
            with _Span("sched.sync"):
                rows = np.asarray(logits, np.float32)
            new_tokens = []     # (slot index, slot, token, finished)
            with _Span("sched.sample") as span:
                for i, s in enumerate(self._slots):
                    if s.consumed < len(s.req.prompt):
                        continue    # prompt not done (chunk budget spent)
                    tok = self._sample_row(s, rows[i])
                    s.last_token = tok
                    s.emitted += 1
                    if s.emitted == 1:
                        # first sampled token: the cache row holds exactly
                        # the prompt tape — index it for prefix reuse
                        self._snapshot_prefix(s, i)
                    self.streams[s.req.rid].append(tok)
                    fin = (tok == self.eos_id
                           or s.emitted >= s.req.max_new_tokens)
                    new_tokens.append((i, s, tok, fin))
                if _Span.is_enabled():
                    span.set_metadata(rows=len(new_tokens), sampled=sum(
                        1 for _, s, _, _ in new_tokens
                        if s.req.temperature > 0))
            t = now if self.clock is None else float(self.clock())
            done = []
            for i, s, tok, fin in new_tokens:
                if s.t_first is None:
                    s.t_first = t
                emissions.append(Emission(s.req.rid, tok, fin, t))
                if fin:
                    self._finish(s, t)
                    done.append(i)
            with _Span("sched.release") as span:
                if done and _Span.is_enabled():
                    span.set_metadata(rids=_rids(self._slots[i]
                                                 for i in done))
                # release in descending index order so each compaction's
                # "last slot" is still correct
                for i in sorted(done, reverse=True):
                    self._release(i)
        self._ticks += 1
        self._micro_total += micro
        return TickInfo(now=now, admitted=admitted, micro_steps=micro,
                        bucket=bucket, n_active=len(self._slots),
                        queued=len(self._queue),
                        emissions=tuple(emissions))

    def run_until_drained(self, *, step_s: float = 1.0,
                          max_ticks: int = 100_000) -> List[TickInfo]:
        """Drive the internal virtual clock until every submitted
        request completed: each tick costs ``step_s * (1 + micro_steps)``
        virtual seconds; idle gaps fast-forward to the next arrival."""
        infos = []
        while self.outstanding():
            if len(infos) >= max_ticks:
                raise RuntimeError(
                    f"scheduler did not drain in {max_ticks} ticks "
                    f"({self.outstanding()} requests outstanding)")
            nxt = self.next_arrival()
            if not self._slots and nxt is not None and nxt > self._now:
                self.advance_to(nxt)
            info = self.tick()
            infos.append(info)
            self.advance(step_s * (1 + info.micro_steps))
        return infos

    # -- reporting ---------------------------------------------------------
    def metrics(self) -> dict:
        """Per-request serving metrics in virtual seconds. ``dropped``
        is definitionally 0 (unbounded FIFO queue) and asserted by the
        load harness; ``wait`` is admission delay (the starvation bound
        the property test pins)."""
        recs = [r for r in self._done.values()]
        ttft = sorted(r["first"] - r["arrival"] for r in recs)
        wait = sorted(r["admit"] - r["arrival"] for r in recs)
        toks = sum(r["n_tokens"] for r in recs)
        dur = max(self._now, 1e-9)
        px = self._prefix
        px_total = px["hits"] + px["misses"]
        return dict(
            completed=len(recs), dropped=0, outstanding=self.outstanding(),
            rejected=self._rejected,
            tokens=toks, ticks=self._ticks, steps=self._n_steps,
            micro_steps=self._micro_total,
            tokens_per_vs=round(toks / dur, 3),
            ttft_vs={"p50": _pct(ttft, 0.5), "p95": _pct(ttft, 0.95),
                     "max": ttft[-1] if ttft else 0.0},
            wait_vs={"p50": _pct(wait, 0.5), "p95": _pct(wait, 0.95),
                     "max": wait[-1] if wait else 0.0},
            bucket_steps=dict(self._bucket_steps),
            prefix_hits=px["hits"], prefix_misses=px["misses"],
            prefix_tokens_reused=px["tokens_reused"],
            prefix_inserts=px["inserts"],
            prefix_hit_rate=round(px["hits"] / px_total, 4)
            if px_total else 0.0)

    def plan_report(self) -> dict:
        """The engine's plan/health report plus the scheduler view:
        ``mode`` is the mode the scheduler is actually stepping in (it
        can degrade independently of the engine's caller-driven path)
        and ``degraded`` flags divergence from the requested mode — the
        per-replica bit the router aggregate surfaces."""
        rep = self.eng.plan_report()
        rep["mode"] = self.mode
        rep["degraded"] = self.mode != self.eng.requested_mode
        rep["scheduler"] = dict(
            max_slots=self.max_slots, prefill_chunk=self.prefill_chunk,
            ticks=self._ticks, steps=self._n_steps,
            micro_steps=self._micro_total, active=len(self._slots),
            queued=len(self._queue), bucket_steps=dict(self._bucket_steps),
            fused_prefill=self.fused_prefill,
            seq_buckets=list(self._seq_buckets),
            # (slot bucket, seq bucket) -> fused micro-steps; stringified
            # so the report stays JSON-serializable
            prefill_bucket_steps={
                f"{b}x{s}": n
                for (b, s), n in sorted(self._prefill_bucket_steps.items())},
            rejected=self._rejected,
            prefix=dict(self._prefix,
                        **(self.prefix_cache.stats()
                           if self.prefix_cache is not None else {})))
        return rep


class AsyncServeEngine:
    """Asyncio front-end: ``generate(request)`` is an async generator
    yielding the request's tokens as the shared pump loop produces
    them. One pump drives the scheduler (or a
    :class:`~repro.serve.router.Router` — same duck-typed surface) for
    ALL in-flight requests, yielding to the event loop between ticks so
    arbitrarily many ``generate`` streams interleave over one batched
    decode loop. The pump advances the same virtual clock the sync path
    uses, so async streams are bit-identical to ``tick``-driven ones.
    """

    def __init__(self, sched, *, step_s: float = 1.0):
        self._sched = sched
        self._step_s = float(step_s)
        self._queues: Dict[int, asyncio.Queue] = {}
        self._pump_task: Optional[asyncio.Task] = None

    async def generate(self, request: Request):
        q: asyncio.Queue = asyncio.Queue()
        self._queues[request.rid] = q
        self._sched.submit(request)
        if self._pump_task is None or self._pump_task.done():
            self._pump_task = asyncio.get_running_loop().create_task(
                self._pump())
        try:
            while True:
                em = await q.get()
                yield em.token
                if em.done:
                    return
        finally:
            self._queues.pop(request.rid, None)

    async def _pump(self):
        sched = self._sched
        while sched.outstanding():
            nxt = sched.next_arrival()
            if sched.n_active == 0 and nxt is not None and nxt > sched.now:
                sched.advance_to(nxt)
            info = sched.tick()
            for em in info.emissions:
                q = self._queues.get(em.rid)
                if q is not None:
                    q.put_nowait(em)
            sched.advance(self._step_s * (1 + info.micro_steps))
            await asyncio.sleep(0)
