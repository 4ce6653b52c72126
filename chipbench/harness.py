"""One cell of the benchmark: build the served system from the cell's
files, warm every shape its traffic uses, serve the traffic open-loop
on the wall clock, and check what was served against the plain
reference.

The system under test is the program's own serving path:
``Engine(ServeConfig(...))`` under ``Scheduler`` (prefill fused or
token by token, as the configuration's ``serving`` block says), driven
through ``Scheduler.submit`` and ``Scheduler.tick(now)`` with
``now`` read from the host clock. Everything else (weights, traffic,
timing, the reference and the comparison) lives under ``chipbench/``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib
import json
import math
import pathlib
import sys
import time
from typing import Dict, List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parent
for p in (str(HERE), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import traffic as traffic_mod  # noqa: E402

__all__ = ["Cell", "load_cell", "CompileStats", "System", "build",
           "warm_up", "Records", "drive", "window_metrics", "check",
           "serving_failures", "percentile"]


# -- the cell's files ------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config: dict            # chipbench/configs/<config>.json
    mix: dict               # chipbench/traffic/<traffic>.json
    end_to_end: List[dict]  # the metrics a --trace 0 run reports
    per_layer: List[dict]   # the metrics a --trace 1 run reports


def _reports(metric: dict, name: str) -> bool:
    return name in metric.get("workloads", [name])


def load_cell(name: str, root: pathlib.Path = CHECKOUT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise ValueError(f"no workload {name!r} in BENCHMARK.json")
    config = json.loads((HERE / "configs" / f"{wl['config']}.json").read_text())
    mix = json.loads((HERE / "traffic" / f"{wl['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, wl, config, mix, e2e, per_layer)


# -- compile accounting ----------------------------------------------------
class CompileStats:
    """Compile seconds, compiles and persistent-cache hits, from JAX's
    monitoring events (counted in this process from creation on)."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self._jax = jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.seconds += secs
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._duration)
        self._jax.monitoring.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        return dict(compile_s=self.seconds, compiles=self.compiles,
                    cache_hits=self.cache_hits, cache_misses=self.cache_misses)


# -- the system ------------------------------------------------------------
def model_config(model: dict):
    """The program's ``ModelConfig`` for a configuration's ``model``
    block (its keys are the config's field names)."""
    from repro.models.config import ModelConfig

    return ModelConfig(**model)


def reference(config: dict):
    return importlib.import_module(f"reference.{config['reference']}")


def weight_key(seed: int):
    """A PRNG key from any whole number: the seed is hashed to 32 bits."""
    import jax
    return jax.random.key(np.uint32(
        np.random.SeedSequence(int(seed)).generate_state(1)[0]))


@dataclasses.dataclass
class System:
    config: dict
    cfg: object               # the program's ModelConfig
    ref: object               # the reference module
    params: object
    eng: object
    sched: object


def build(config: dict, seed: int, devices) -> System:
    """Weights from ``seed`` in one jitted call on the device, then the
    program's Engine and Scheduler over them."""
    import jax
    from jax.sharding import Mesh

    from repro.distributed import sharding as shd
    from repro.models import transformer as tf
    from repro.serve.engine import Engine, ServeConfig
    from repro.serve.scheduler import Scheduler

    cfg = model_config(config["model"])
    ref = reference(config)
    mesh = Mesh(np.asarray(devices[:1]).reshape(1, 1), ("data", "model"))
    shapes = jax.eval_shape(functools.partial(tf.init_params, cfg),
                            jax.random.key(0))
    shardings = shd.shardings_for(shd.param_pspecs(cfg, mesh, shd.MeshAxes()),
                                  mesh)
    params = jax.jit(lambda k: ref.make_weights(shapes, k),
                     out_shardings=shardings)(weight_key(seed))
    sv = config["serving"]
    scfg = ServeConfig(batch=sv["slots"], max_kv=sv["max_kv"],
                       eos_id=cfg.vocab, mode=sv["mode"],
                       prefill_seq_buckets=tuple(sv["prefill_seq_buckets"])
                       or None)
    eng = Engine(cfg, params, mesh, scfg)
    # The Scheduler keeps its own cache; the engine's would hold a
    # second copy of every slot's KV that nothing reads.
    eng.cache = None
    sched = Scheduler(eng, prefill_chunk=sv["prefill_chunk"],
                      fused_prefill=sv["fused_prefill"])
    if sched.fused_prefill != sv["fused_prefill"]:
        raise RuntimeError(f"fused prefill is {sched.fused_prefill} for "
                           f"{cfg.name}, configured {sv['fused_prefill']}")
    return System(config, cfg, ref, params, eng, sched)


def serving_failures(sched, mode: str) -> list:
    """Any fallback, retry or verification failure among the health
    counters, or an engine or scheduler in another mode than ``mode``."""
    health = sched.plan_report()["health"]
    bad = [f"health[{k}]={health[k]}"
           for k in ("fallbacks", "retries", "verify_failures")
           if health.get(k, 0)]
    ran = {"engine": sched.eng.mode, "scheduler": sched.mode}
    bad += [f"{who} ran {m!r}, requested {mode!r}"
            for who, m in ran.items() if m != mode]
    return bad


# -- driving ---------------------------------------------------------------
@dataclasses.dataclass
class Records:
    """What the client side saw, on the harness's clock."""
    submitted: List[int] = dataclasses.field(default_factory=list)
    n_admitted: int = 0
    admit: Dict[int, float] = dataclasses.field(default_factory=dict)
    lateness: Dict[int, float] = dataclasses.field(default_factory=dict)
    emit: Dict[int, List[float]] = dataclasses.field(default_factory=dict)
    tokens: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    #: per tick: (start, end, micro_steps, bucket, [(rid, k-th token)])
    ticks: list = dataclasses.field(default_factory=list)
    next_idx: int = 0
    #: most device bytes in use after any tick driven with ``memory``
    live_bytes: int = 0


def _no_span(name):
    return contextlib.nullcontext()


def _request(r: traffic_mod.Req, origin: float):
    from repro.serve.scheduler import Request
    return Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                   arrival_s=origin + r.due_s, temperature=r.temperature,
                   seed=r.seed)


def drive(sched, schedule, clock, origin: float, until: float, rec: Records,
          span=_no_span, memory=None) -> None:
    """Serve ``schedule`` (its due times offset by ``origin``) until the
    clock reads ``until``: submit each request once it is due, tick
    while anything is queued or active, sleep to the next due time
    otherwise. Each emission is stamped when ``tick`` has returned;
    ``memory`` (a function returning the device's bytes in use) is read
    after each tick, once its emissions are stamped."""
    reqs = schedule.reqs
    while True:
        now = clock()
        if now >= until:
            return
        i = rec.next_idx
        if i < len(reqs) and origin + reqs[i].due_s <= now:
            with span("submit"):
                while i < len(reqs) and origin + reqs[i].due_s <= now:
                    r = reqs[i]
                    if not sched.submit(_request(r, origin)):
                        raise RuntimeError(f"request {r.rid} refused")
                    rec.submitted.append(r.rid)
                    rec.lateness[r.rid] = now - (origin + r.due_s)
                    i += 1
            rec.next_idx = i
        if not sched.outstanding():
            nxt = origin + reqs[i].due_s if i < len(reqs) else until
            with span("wait_arrival"):
                time.sleep(max(0.0, min(nxt, until) - clock()))
            continue
        with span("tick"):
            info = sched.tick(now)
        t1 = clock()
        for rid in rec.submitted[rec.n_admitted:rec.n_admitted + info.admitted]:
            rec.admit[rid] = now
        rec.n_admitted += info.admitted
        emitted = []
        for em in info.emissions:
            ts = rec.emit.setdefault(em.rid, [])
            ts.append(t1)
            rec.tokens.setdefault(em.rid, []).append(em.token)
            emitted.append((em.rid, len(ts)))
        rec.ticks.append((now, t1, info.micro_steps, info.bucket, emitted))
        if memory is not None:
            rec.live_bytes = max(rec.live_bytes, memory())


def warm_up(system: System, clock) -> int:
    """Run every program the cell's traffic can reach once: for each
    slot bucket ``b`` and prefill sequence bucket ``S`` (``S`` = 1
    without fused prefill), ``b`` requests of ``S + 1`` prompt tokens
    (one prefill micro-step at (b, S), then decode at ``b``), half of
    them one token longer and sampled, so
    that the other half's release compacts rows and the last token
    decodes at the next bucket down. Two rounds: the first steps see a
    freshly allocated cache, whose placement differs from the one every
    later step hands on, and jit compiles each placement once. Returns
    the requests served."""
    from repro.distributed.step import slot_buckets
    from repro.serve.scheduler import Request

    sched = system.sched
    sv = system.config["serving"]
    rid = -1
    rounds = [(b, s) for b in slot_buckets(sv["slots"])
              for s in (sv["prefill_seq_buckets"] or [1])] * 2
    for b, s in rounds:
        for j in range(b):
            sched.submit(Request(
                rid=rid, prompt=np.ones(s + 1, np.int32),
                max_new_tokens=2 + j % 2, arrival_s=0.0,
                temperature=0.8 if j % 2 else 0.0, seed=j))
            rid -= 1
        while sched.outstanding():
            sched.tick(clock())
    return -rid - 1


# -- metrics ---------------------------------------------------------------
def percentile(values, q: float) -> float:
    """The q-th percentile (0-100), linear between order statistics."""
    return float(np.percentile(np.asarray(values, float), q))


def window_metrics(schedule, rec: Records, origin: float, w0: float,
                   w1: float) -> dict:
    """Client-side numbers of the window [w0, w1): TTFT of every request
    due in it from its due time (no first token by w1 counts as w1),
    every inter-token gap inside it, tokens emitted in it, admissions."""
    due_in = [r for r in schedule.reqs if w0 <= origin + r.due_s < w1]
    ttft = []
    for r in due_in:
        ts = rec.emit.get(r.rid)
        first = ts[0] if ts and ts[0] <= w1 else w1
        ttft.append(first - (origin + r.due_s))
    gaps = [b - a for ts in rec.emit.values()
            for a, b in zip(ts, ts[1:]) if w0 <= a and b <= w1]
    tokens = sum(1 for ts in rec.emit.values() for t in ts if w0 <= t <= w1)
    admitted = [rid for rid, t in rec.admit.items() if w0 <= t < w1]
    waits = [rec.admit[r.rid] - (origin + r.due_s) for r in due_in
             if r.rid in rec.admit]
    late = [rec.lateness[r.rid] for r in due_in if r.rid in rec.lateness]
    return dict(due=len(due_in), ttft=ttft, gaps=gaps, tokens=tokens,
                admitted=len(admitted), waits=waits, lateness=late,
                censored=sum(1 for r in due_in
                             if not rec.emit.get(r.rid)
                             or rec.emit[r.rid][0] > w1))


# -- correctness -----------------------------------------------------------
def pad_len(mix: dict) -> int:
    """Length every reference pass is padded to: the longest prompt
    plus the longest output the mix can draw, in blocks of 128."""
    n = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    return int(math.ceil(n / 128) * 128)


def sample(schedule, rec: Records, seed: int, w1: float,
           min_tokens: int = 300, max_reqs: int = 8) -> list:
    """Greedy requests finished by ``w1``, drawn from ``seed``: the one
    with the longest prompt plus output, then others until
    ``min_tokens`` served tokens or ``max_reqs`` requests."""
    done = [r for r in schedule.reqs if r.temperature == 0
            and len(rec.emit.get(r.rid, ())) == r.max_new_tokens
            and rec.emit[r.rid][-1] <= w1]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + r.max_new_tokens, -r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], longest.max_new_tokens
    for i in order:
        if n >= min_tokens or len(out) >= max_reqs:
            break
        out.append(rest[i])
        n += rest[i].max_new_tokens
    return out


def check(ref, model: dict, params, reqs, rec: Records, t_pad: int,
          control: bool = False) -> dict:
    """Widest gap, over every served token of ``reqs``, by which its
    reference logit lies below the reference's best at that position
    (the reference teacher-forced on prompt plus served tokens). With
    ``control``, also the widest gap of the token the fp8 control puts
    first at the same positions."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(functools.partial(ref.next_token_gaps, m=model,
                                   control=control))
    worst, worst_ctl, n_tok = 0.0, 0.0, 0
    for r in reqs:
        served = np.asarray(rec.tokens[r.rid], np.int32)
        seq = np.concatenate([r.prompt, served])
        if len(seq) > t_pad:
            raise ValueError(f"request {r.rid} is longer than {t_pad}")
        toks = np.zeros(t_pad, np.int32)
        toks[:len(seq)] = seq
        g, c = fn(params, tokens=jnp.asarray(toks))
        lo, hi = len(r.prompt) - 1, len(seq) - 1
        worst = max(worst, float(np.max(np.asarray(g)[lo:hi])))
        if control:
            worst_ctl = max(worst_ctl, float(np.max(np.asarray(c)[lo:hi])))
        n_tok += hi - lo
    out = dict(served_logit_gap=worst, requests=len(reqs), tokens=n_tok)
    if control:
        out["control_logit_gap"] = worst_ctl
    return out


def free(system: System) -> None:
    """Drop the program's state (scheduler cache, engine, compiled
    steps); the benchmark's weights stay for the reference."""
    system.sched = None
    system.eng = None
    gc.collect()
