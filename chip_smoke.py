"""Smoke of the served path on a TPU: is the system still able to serve?

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # one host with four chips
    python chip_smoke.py --reduced    # rehearsal at a tiny size, any host

Default: qwen3-1.7b at its published widths (bf16, random weights from
``--seed``) serves seeded requests on one chip through the normal entry
points (``init_sharded``, ``Engine``, ``Scheduler(fused_prefill=True)``),
and each request's first-token logits are compared with the engine's
token-by-token prefill of the same prompt.

``--chips 4``: the same model at TP=4 on one host, in one process. The
explicit mode (decode and fused prefill replay the init-compiled
``layer_allreduce`` / ``logits_allgather`` plans, Pallas kernels on a
TPU) serves the requests, and so does the auto (GSPMD) mode on the same
mesh, params and prompts; their first-token logits are compared.

The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only on a TPU at full width when every check passed: the
platform is ``tpu``; no fallback, retry or verification failure was
counted; engine and scheduler ran the requested mode; on four chips the
decode plans ran on the expected backend; every comparison is within
its tolerance. Otherwise the script exits non-zero with no such line.
``--reduced`` runs every phase and check at a tiny size (on the CPU with
``JAX_PLATFORMS=cpu``; add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for ``--chips 4``)
and never prints the result line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

#: The fused-prefill path and the token-by-token path compute the same
#: bf16 model with different shapes (a 512-token chunk against one token
#: at a time), so XLA sums every matmul and attention reduction in a
#: different order. bf16 keeps 8 significant bits (relative step 2**-8),
#: and 28 layers compound the rounding, so the paths agree to a few
#: hundredths of the logit scale, not bit for bit: max |diff| must stay
#: within this share of max |logit| of the reference.
PREFILL_RTOL = 0.05
#: Explicit TP=4 against GSPMD: the per-layer AllReduce sums four bf16
#: partials in the plan's order instead of XLA's, twice per layer.
#: Same bound, same reason.
TP_RTOL = 0.05

FULL = dict(arch="qwen3-1.7b", batch=8, max_kv=1024, lengths=(96, 480),
            new_tokens=32, seq_bucket=512)
REDUCED = dict(arch="qwen3-1.7b", batch=4, max_kv=64, lengths=(5, 19),
               new_tokens=4, seq_bucket=32)


class _CompileStats:
    """Compile seconds and persistent-cache hits, from JAX's monitoring
    events (counted in this process from the moment it is created)."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self._DURATIONS:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def snapshot(self) -> dict:
        return dict(compile_s=self.seconds, cache_hits=self.cache_hits,
                    cache_misses=self.cache_misses)


def serving_failures(sched, mode: str) -> list:
    """What makes a served run fail: any fallback, retry or verification
    failure among the scheduler's health counters (engine and
    communicator merged), or an engine or scheduler that ends in another
    mode than ``mode``."""
    health = sched.plan_report()["health"]
    bad = [f"health[{k}]={health[k]}"
           for k in ("fallbacks", "retries", "verify_failures")
           if health.get(k, 0)]
    ran = {"engine": sched.eng.mode, "scheduler": sched.mode}
    bad += [f"{who} ran {m!r}, requested {mode!r}"
            for who, m in ran.items() if m != mode]
    return bad


def _prompts(size: dict, cfg, seed: int):
    """``batch`` seeded prompts: half of each length in ``lengths``."""
    rng = np.random.RandomState(seed)
    per = size["batch"] // len(size["lengths"])
    return [rng.randint(0, cfg.vocab, n).astype(np.int32)
            for n in size["lengths"] for _ in range(per)]


def _serve(cfg, params, mesh, size, prompts, mode, log):
    """Serve ``prompts`` through Engine + Scheduler(fused_prefill=True);
    returns the engine and the scheduler, whose ``first_logits`` holds
    each request's first-token logits."""
    from repro.serve.engine import Engine, ServeConfig
    from repro.serve.scheduler import Request, Scheduler

    class _Recording(Scheduler):
        """Keeps each request's first-token logits row."""

        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.first_logits = {}

        def _sample_row(self, slot, row):
            if slot.emitted == 0:
                self.first_logits[slot.req.rid] = np.array(row)
            return super()._sample_row(slot, row)

    scfg = ServeConfig(batch=size["batch"], max_kv=size["max_kv"],
                       mode=mode, prefill_seq_buckets=(size["seq_bucket"],))
    t0 = time.perf_counter()
    eng = Engine(cfg, params, mesh, scfg)
    sched = _Recording(eng, prefill_chunk=2, fused_prefill=True)
    log(f"[{mode}] engine+scheduler set-up: "
        f"{time.perf_counter() - t0:.3f} s (plans compiled: "
        f"{eng.comm.stats['compiles']})")
    if not sched.fused_prefill:
        raise RuntimeError("fused prefill was gated off for this model")
    for rid, p in enumerate(prompts):
        sched.submit(Request(rid=rid, prompt=p,
                             max_new_tokens=size["new_tokens"]))
    ticks = []
    while sched.outstanding():
        t0 = time.perf_counter()
        sched.tick()          # host sync: the step's logits come back
        ticks.append(time.perf_counter() - t0)
        sched.advance(1.0)
    m = sched.metrics()
    log(f"[{mode}] served {m['completed']} requests, {m['tokens']} tokens "
        f"in {len(ticks)} ticks: {sum(ticks):.3f} s wall; first tick "
        f"(prefill + compiles) {ticks[0]:.3f} s, median later tick "
        f"{np.median(ticks[1:]) * 1e3:.3f} ms")
    return eng, sched


def _plan_summary(eng) -> dict:
    from repro.core import comm as comm_lib

    out = {}
    for name, fam in eng.decode_plans.items():
        plans = (fam.plans if isinstance(fam, comm_lib.BucketedPlan)
                 else {fam.shape[0]: fam})
        out[name] = {str(b): f"{p.algo}/{p.backend}"
                     for b, p in sorted(plans.items())}
    return out


def _logit_diff(ref: np.ndarray, got: np.ndarray) -> tuple:
    diff = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    return diff, scale


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--reduced", action="store_true",
                    help="rehearsal at a tiny size; never prints a result")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, flush=True)

    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)}")
    if dev.platform != "tpu" and not args.reduced:
        print(f"no TPU: JAX found {dev.platform} devices; nothing run",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices; JAX "
              f"found {len(devs)}", file=sys.stderr)
        return 2

    from jax.sharding import Mesh

    from repro import configs
    from repro.distributed import sharding as shd
    from repro.distributed.step import init_sharded, slot_buckets

    size = REDUCED if args.reduced else FULL
    cfg = configs.get_config(size["arch"])
    if args.reduced:
        cfg = dataclasses.replace(configs.reduced(cfg), dtype="bfloat16")
    mesh = Mesh(np.asarray(devs[:args.chips]).reshape(1, args.chips),
                ("data", "model"))
    stats = _CompileStats()
    failures = []
    try:
        t0 = time.perf_counter()
        params, _ = init_sharded(cfg, mesh, shd.MeshAxes(),
                                 jax.random.key(args.seed))
        jax.block_until_ready(params)
        n_params = sum(x.size for x in jax.tree.leaves(params))
        log(f"model: {cfg.name} d_model={cfg.d_model} layers="
            f"{cfg.n_layers} vocab={cfg.vocab} dtype={cfg.dtype} "
            f"params={n_params} on mesh {dict(mesh.shape)}; init "
            f"{time.perf_counter() - t0:.3f} s")
        prompts = _prompts(size, cfg, args.seed)
        log(f"requests: {len(prompts)} prompts of lengths "
            f"{[len(p) for p in prompts]}, {size['new_tokens']} new tokens "
            f"each, fused-prefill seq bucket {size['seq_bucket']}")

        if args.chips == 1:
            eng, sched = _serve(cfg, params, mesh, size, prompts, "auto",
                                log)
            # reference: the engine's token-by-token prefill of each
            # same-length group (tiled to the engine batch), then greedy
            # decode of the same number of tokens
            t0 = time.perf_counter()
            diffs, agree, total = [], 0, 0
            for n in size["lengths"]:
                rids = [i for i, p in enumerate(prompts) if len(p) == n]
                batch = np.stack([prompts[rids[i % len(rids)]]
                                  for i in range(size["batch"])])
                eng.reset()
                ref = np.asarray(eng.prefill(batch), np.float32)
                toks = eng.decode(ref, num_tokens=size["new_tokens"])
                for i, rid in enumerate(rids):
                    diffs.append(_logit_diff(ref[i],
                                             sched.first_logits[rid]))
                    got = sched.streams[rid]
                    agree += int(np.sum(toks[i, :len(got)] == got))
                    total += len(got)
            log(f"reference (Engine.prefill + decode): "
                f"{time.perf_counter() - t0:.3f} s")
            worst = max(diffs, key=lambda t: t[0] / t[1])
            ratio = worst[0] / worst[1]
            log(f"compare first-token logits, fused-prefill scheduler vs "
                f"token-by-token engine: max|diff|={worst[0]:.6g} at "
                f"max|logit|={worst[1]:.6g} -> {ratio:.6g} (tolerance "
                f"{PREFILL_RTOL}, bf16 sums in a different order)")
            log(f"greedy tokens agreeing: {agree}/{total} "
                f"({agree / total:.4f})")
            if not ratio <= PREFILL_RTOL:
                failures.append(f"prefill logits differ by {ratio:.4g} of "
                                f"the logit scale > {PREFILL_RTOL}")
            log(f"health: {sched.plan_report()['health']}")
            failures += serving_failures(sched, "auto")
        else:
            runs = {}
            for mode in ("explicit", "auto"):
                eng, sched = _serve(cfg, params, mesh, size, prompts, mode,
                                    log)
                log(f"[{mode}] health: {sched.plan_report()['health']}")
                failures += [f"[{mode}] {f}"
                             for f in serving_failures(sched, mode)]
                runs[mode] = (eng, sched)
            eng_x, sched_x = runs["explicit"]
            plans = _plan_summary(eng_x)
            log(f"plans (bucket rows -> algorithm/backend): "
                f"{json.dumps(plans)}")
            log(f"plan hits: " + json.dumps(
                {k: v["hits"] for k, v in
                 sched_x.plan_report()["plans"].items()}))
            want = eng_x.comm.backend
            decode_rows = set(slot_buckets(size["batch"]))
            off = [f"{name}[{b}]={v}" for name, fam in plans.items()
                   for b, v in fam.items()
                   if int(b) in decode_rows and not v.endswith("/" + want)]
            if off:
                failures.append(f"decode plans not on {want}: {off}")
            sched_a = runs["auto"][1]
            worst = max((_logit_diff(sched_a.first_logits[r],
                                     sched_x.first_logits[r])
                         for r in range(len(prompts))),
                        key=lambda t: t[0] / t[1])
            ratio = worst[0] / worst[1]
            agree = sum(int(np.sum(np.asarray(sched_x.streams[r])
                                   == np.asarray(sched_a.streams[r])))
                        for r in range(len(prompts)))
            total = sum(len(sched_a.streams[r]) for r in range(len(prompts)))
            log(f"compare first decode step logits, explicit (plan "
                f"replay) vs auto (GSPMD): max|diff|={worst[0]:.6g} at "
                f"max|logit|={worst[1]:.6g} -> {ratio:.6g} (tolerance "
                f"{TP_RTOL}, bf16 AllReduce sums in a different order)")
            log(f"greedy tokens agreeing: {agree}/{total} "
                f"({agree / total:.4f})")
            if not ratio <= TP_RTOL:
                failures.append(f"explicit logits differ from auto by "
                                f"{ratio:.4g} of the logit scale > {TP_RTOL}")
        log(f"compile: {json.dumps(stats.snapshot())}")
    finally:
        stats.close()

    if failures:
        for f in failures:
            print(f"FAIL: {f}", file=sys.stderr)
        return 1
    if args.reduced or dev.platform != "tpu":
        log("rehearsal: every check passed; no result line off a TPU at "
            "full width")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    sys.exit(run())
