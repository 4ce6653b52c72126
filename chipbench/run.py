"""Run one cell of the benchmark once, on the machine it is started on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration,
``chipbench/configs/<config>.json``, and a traffic mix,
``chipbench/traffic/<traffic>.json``. Every metric is read by its own
file, ``chipbench/metrics/<metric>.py`` (``read(view) -> float or
None``); a metric split by the cells it serves (``x.online``,
``x.offline``) shares the reader ``x.py`` when it has none of its own.
The plain reference of a configuration's family is
``chipbench/reference/<family>.py``; the device peaks are
``chipbench/peaks.json``. A new cell or metric is new files and a new
entry in ``BENCHMARK.json``.

One process, no children. Set-up (imports, weights made on the device
from ``--seed``, engine and scheduler, loading or compiling every
program the cell's traffic reaches, and a pre-roll of the cell's own
schedule) is ``setup_s``; then the window of ``--seconds`` is served
open-loop on the wall clock. ``--trace 1`` traces the window's last
``trace_s`` seconds (from the mix file) and reports the per-layer
metrics instead of the end-to-end ones. After the window, a sample of
the greedy requests it finished is compared with the reference; the
comparison's numbers and limits are the last lines on standard error
and the ``checks`` key of the result, the last line on standard output.

Exits 2 with no result when JAX finds no TPU, fewer chips than the
cell asks for, or a device kind without peaks; 3 when the served path
fell back, retried, or ran another mode than configured.

Two options for defining a cell, never given by its runs: ``--control
1`` lets the tokens that the fp8 control (the reference one precision
step below the served bf16) puts first stand in for the served ones,
judged by the same comparison and limit, so that such a run has to come
out not correct; ``--rate r`` serves the mix's lengths with steady
Poisson arrivals at ``r`` requests/s, and the queue at the window's
start and end shows whether the program keeps up (the knee sweep).
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import counts  # noqa: E402
import harness as hz  # noqa: E402
import traffic as traffic_mod  # noqa: E402

TRACE_DIR = hz.CHECKOUT / ".chipbench" / "trace"
STEP_PREFIX = "jit_step"     # the program's jitted scheduler steps


def clock() -> float:
    return time.perf_counter() - T_START


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- metric readers --------------------------------------------------------
def reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = HERE / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class View:
    """What a metric reader may read."""
    m: dict                   # the configuration's model block
    peaks: dict               # this device's entry of peaks.json
    counts: object            # chipbench/counts.py
    seconds: float            # the window's length
    setup_s: float
    win: dict                 # harness.window_metrics of the window
    open_loop: bool
    traced: object = None     # TracedWindow, in a --trace 1 run


@dataclasses.dataclass
class TracedWindow:
    trace: object             # tracing.Trace
    lo: float                 # traced window on the trace's clock
    hi: float
    ticks: list               # harness ticks inside it
    prompt_len: dict          # rid -> prompt length
    steps: object             # [(kind, device seconds)] or None

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_s(self) -> float:
        import tracing
        return tracing.union_seconds(self.trace.ops, self.lo, self.hi)

    def step_seconds(self, kind: str):
        if self.steps is None:
            return None
        return [s for k, s in self.steps if k == kind]

    def decode_contexts(self) -> list:
        """Per decode step, the positions each emitting row attended:
        the k-th token of a prompt of length n is sampled by the step
        that attends n + k - 1 positions."""
        return [[self.prompt_len[rid] + k - 1 for rid, k in em]
                for _, _, _, bucket, em in self.ticks if bucket]

    def flops(self, m: dict) -> int:
        """Model operations of the traced ticks: every emitted token's
        step, plus the prompt of each request whose first token came in
        the window (its prefill is counted where it completed)."""
        f = 0
        for ctxs, (_, _, _, _, em) in zip(
                self.decode_contexts(), [t for t in self.ticks if t[3]]):
            for c, (rid, k) in zip(ctxs, em):
                f += counts.token_flops(m, c, True)
                if k == 1:
                    f += counts.prefill_flops(m, 0, self.prompt_len[rid] - 1)
        return f


def read_metrics(specs, view: View) -> dict:
    out = {}
    for spec in specs:
        v = reader(spec["name"])(view)
        if v is not None:
            out[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
    return out


# -- one run ---------------------------------------------------------------
def _traced(trace, rec, schedule, t_trace0):
    import tracing
    log(f"trace: {trace.summary}")
    ticks = [t for t in rec.ticks if t[0] >= t_trace0]
    spans = [s for s in trace.spans if s[2] in tracing.HOST_SPANS]
    if not spans:
        log("trace: no harness spans in the traced window")
        return None
    lo, hi = spans[0][0], max(s[1] for s in spans)
    expected = []
    for _, _, micro, bucket, _ in ticks:
        expected += ["prefill"] * micro + (["decode"] if bucket else [])
    n_tick_spans = sum(1 for s in spans if s[2] == "tick")
    steps = None
    if n_tick_spans == len(ticks):
        steps = tracing.label_modules(trace.modules, lo, hi, expected,
                                      STEP_PREFIX)
    if steps is None:
        log(f"trace: {n_tick_spans} tick spans for {len(ticks)} ticks, "
            f"{sum(1 for m in trace.modules if m[2].startswith(STEP_PREFIX))}"
            f" step programs for {len(expected)} steps: step times not "
            f"attributed")
    names = {}
    for m in trace.modules:
        names[m[2]] = names.get(m[2], 0) + 1
    log(f"trace: {trace.n_devices} device planes, {len(trace.ops)} device "
        f"operations, programs run: "
        f"{sorted(names.items(), key=lambda kv: -kv[1])[:12]}")
    plen = {r.rid: len(r.prompt) for r in schedule.reqs}
    return TracedWindow(trace, lo, hi, ticks, plen, steps)


def run_cell(cell, *, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, trace_dir=TRACE_DIR, control: bool = False):
    """Serve one run of ``cell``; returns (result dict, exit code)."""
    import jax

    dev = devices[0]

    def in_use() -> int:
        return int((dev.memory_stats() or {}).get("bytes_in_use", 0))

    stats = hz.CompileStats()
    try:
        t_imported = clock()
        system = hz.build(cell.config, seed, devices)
        jax.block_until_ready(system.params)
        t_built = clock()
        n_warm = hz.warm_up(system, clock)
        t_warm = clock()
        log(f"compile after warm-up: {json.dumps(stats.snapshot())}")
        schedule = traffic_mod.generate(cell.mix, seed=seed, window_s=seconds,
                                        vocab=system.cfg.vocab)
        rec = hz.Records()
        origin = clock()
        w0 = origin + schedule.preroll_s
        w1 = w0 + seconds
        sched = system.sched
        hz.drive(sched, schedule, clock, origin, w0, rec)
        setup_s = clock()
        compiles0 = stats.compiles
        queued0 = len(rec.submitted) - rec.n_admitted
        traced = None
        if trace:
            import tracing
            t_trace0 = max(w0, w1 - float(cell.mix["trace_s"]))
            hz.drive(sched, schedule, clock, origin, t_trace0, rec,
                     memory=in_use)
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host spans yes, every call no
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            try:
                hz.drive(sched, schedule, clock, origin, w1, rec,
                         span=jax.profiler.TraceAnnotation, memory=in_use)
            finally:
                jax.profiler.stop_trace()
            traced = _traced(tracing.load(str(trace_dir)), rec, schedule,
                             t_trace0)
        else:
            hz.drive(sched, schedule, clock, origin, w1, rec, memory=in_use)
        in_window = stats.compiles - compiles0
        queued1 = len(rec.submitted) - rec.n_admitted
        failures = hz.serving_failures(sched, cell.config["serving"]["mode"])
        peak_bytes = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
        win = hz.window_metrics(schedule, rec, origin, w0, w1)
        log(f"set-up: {setup_s:.3f} s = imports {t_imported:.3f} + weights "
            f"and engine {t_built - t_imported:.3f} + warm-up "
            f"{t_warm - t_built:.3f} ({n_warm} requests) + schedule and "
            f"pre-roll {setup_s - t_warm:.3f}")
        log(f"compile at window start: compiles {compiles0}, "
            f"{json.dumps(stats.snapshot())}; compiles inside the window: "
            f"{in_window}")
        _log_window(win, schedule, seconds)
        log(f"queue: {queued0} waiting at the window's start, {queued1} at "
            f"its end")
        log(f"memory: process peak {peak_bytes} bytes; most in use after a "
            f"tick of the window {rec.live_bytes} bytes")
        if failures:
            for f in failures:
                log(f"FAIL: {f}")
            return None, 3
        view = View(m=cell.config["model"], peaks=peaks, counts=counts,
                    seconds=seconds, setup_s=setup_s, win=win,
                    open_loop=schedule.open_loop, traced=traced)
        metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                               view)
        reqs = hz.sample(schedule, rec, seed, w1)
        hz.free(system)
        t_ref = clock()
        chk = (hz.check(system.ref, cell.config["model"], system.params, reqs,
                        rec, hz.pad_len(cell.mix), control=control) if reqs
               else dict(served_logit_gap=float("nan"),
                         control_logit_gap=float("nan"), requests=0, tokens=0))
        log(f"reference: {chk['requests']} requests, {chk['tokens']} served "
            f"tokens, {clock() - t_ref:.3f} s")
    finally:
        stats.close()

    limit = float(cell.config["check"]["served_logit_gap"])
    gap = chk["served_logit_gap"]
    if control:
        log(f"control: the fp8 control's tokens are judged in place of the "
            f"served ones; the served tokens' own gap {gap!r}")
        gap = chk["control_logit_gap"]
    correct = bool(chk["requests"] > 0 and gap <= limit)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(cell.workload["chips"]),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct,
              "attempted": win["due"] if schedule.open_loop else win["admitted"],
              "failed": 0, "metrics": metrics, "device": device}
    if traced is not None:
        import tracing
        busy = traced.busy_s()
        device.update(busy_s=busy, window_s=traced.window_s)
        gaps = tracing.idle_gaps(traced.trace.ops, traced.lo, traced.hi)
        named = tracing.attribute_gaps(gaps, traced.trace.spans)
        result["breakdown"] = {
            "device_ops": tracing.top_ops(traced.trace.ops, traced.lo,
                                          traced.hi),
            "idle_gaps": [[n, s] for n, s in
                          sorted(named.items(), key=lambda kv: -kv[1])][:10]}
    result["memory"] = {"process_peak_bytes": peak_bytes,
                        "window_in_use_max_bytes": rec.live_bytes}
    result["checks"] = {
        "control_logit_gap" if control else "served_logit_gap": {
            "value": gap if chk["requests"] else None, "limit": limit}}
    return result, 0


def _log_window(win: dict, schedule, seconds: float) -> None:
    def pct(xs, q):
        return f"{hz.percentile(xs, q) * 1e3:.3f} ms" if xs else "none"

    late = win["lateness"]
    log(f"window: {win['due']} requests due, {win['admitted']} admitted, "
        f"{win['tokens']} tokens ({win['tokens'] / seconds:.3f}/s); TTFT "
        f"samples {len(win['ttft'])} ({win['censored']} without a first "
        f"token by the window's end), p50 {pct(win['ttft'], 50)}, max "
        f"{pct(win['ttft'], 100)}; inter-token gaps {len(win['gaps'])}, "
        f"p50 {pct(win['gaps'], 50)}, p99 {pct(win['gaps'], 99)}; queue "
        f"waits {len(win['waits'])}, p95 {pct(win['waits'], 95)}")
    if schedule.open_loop:
        log(f"generator lateness: p50 {pct(late, 50)}, p99 {pct(late, 99)}, "
            f"max {max(late) * 1e3 if late else 0:.3f} ms")


def with_rate(cell, rate: float):
    """``cell`` with its arrivals replaced by steady Poisson arrivals at
    ``rate`` requests/s (the knee sweep)."""
    return dataclasses.replace(cell, mix=dict(
        cell.mix, arrival={"process": "poisson", "rate_rps": rate}))


# -- entry -----------------------------------------------------------------
def _enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``.jax_cache`` at the root of the checkout, a fixed path. Every
    program is kept, however short its compile."""
    import os

    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        hz.CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="judge the fp8 control's tokens in place of the "
                         "served ones")
    ap.add_argument("--rate", type=float, default=None,
                    help="steady Poisson arrivals at this rate (req/s)")
    args = ap.parse_args(argv)

    try:
        cell = hz.load_cell(args.workload)
    except (OSError, ValueError, KeyError) as e:
        log(f"cannot load workload {args.workload!r}: {e}")
        return 2
    if args.rate is not None:
        cell = with_rate(cell, args.rate)
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform} devices; nothing run")
        return 2
    if len(devices) < cell.workload["chips"]:
        log(f"{args.workload} needs {cell.workload['chips']} chips; JAX "
            f"found {len(devices)}")
        return 2
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if dev.device_kind not in peaks:
        log(f"no peaks recorded for device kind {dev.device_kind!r}")
        return 2
    log(f"compile cache: {_enable_compile_cache()}")

    result, code = run_cell(cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), devices=devices,
                            peaks=peaks[dev.device_kind],
                            control=bool(args.control))
    if result is None:
        return code
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
