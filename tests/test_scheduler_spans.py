"""The scheduler's ``sched.*`` host spans and the step's named scopes.

* Under a profiler trace each tick writes ``sched.tick`` around
  ``sched.admit``, ``sched.step`` (one per step, with its kind and
  bucket), ``sched.sync``, ``sched.sample`` and ``sched.release``, in
  that order, with the request ids that tie a request's spans together.
* Spans and scopes change no emitted token, and the scopes change the
  optimized step program only in its metadata: ``cache_write`` in every
  step, ``mask_slots`` where a step selects recurrent state (hybrid).
* ``Scheduler(clock=)`` stamps first token, finish and emission times
  from the caller's clock once the tick's tokens are sampled.
"""
import contextlib
import glob
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from benchmarks import loadgen
from repro import configs
from repro.distributed import sharding as shd
from repro.distributed import step as step_mod
from repro.models import transformer as tf
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request, Scheduler

BATCH = 4


@pytest.fixture(scope="module")
def eng():
    cfg = loadgen._serve_model()
    ax = shd.MeshAxes()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (ax.data[0], ax.model))
    params, _ = step_mod.init_sharded(cfg, mesh, ax, jax.random.key(0))
    return Engine(cfg, params, mesh,
                  ServeConfig(batch=BATCH, max_kv=64, mode="auto"), ax=ax)


def _requests():
    """Two greedy and one sampled request; rid 2 arrives late, so a
    later tick admits it while rid 0 and 1 decode."""
    return [Request(rid=0, prompt=np.arange(1, 8, dtype=np.int32),
                    max_new_tokens=3),
            Request(rid=1, prompt=np.asarray([5, 6], np.int32),
                    max_new_tokens=4, temperature=0.8, seed=3),
            Request(rid=2, prompt=np.asarray([9, 9, 9], np.int32),
                    max_new_tokens=2, arrival_s=2.0)]


def _serve(eng, **kw):
    sched = Scheduler(eng, prefill_chunk=3, **kw)
    for r in _requests():
        sched.submit(r)
    infos = sched.run_until_drained()
    return sched, infos


def _program_spans(trace_dir):
    """(start ns, end ns, name, args) of every ``sched.*`` host event."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.start_ns, e.end_ns, e.name, dict(e.stats))
                        for e in line.events if e.name.startswith("sched.")]
    return sorted(out, key=lambda s: (s[0], -s[1]))


@pytest.fixture(scope="module")
def traced(eng, tmp_path_factory):
    _serve(eng)                       # compile outside the trace
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        sched, infos = _serve(eng)
    finally:
        jax.profiler.stop_trace()
    return sched, infos, _program_spans(trace_dir)


def test_spans_nest_in_each_tick_in_order(traced):
    _, infos, spans = traced
    ticks = [s for s in spans if s[2] == "sched.tick"]
    assert len(ticks) == len(infos)
    for (t0, t1, _, _), info in zip(ticks, infos):
        inner = [s for s in spans if t0 <= s[0] and s[1] <= t1
                 and s[2] != "sched.tick"]
        names = [s[2] for s in inner]
        n_steps = info.micro_steps + 1
        assert names == (["sched.admit"] + ["sched.step"] * n_steps
                         + ["sched.sync", "sched.sample", "sched.release"])
        steps = [s[3] for s in inner if s[2] == "sched.step"]
        assert [a["kind"] for a in steps] == \
            ["prefill"] * info.micro_steps + ["combined"]
        assert {a["bucket"] for a in steps} == {info.bucket}
        sample = inner[-2][3]
        assert sample["rows"] == len(info.emissions)
        done = [str(e.rid) for e in info.emissions if e.done]
        # the trace reads a single id back as a number
        assert str(inner[-1][3].get("rids", "")) == " ".join(done)


def test_span_args_tie_a_request_together(traced):
    _, infos, spans = traced
    admits = [str(s[3]["rids"]) for s in spans
              if s[2] == "sched.admit" and "rids" in s[3]]
    assert admits == ["0 1", "2"]
    released = " ".join(str(s[3]["rids"]) for s in spans
                        if s[2] == "sched.release" and "rids" in s[3])
    assert sorted(released.split()) == ["0", "1", "2"]
    # rid 1 samples (temperature 0.8): every tick it emits in counts it
    sampled = sum(s[3]["sampled"] for s in spans if s[2] == "sched.sample")
    assert sampled == 4


def test_spans_change_no_token(traced, eng):
    sched, _, _ = traced
    plain, _ = _serve(eng)
    assert plain.streams == sched.streams
    assert set(plain.streams) == {0, 1, 2}


def _optimized_hlo(eng, b, scoped: bool):
    fn, _ = step_mod.make_sched_step(eng.cfg, eng.mesh, eng.ax, batch=b,
                                     max_kv=eng.scfg.max_kv)
    cache = tf.init_cache(eng.cfg, b, eng.scfg.max_kv)
    args = (eng.params, cache, np.zeros(b, np.int32), np.zeros(b, np.int32),
            np.ones(b, bool))
    with contextlib.ExitStack() as stack:
        if not scoped:
            mp = stack.enter_context(pytest.MonkeyPatch.context())
            mp.setattr(jax, "named_scope",
                       lambda name: contextlib.nullcontext())
        compiled = jax.jit(fn).lower(*args).compile()
    module, = compiled.runtime_executable().hlo_modules()
    opts = jax._src.lib.xla_client._xla.HloPrintOptions()
    opts.print_metadata = False
    return compiled.as_text(), module.to_string(opts)


@pytest.fixture(scope="module")
def hybrid_eng():
    cfg = configs.reduced(configs.get_config("hymba-1.5b"))
    ax = shd.MeshAxes()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (ax.data[0], ax.model))
    params, _ = step_mod.init_sharded(cfg, mesh, ax, jax.random.key(0))
    return Engine(cfg, params, mesh,
                  ServeConfig(batch=BATCH, max_kv=64, mode="auto"), ax=ax)


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_scopes_change_only_metadata(request, family):
    """Every step scopes its row write ``cache_write``; ``mask_slots``
    scopes the select of recurrent state, which only the hybrid step
    has: the dense step writes K/V in place and selects nothing."""
    eng = request.getfixturevalue("eng" if family == "dense"
                                  else "hybrid_eng")
    text, stripped = _optimized_hlo(eng, BATCH, scoped=True)
    _, stripped_plain = _optimized_hlo(eng, BATCH, scoped=False)
    assert stripped == stripped_plain
    assert "HloModule jit_step_sched" in text
    assert "/cache_write/" in text
    assert ("/mask_slots/" in text) == (family == "hybrid")


def test_clock_stamps_emissions_after_sampling(eng):
    reads = []

    def clock():
        reads.append(100.0 + len(reads))
        return reads[-1]

    sched, infos = _serve(eng, clock=clock)
    virtual, vinfos = _serve(eng)
    assert sched.streams == virtual.streams
    stamped = [i for i in infos if i.emissions]
    # one read per tick that sampled, after its tokens were sampled
    assert len(reads) == sum(1 for i in infos if i.bucket)
    for info in stamped:
        assert len({e.t for e in info.emissions}) == 1
        assert info.emissions[0].t >= 100.0 and info.emissions[0].t != info.now
    for info in vinfos:
        assert all(e.t == info.now for e in info.emissions)
    for rid, rec in sched._done.items():
        ts = [e.t for i in infos for e in i.emissions if e.rid == rid]
        assert rec["first"] == ts[0] and rec["finish"] == ts[-1]
