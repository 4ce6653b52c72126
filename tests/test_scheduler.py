"""Deterministic test harness for the serving stack (PR headline).

Proves the continuous-batching scheduler + multi-replica plan-file
router end to end:

* **Bit-identity** — a seeded Poisson/Zipf trace served through 2
  router replicas (each loaded from the SAME exported plan-file set,
  §4.4) emits, for every request, the exact token stream a sequential
  single-request run produces. Continuous batching is a pure
  throughput optimization or it is a bug.
* **Property tests** (`tests/_hypothesis_shim.py` when hypothesis is
  absent) — random seeded traces never exceed the slot budget, never
  starve a request (FIFO admission order + bounded virtual wait), and
  emit exactly the sequential baseline's tokens.
* **Plan accounting** — `BucketedPlan` hit counters are monotone under
  mixed-bucket traffic and `plan_report()` returns a consistent
  snapshot (mutating it cannot corrupt live state).
* **Degraded-replica visibility** — a replica whose shipped plan set
  is rejected falls back to auto, still serves bit-identical tokens,
  and shows up in the router aggregate's `degraded` list.

Everything runs on the emulated CPU mesh (conftest pins 16 devices)
with the reduced qwen3 config; module-scoped fixtures keep the engine
builds to a handful.
"""
import asyncio
import dataclasses
import functools
import itertools
import json
import pathlib

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from benchmarks import loadgen  # noqa: E402
from repro.core import api  # noqa: E402
from repro.core import comm as comm_lib  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.distributed import step as step_mod  # noqa: E402
from repro.serve.engine import Engine, ServeConfig, _check_plan_set  # noqa: E402
from repro.serve.router import Router, build_replicas  # noqa: E402
from repro.serve.scheduler import (AsyncServeEngine, Request,  # noqa: E402
                                   Scheduler)

TP = 2
BATCH = 4


def _trace(tcfg, vocab, hot_temperature=0.0):
    """The seeded trace; optionally flip every third request to
    temperature sampling so greedy and seeded-categorical rows share
    steps (both must stay schedule-invariant)."""
    trace = loadgen.synth_trace(tcfg, vocab)
    if hot_temperature:
        trace = [dataclasses.replace(r, temperature=hot_temperature)
                 if i % 3 == 2 else r for i, r in enumerate(trace)]
    return trace


# ---------------------------------------------------------------------------
# fixtures: one fleet + one driven run per module
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """2 explicit replicas x tp=2, both loaded from one exported plan
    set — the §4.4 round trip under test."""
    cfg = loadgen._serve_model()
    scfg = ServeConfig(batch=BATCH, max_kv=64, mode="explicit")
    plan_dir = tmp_path_factory.mktemp("plan_set")
    router = build_replicas(cfg, scfg, n_replicas=2, tp=TP,
                            plan_dir=plan_dir, mode="explicit")
    return dict(cfg=cfg, scfg=scfg, router=router, plan_dir=plan_dir)


@pytest.fixture(scope="module")
def driven(fleet):
    """The main seeded run: mixed greedy + temperature traffic through
    the router, plus the sequential single-request ground truth from a
    THIRD replica loaded from the same plan files."""
    cfg, scfg = fleet["cfg"], fleet["scfg"]
    router = fleet["router"]
    tcfg = loadgen.TrafficConfig(seed=3, n_requests=14, rate_rps=5.0,
                                 max_prompt=10, max_new=6, step_s=0.05)
    trace = _trace(tcfg, cfg.vocab, hot_temperature=0.8)
    hits_before = {
        i: dict(r.eng.decode_plans["layer_allreduce"].hits)
        for i, r in enumerate(router.replicas)}
    infos = loadgen.run_load(router, trace, step_s=tcfg.step_s)
    base = build_replicas(cfg, scfg, n_replicas=1, tp=TP,
                          plan_dir=fleet["plan_dir"], mode="explicit")
    base_streams = loadgen.sequential_baseline(
        base.replicas[0], trace, step_s=tcfg.step_s)
    return dict(trace=trace, infos=infos, base=base_streams,
                hits_before=hits_before, tcfg=tcfg)


# ---------------------------------------------------------------------------
# tentpole: bit-identity + zero drops through the plan-file fleet
# ---------------------------------------------------------------------------
def test_streams_bit_identical_to_sequential(fleet, driven):
    """The headline assertion: co-batching, chunked prefill, slot
    compaction, bucket switching, and routing never change one token
    vs. running each request alone."""
    streams = fleet["router"].streams
    for req in driven["trace"]:
        assert streams[req.rid] == driven["base"][req.rid], \
            f"request {req.rid} diverged from sequential baseline"
        assert len(streams[req.rid]) >= 1


def test_zero_dropped_all_completed(fleet, driven):
    m = fleet["router"].metrics()
    assert m["completed"] == len(driven["trace"])
    assert m["dropped"] == 0
    assert m["outstanding"] == 0
    assert m["tokens"] == sum(len(s) for s in driven["base"].values())
    # every request either hit EOS or its own budget — never truncated
    # by the scheduler
    by_rid = {r.rid: r for r in driven["trace"]}
    for rid, toks in fleet["router"].streams.items():
        req = by_rid[rid]
        assert len(toks) <= req.max_new_tokens
        if len(toks) < req.max_new_tokens:
            assert toks[-1] == fleet["scfg"].eos_id


def test_routing_is_deterministic_and_load_balanced(fleet, driven):
    routed = fleet["router"].routed
    assert set(routed) == {r.rid for r in driven["trace"]}
    # least-loaded with tie->0 must touch both replicas on 14 requests
    assert set(routed.values()) == {0, 1}


def test_slot_budget_and_bucket_ladder(fleet, driven):
    """No tick ever runs more resident requests than max_slots, and
    every combined step ran at a ladder bucket that covers them."""
    ladder = step_mod.slot_buckets(BATCH)
    for info in driven["infos"]:
        assert info.n_active <= 2 * BATCH        # fleet-wide (2 replicas)
        assert info.bucket in (0, *ladder)
    m = fleet["router"].metrics()
    assert set(m["bucket_steps"]) <= set(ladder)
    assert sum(m["bucket_steps"].values()) > 0


def test_virtual_time_metrics(fleet, driven):
    """TTFT/wait percentiles are finite, ordered, and reproducible
    straight from the seeded virtual clock."""
    m = fleet["router"].metrics()
    for k in ("ttft_vs", "wait_vs"):
        assert 0 <= m[k]["p50"] <= m[k]["p95"] <= m[k]["max"]
    assert m["tokens_per_vs"] > 0
    # TTFT includes queueing + prefill, so it dominates the pure wait
    assert m["ttft_vs"]["max"] >= m["wait_vs"]["max"]


# ---------------------------------------------------------------------------
# satellite: BucketedPlan hit accounting + plan_report snapshots
# ---------------------------------------------------------------------------
def test_bucketed_hits_monotone_under_mixed_traffic(fleet, driven):
    """Mixed-bucket concurrent traffic only ever increments the loaded
    family's per-bucket hit counters (hits count plan dispatches at
    trace time: one per compiled step function per bucket)."""
    for i, r in enumerate(fleet["router"].replicas):
        fam = r.eng.decode_plans["layer_allreduce"]
        assert isinstance(fam, comm_lib.BucketedPlan)
        before = driven["hits_before"][i]
        assert set(fam.hits) <= set(fam.buckets)
        for b, n in before.items():
            assert fam.hits.get(b, 0) >= n
        assert sum(fam.hits.values()) > sum(before.values())


def test_plan_report_is_a_consistent_snapshot(fleet, driven):
    """plan_report() must be safe to hand to a metrics exporter:
    mutating the returned structure cannot corrupt live counters, and
    two immediate calls agree."""
    sched = fleet["router"].replicas[0]
    rep = sched.plan_report()
    ref = json.dumps(rep, sort_keys=True, default=str)
    # mutate every layer of the returned snapshot
    rep["health"]["fallbacks"] += 100
    rep["mode"] = "corrupted"
    rep["plans"]["layer_allreduce"]["hits"].clear()
    rep["scheduler"]["bucket_steps"].clear()
    rep2 = sched.plan_report()
    assert json.dumps(rep2, sort_keys=True, default=str) == ref
    # and the live objects really were untouched
    assert sched.eng.health["fallbacks"] == rep2["health"]["fallbacks"]
    assert sched.eng.decode_plans["layer_allreduce"].hits


def test_router_aggregates_fleet_health(fleet, driven):
    rep = fleet["router"].plan_report()
    assert rep["modes"] == ["explicit", "explicit"]
    assert rep["requested_modes"] == ["explicit", "explicit"]
    assert rep["degraded"] == []
    per = [r["health"] for r in rep["replicas"]]
    for k, v in rep["health"].items():
        assert v == sum(h[k] for h in per)


# ---------------------------------------------------------------------------
# satellite: plan-set export/load round trip (the shipped artifact)
# ---------------------------------------------------------------------------
def test_plan_set_files_and_roundtrip(fleet):
    plan_dir = pathlib.Path(fleet["plan_dir"])
    manifest = json.loads((plan_dir / "plan_set.json").read_text())
    assert manifest["kind"] == "plan_set"
    assert "layer_allreduce" in manifest["plans"]
    for name, entry in manifest["plans"].items():
        assert (plan_dir / entry["file"]).is_file()
        # each file loads standalone through the public single-plan API
        plan = api.load_plan(plan_dir / entry["file"])
        assert plan.to_json()

    # two independent loads of the same artifact are byte-identical
    a = api.load_plan_set(plan_dir)
    b = api.load_plan_set(plan_dir)
    assert set(a) == set(b) == set(manifest["plans"])
    for name in a:
        assert a[name].to_json() == b[name].to_json()
    # ...and match what the replicas are actually serving with (modulo
    # the replica's live dispatch hit counters)
    def norm(plan):
        d = json.loads(plan.to_json())
        d.pop("hits", None)
        return d

    served = fleet["router"].replicas[0].eng.decode_plans
    for name in a:
        assert norm(a[name]) == norm(served[name])


def test_plan_set_load_rejects_bad_artifacts(tmp_path):
    with pytest.raises(ValueError, match="plan_set"):
        api.load_plan_set(tmp_path)          # no manifest
    bad = tmp_path / "plan_set.json"
    bad.write_text(json.dumps({"version": 1, "kind": "nonsense",
                               "plans": {}}))
    with pytest.raises(ValueError, match="kind"):
        api.load_plan_set(tmp_path)


def test_check_plan_set_rejects_mismatches(fleet):
    cfg = fleet["cfg"]
    plans = api.load_plan_set(fleet["plan_dir"])
    _check_plan_set(cfg, plans, tp=TP, batch_local=BATCH)     # sane
    with pytest.raises(ValueError, match="layer_allreduce"):
        _check_plan_set(cfg, {}, tp=TP, batch_local=BATCH)
    with pytest.raises(ValueError):
        _check_plan_set(cfg, plans, tp=TP, batch_local=BATCH * 64)
    with pytest.raises(ValueError):
        _check_plan_set(cfg, plans, tp=TP * 2, batch_local=BATCH)


# ---------------------------------------------------------------------------
# satellite: a degraded replica is visible AND still bit-identical
# ---------------------------------------------------------------------------
def test_degraded_replica_visible_and_bit_identical(fleet, driven):
    """Replica 1 gets a rejected plan set (empty dict), falls back to
    auto: the router aggregate must name it, and its tokens must still
    match the explicit baseline exactly — degraded means slower, never
    wrong."""
    cfg, scfg = fleet["cfg"], fleet["scfg"]
    ax = shd.MeshAxes()
    devs = jax.devices()

    def replica(decode_plans, dev0):
        mesh = Mesh(np.asarray(devs[dev0:dev0 + TP]).reshape(1, TP),
                    (ax.data[0], ax.model))
        params, _ = step_mod.init_sharded(cfg, mesh, ax, jax.random.key(0))
        eng = Engine(cfg, params, mesh, scfg, ax=ax, mode="explicit",
                     decode_plans=decode_plans)
        return Scheduler(eng)

    good = replica(api.load_plan_set(fleet["plan_dir"]), 0)
    with pytest.warns(UserWarning, match="rejected"):
        bad = replica({}, TP)
    assert good.eng.mode == "explicit"
    assert bad.eng.mode == "auto" and bad.eng.requested_mode == "explicit"

    router = Router([good, bad])
    rep = router.plan_report()
    assert rep["modes"] == ["explicit", "auto"]
    assert rep["degraded"] == [1]
    assert rep["health"]["fallbacks"] >= 1

    trace = driven["trace"][:6]
    loadgen.run_load(router, trace, step_s=driven["tcfg"].step_s)
    assert set(router.routed.values()) == {0, 1}   # both replicas served
    for req in trace:
        assert router.streams[req.rid] == driven["base"][req.rid]


# ---------------------------------------------------------------------------
# async front-end: one pump, interleaved generators, same tokens
# ---------------------------------------------------------------------------
def test_async_streaming_matches_sync(fleet, driven):
    cfg, scfg = fleet["cfg"], fleet["scfg"]
    base = build_replicas(cfg, scfg, n_replicas=1, tp=TP,
                          plan_dir=fleet["plan_dir"], mode="explicit")
    eng = AsyncServeEngine(base.replicas[0], step_s=driven["tcfg"].step_s)
    trace = [dataclasses.replace(r, arrival_s=0.0)
             for r in driven["trace"][:4]]

    async def collect(req):
        return [tok async for tok in eng.generate(req)]

    async def main():
        return await asyncio.gather(*(collect(r) for r in trace))

    outs = asyncio.run(main())
    for req, toks in zip(trace, outs):
        assert toks == driven["base"][req.rid]


# ---------------------------------------------------------------------------
# scheduler-level behavior on a cheap 1-device auto engine
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _tiny_engine():
    """1-device auto engine — plain function (not a fixture) because
    the hypothesis shim's ``given`` wrapper can't receive pytest
    fixtures; cached so scheduler tests and the property run share one
    build."""
    cfg = loadgen._serve_model()
    ax = shd.MeshAxes()
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (ax.data[0], ax.model))
    params, _ = step_mod.init_sharded(cfg, mesh, ax, jax.random.key(0))
    return Engine(cfg, params, mesh,
                  ServeConfig(batch=BATCH, max_kv=64, mode="auto"), ax=ax)


@pytest.fixture(scope="module")
def tiny_eng():
    return _tiny_engine()


def test_chunked_prefill_never_stalls_decode(tiny_eng):
    """A long co-resident prompt costs micro-steps but a decoding
    request still emits exactly one token on every tick."""
    sched = Scheduler(tiny_eng, max_slots=2, prefill_chunk=3)
    long_p = Request(rid=0, prompt=np.arange(1, 10, dtype=np.int32),
                     max_new_tokens=3)
    short = Request(rid=1, prompt=np.asarray([7], np.int32),
                    max_new_tokens=8)
    sched.submit(short)
    sched.submit(long_p)
    infos = []
    while sched.outstanding():
        infos.append(sched.tick())
        sched.advance(1.0)
    # every tick while rid=1 was live emitted a token for it
    live = [i for i in infos if any(e.rid == 1 and e.done
                                    for e in i.emissions)]
    first_done = infos.index(live[0])
    for info in infos[:first_done + 1]:
        assert any(e.rid == 1 for e in info.emissions), \
            "decode request stalled behind a prefilling prompt"
        assert info.micro_steps <= sched.prefill_chunk - 1
    assert len(sched.streams[1]) == 8


def test_submit_and_clock_validation(tiny_eng):
    sched = Scheduler(tiny_eng, max_slots=2)
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit(Request(rid=0, prompt=np.asarray([], np.int32),
                             max_new_tokens=1))
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit(Request(rid=0, prompt=np.asarray([1], np.int32),
                             max_new_tokens=0))
    sched.submit(Request(rid=0, prompt=np.asarray([1], np.int32),
                         max_new_tokens=1))
    with pytest.raises(ValueError, match="duplicate"):
        sched.submit(Request(rid=0, prompt=np.asarray([2], np.int32),
                             max_new_tokens=1))
    sched.advance(5.0)
    with pytest.raises(ValueError, match="backwards"):
        sched.tick(1.0)
    with pytest.raises(ValueError, match="max_slots"):
        Scheduler(tiny_eng, max_slots=BATCH + 1)
    with pytest.raises(ValueError, match="prefill_chunk"):
        Scheduler(tiny_eng, prefill_chunk=0)


def test_bounded_queue_backpressure(tiny_eng):
    """The opt-in ``queue_limit`` rejects loudly: ``submit`` returns
    False, the drop is counted in ``metrics()['rejected']``, and the
    router propagates the rejection (returns None, rid NOT routed)."""
    with pytest.raises(ValueError, match="queue_limit"):
        Scheduler(tiny_eng, queue_limit=0)

    def req(rid):
        return Request(rid=rid, prompt=np.asarray([1, 2], np.int32),
                       max_new_tokens=1)

    sched = Scheduler(tiny_eng, max_slots=2, queue_limit=2)
    assert sched.submit(req(0)) is True
    assert sched.submit(req(1)) is True
    assert sched.submit(req(2)) is False
    assert sched.metrics()["rejected"] == 1
    assert 2 not in sched.streams

    router = Router([Scheduler(tiny_eng, max_slots=2, queue_limit=1)])
    assert router.submit(req(10)) == 0
    assert router.submit(req(11)) is None
    assert 10 in router.routed and 11 not in router.routed
    assert router.metrics()["rejected"] == 1


# ---------------------------------------------------------------------------
# property tests: random seeded traces (hypothesis / vendored shim)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=1)
def _prop_env():
    """Shared schedulers so the jitted per-bucket step functions
    compile once for the whole property run; rids stay globally unique
    via the counter."""
    return dict(
        conc=Scheduler(_tiny_engine(), max_slots=2, prefill_chunk=2),
        seq=Scheduler(_tiny_engine(), max_slots=1, prefill_chunk=2),
        rid=itertools.count(1000))


@settings(deadline=None, max_examples=5)
@given(st.integers(0, 10_000), st.integers(1, 6), st.floats(0.5, 8.0))
def test_scheduler_invariants_random_traces(seed, n_req, rate):
    """For any seeded trace: the slot budget holds on every tick, FIFO
    admission never starves (admission order == arrival order, waits
    bounded by the total virtual work), and the emitted tokens are
    exactly the sequential baseline's."""
    tcfg = loadgen.TrafficConfig(
        seed=seed, n_requests=n_req, rate_rps=rate, max_prompt=6,
        max_new=4, temperature=0.8 if seed % 2 else 0.0, step_s=0.05)
    env = _prop_env()
    conc, seq = env["conc"], env["seq"]
    t0 = conc.now
    # shift arrivals onto the shared scheduler's running clock (it is
    # reused across examples and virtual time only moves forward)
    trace = [dataclasses.replace(r, rid=next(env["rid"]),
                                 arrival_s=round(r.arrival_s + t0, 6))
             for r in loadgen.synth_trace(tcfg, conc.eng.cfg.vocab)]

    infos = loadgen.run_load(conc, trace, step_s=tcfg.step_s)

    # slot budget: never more resident than max_slots, on any tick
    assert all(i.n_active <= conc.max_slots for i in infos)
    # no starvation: everyone admitted, FIFO in arrival order, within
    # the total virtual work the trace could possibly cost
    recs = [conc._done[r.rid] for r in trace]
    assert len(recs) == n_req
    admits = [r["admit"] for r in
              sorted(recs, key=lambda r: r["arrival"])]
    assert admits == sorted(admits)
    bound = (conc.now - t0) + tcfg.step_s
    assert all(r["admit"] - r["arrival"] <= bound for r in recs)

    # exact token conservation vs. the sequential baseline
    base = loadgen.sequential_baseline(
        seq, [dataclasses.replace(r, rid=r.rid + 500_000) for r in trace],
        step_s=tcfg.step_s)
    for r in trace:
        assert conc.streams[r.rid] == base[r.rid + 500_000]
