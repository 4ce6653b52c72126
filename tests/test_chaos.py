"""Chaos: runtime fault injection against the serve engine's
guardrails. Every runtime fault class (``faults.RUNTIME_KINDS``) must
be detected and recovered — bounded retry for transients, numeric
guard + auto fallback for corruption, watchdog + auto fallback for
stalls — with the decoded greedy tokens bit-identical to the clean
auto reference. The static half of the taxonomy (verifier rejection)
is covered by tests/test_verify.py; the exhaustive matrix runs in
``scripts/check.sh --chaos``."""
import numpy as np
import pytest

from benchmarks.chaos import _tiny_engine
from repro.core import faults


def _decode(eng, prompts, tokens=4):
    return np.asarray(eng.decode(eng.prefill(prompts), num_tokens=tokens))


@pytest.fixture(scope="module")
def reference():
    """Clean auto-mode decode: the ground truth every recovered engine
    must reproduce (prompts are deterministic across _tiny_engine calls)."""
    eng, prompts = _tiny_engine("auto", {})
    return _decode(eng, prompts), prompts


def test_guardrails_do_not_perturb_clean_decode(reference):
    """Acceptance: with every guardrail armed and no fault, explicit
    decode stays explicit, matches auto bit-for-bit, and the decode
    loop is pure plan replay (compile counters flat)."""
    ref_toks, prompts = reference
    eng, _ = _tiny_engine("explicit",
                          dict(guard_numerics=True, plan_timeout_s=30.0))
    assert eng.mode == "explicit"
    logits = eng.prefill(prompts)
    compiles = eng.comm.stats["compiles"]
    toks = np.asarray(eng.decode(logits, num_tokens=4))
    assert eng.comm.stats["compiles"] == compiles, "decode recompiled"
    assert eng.mode == "explicit"
    assert (toks == ref_toks).all()
    health = eng.plan_report()["health"]
    assert health["retries"] == 0 and health["faults_detected"] == 0
    assert health["timeouts"] == 0 and health["fallbacks"] == 0
    assert health["verified"] > 0 and health["verify_failures"] == 0


def test_transient_failure_recovers_by_retry(reference):
    ref_toks, prompts = reference
    eng, _ = _tiny_engine("explicit", {})
    with faults.inject(faults.FaultSpec("fail_call", count=1)) as inj:
        toks = _decode(eng, prompts)
    assert inj.fired == 1
    assert eng.mode == "explicit", "a transient must not cost the fast path"
    assert eng.health["retries"] >= 1
    assert eng.health["fallbacks"] == 0
    assert (toks == ref_toks).all()


def test_persistent_failure_falls_back_to_auto(reference):
    """Retries exhausted -> loud, permanent degradation to auto; the
    failed step re-runs there so no token is lost."""
    ref_toks, prompts = reference
    eng, _ = _tiny_engine("explicit", {})
    with pytest.warns(UserWarning, match="falling back to auto"):
        with faults.inject(faults.FaultSpec("fail_call", count=100)):
            toks = _decode(eng, prompts)
    assert eng.mode == "auto"
    assert eng.health["retries"] == eng.scfg.max_retries
    assert eng.health["fallbacks"] >= 1
    assert (toks == ref_toks).all()


def test_numeric_guard_detects_corruption(reference):
    ref_toks, prompts = reference
    eng, _ = _tiny_engine("explicit", dict(guard_numerics=True))
    with pytest.warns(UserWarning, match="non-finite"):
        with faults.inject(faults.FaultSpec("corrupt_chunk", count=1)) as inj:
            toks = _decode(eng, prompts)
    assert inj.fired == 1
    assert eng.mode == "auto"
    assert eng.health["faults_detected"] >= 1
    assert (toks == ref_toks).all()


def test_watchdog_times_out_stalled_rank(reference):
    ref_toks, prompts = reference
    eng, _ = _tiny_engine("explicit", dict(plan_timeout_s=0.75))
    with pytest.warns(UserWarning, match="plan_timeout_s"):
        with faults.inject(
                faults.FaultSpec("stall_rank", count=1, delay_s=5.0)) as inj:
            toks = _decode(eng, prompts)
    assert inj.fired == 1
    assert eng.mode == "auto"
    assert eng.health["timeouts"] >= 1
    assert (toks == ref_toks).all()


def test_health_counters_in_plan_report():
    eng, _ = _tiny_engine("explicit", {})
    health = eng.plan_report()["health"]
    for key in ("retries", "timeouts", "faults_detected", "fallbacks",
                "verified", "verify_failures", "recompiles"):
        assert key in health
    assert health["verified"] > 0      # init-compiled plans were verified


def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.FaultSpec("melt_gpu")
    prog_fault = faults.FaultSpec("fail_call")
    from repro.core.algorithms import REGISTRY
    with pytest.raises(ValueError, match="runtime fault"):
        faults.inject_program(REGISTRY["allreduce_ring"](4), prog_fault, 4)
    with pytest.raises(ValueError, match="static fault"):
        faults.FaultInjector(faults.FaultSpec("drop_put"))
    assert faults.active() is None     # nothing leaks between tests


# ---------------------------------------------------------------------------
# no hiding: a step that fails to compile is an error, not a fault
# ---------------------------------------------------------------------------
@pytest.fixture
def mosaic_refusal(monkeypatch):
    """Every collective executor raises what a Mosaic refusal raises at
    compile time (a RuntimeError) while the step traces."""
    from repro.core import executor

    def refuse(self, x):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(executor.XlaExecutor, "__call__", refuse)
    monkeypatch.setattr(executor.PallasExecutor, "__call__", refuse)


def test_engine_compile_error_propagates(mosaic_refusal):
    eng, prompts = _tiny_engine("explicit", {})
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        eng.prefill(prompts)
    assert eng.mode == "explicit"
    assert eng.health["retries"] == 0 and eng.health["fallbacks"] == 0


@pytest.mark.parametrize("fused", [False, True])
def test_scheduler_compile_error_propagates(mosaic_refusal, fused):
    from repro.serve.scheduler import Request, Scheduler

    eng, _ = _tiny_engine("explicit", dict(prefill_seq_buckets=(4,)))
    sched = Scheduler(eng, fused_prefill=fused)
    sched.submit(Request(rid=0, prompt=np.arange(1, 7, dtype=np.int32),
                         max_new_tokens=2))
    with pytest.raises(RuntimeError, match="Mosaic failed to compile"):
        sched.tick()
    assert sched.mode == "explicit"
    assert sched.plan_report()["health"]["fallbacks"] == 0
