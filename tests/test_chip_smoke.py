"""``chip_smoke.py``: no result line off a TPU, every check wired, and a
non-zero exit whenever serving fell back or degraded."""
import types

import pytest

import chip_smoke
from repro.core import faults


def _no_result_line(out: str) -> bool:
    return not any(line.lstrip().startswith('{"ok"')
                   for line in out.splitlines())


def test_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.run([]) != 0
    out = capsys.readouterr()
    assert _no_result_line(out.out)
    assert "no TPU" in out.err


def _sched(health, eng_mode="explicit", sched_mode="explicit"):
    full = {"retries": 0, "fallbacks": 0, "verify_failures": 0, **health}
    return types.SimpleNamespace(
        mode=sched_mode, eng=types.SimpleNamespace(mode=eng_mode),
        plan_report=lambda: {"health": full})


@pytest.mark.parametrize("health,eng_mode,sched_mode,n_bad", [
    ({}, "explicit", "explicit", 0),
    ({"fallbacks": 1}, "explicit", "explicit", 1),
    ({"retries": 2}, "explicit", "explicit", 1),
    ({"verify_failures": 1}, "explicit", "explicit", 1),
    ({}, "auto", "explicit", 1),
    ({}, "explicit", "auto", 1),
])
def test_serving_failures(health, eng_mode, sched_mode, n_bad):
    bad = chip_smoke.serving_failures(_sched(health, eng_mode, sched_mode),
                                      "explicit")
    assert len(bad) == n_bad


@pytest.mark.parametrize("chips", [1, 4])
def test_reduced_rehearsal_passes_every_check(capsys, chips):
    """The whole smoke at a tiny size on CPU devices: every phase and
    check runs and passes, and still no result line is printed."""
    assert chip_smoke.run(["--reduced", "--chips", str(chips)]) == 3
    out = capsys.readouterr().out
    assert "every check passed" in out and _no_result_line(out)


def test_fallback_fails_the_smoke(capsys):
    """A persistent step failure makes the explicit scheduler fall back
    to auto: the smoke counts it and exits non-zero."""
    with faults.inject(faults.FaultSpec("fail_call", count=1000)):
        assert chip_smoke.run(["--reduced", "--chips", "4"]) == 1
    out = capsys.readouterr()
    assert "[explicit] health[fallbacks]=1" in out.err
    assert _no_result_line(out.out)
