"""The comparison that decides ``correct`` separates sound runs from
broken ones. At a small size on the CPU, through the run's own path
(``run.run_cell``): with ``control`` the fp8 control's tokens (the
reference one precision step below the served bf16) stand in for the
served ones and come out not correct on three seeds, while the
program's own tokens come out correct; and a run whose timed path is
broken underneath (a served token altered where it is sampled, a step
that hands back its cache unchanged) comes out not correct."""
import pathlib
import sys

import jax
import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from small import PEAKS, SMALL_LIMIT, small_cell  # noqa: E402


def _run(seed, seconds=1.0, control=False, name="qwen3-1.7b.chat"):
    res, code = run.run_cell(small_cell(name), seed=seed,
                             seconds=seconds, trace=False,
                             devices=jax.devices()[:1], peaks=PEAKS,
                             control=control)
    assert code == 0
    return res


@pytest.mark.parametrize("name", ["qwen3-1.7b.chat"])
def test_control_fails_where_the_program_passes(name):
    for seed in (1, 2, 3):
        sound = _run(seed, seconds=3.0, name=name)
        assert sound["correct"], sound["checks"]
        ctl = _run(seed, seconds=3.0, control=True, name=name)
        chk = ctl["checks"]["control_logit_gap"]
        assert not ctl["correct"] and chk["value"] > chk["limit"] == SMALL_LIMIT


def test_altered_token_is_caught(monkeypatch):
    from repro.serve.scheduler import Scheduler

    orig = Scheduler._sample_row

    def altered(self, slot, row):
        tok = orig(self, slot, row)
        if slot.req.temperature == 0 and slot.emitted == 1:
            tok = int(np.argsort(row)[len(row) // 2])   # a middling token
        return tok

    monkeypatch.setattr(Scheduler, "_sample_row", altered)
    res = _run(2**31 + 23)
    assert not res["correct"]
    assert res["checks"]["served_logit_gap"]["value"] > SMALL_LIMIT


@pytest.mark.parametrize("name", ["qwen3-1.7b.chat"])
def test_step_returning_its_state_unchanged_is_caught(monkeypatch, name):
    from repro.distributed import step as step_mod

    orig = step_mod.make_sched_step

    def unchanged(*a, **k):
        fn, spec = orig(*a, **k)

        def step(params, cache, tokens, pos, active):
            logits, _ = fn(params, cache, tokens, pos, active)
            return logits, cache
        return step, spec

    monkeypatch.setattr(step_mod, "make_sched_step", unchanged)
    res = _run(2**31 + 23, name=name)
    assert not res["correct"]
