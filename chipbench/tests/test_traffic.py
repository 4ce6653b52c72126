"""The traffic generator: seeded, the same work for every seed, clamps
and medians as the mix files state, the burst process's mean rate, the
backlog's count, and TTFT percentiles that count censored requests."""
import json
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import harness as hz  # noqa: E402
import traffic  # noqa: E402

MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = traffic.generate(_mix(name), seed=2**31 + 99, window_s=20, vocab=1000)
    b = traffic.generate(_mix(name), seed=2**31 + 99, window_s=20, vocab=1000)
    assert [r.due_s for r in a.reqs] == [r.due_s for r in b.reqs]
    assert all(np.array_equal(x.prompt, y.prompt) and
               x.max_new_tokens == y.max_new_tokens and x.seed == y.seed
               for x, y in zip(a.reqs, b.reqs))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_give_the_same_work(name):
    a = traffic.generate(_mix(name), seed=1, window_s=20, vocab=1000)
    b = traffic.generate(_mix(name), seed=2, window_s=20, vocab=1000)
    assert [r.due_s for r in a.reqs] == [r.due_s for r in b.reqs]
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens,
                lambda r: r.temperature):
        assert list(map(key, a.reqs)) == list(map(key, b.reqs))
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.reqs, b.reqs))


def test_mixed_order_spreads_lengths():
    order = traffic.mixed_order(100)
    assert sorted(order) == list(range(100))
    # every run of 10 consecutive requests holds strata from each half
    for i in range(0, 100, 10):
        chunk = order[i:i + 10]
        assert (chunk < 50).any() and (chunk >= 50).any()


@pytest.mark.parametrize("name", MIXES)
def test_clamps_and_medians(name):
    mix = _mix(name)
    s = traffic.generate(mix, seed=7, window_s=40, vocab=1000)
    for key, spec in (("prompt", mix["prompt_len"]),
                      ("out", mix["output_len"])):
        vals = np.array([len(r.prompt) if key == "prompt" else
                         r.max_new_tokens for r in s.reqs])
        assert vals.min() >= spec["min"] and vals.max() <= spec["max"]
        assert abs(np.median(vals) - spec["median"]) <= 0.05 * spec["median"] + 1
    greedy = np.mean([r.temperature == 0 for r in s.reqs])
    assert abs(greedy - mix["greedy_share"]) <= 1 / len(s.reqs) + 1e-9
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in s.reqs)


def test_lengths_are_stratified_quantiles():
    spec = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 1,
            "max": 10**6}
    v = traffic.lengths(spec, 1001)
    assert v[500] == 100 and np.all(np.diff(v) >= 0)


def test_poisson_rate_and_gaps():
    mix = {"arrival": {"process": "poisson", "rate_rps": 3.0},
           "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.1,
                          "min": 1, "max": 20},
           "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.1,
                          "min": 1, "max": 20},
           "greedy_share": 0.5, "temperature": 1.0, "preroll_s": 10}
    s = traffic.generate(mix, seed=3, window_s=90, vocab=50)
    assert len(s.reqs) == 300
    due = np.array([r.due_s for r in s.reqs])
    assert np.all(np.diff(due) >= 0) and due[0] >= 0 and due[-1] < 100
    gaps = np.diff(due)
    # exponential gaps: the coefficient of variation is about 1
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_phases_mean_rate():
    # bursts: 5 s at 1.6 r, then 5 s at 0.4 r, with chat's lengths
    mix = dict(_mix("chat"), arrival={"process": "phases", "rate_rps": 1.05,
                                      "phases": [[5, 1.6], [5, 0.4]]})
    r = mix["arrival"]["rate_rps"]
    phases = mix["arrival"]["phases"]
    period = sum(d for d, _ in phases)
    n_periods = 6
    s = traffic.generate(dict(mix, preroll_s=0), seed=5,
                         window_s=n_periods * period, vocab=50)
    mean_factor = sum(d * f for d, f in phases) / period
    assert len(s.reqs) == pytest.approx(r * mean_factor * n_periods * period,
                                        abs=n_periods)
    due = np.array([x.due_s for x in s.reqs])
    hi = np.sum((due % period) < phases[0][0])
    lo = len(due) - hi
    assert hi / lo == pytest.approx(
        phases[0][0] * phases[0][1] / (phases[1][0] * phases[1][1]), rel=0.05)


def test_backlog_count():
    mix = _mix("offline")
    s = traffic.generate(mix, seed=11, window_s=40, vocab=50)
    assert len(s.reqs) == mix["arrival"]["count"]
    assert all(r.due_s == 0 for r in s.reqs) and not s.open_loop


def test_ttft_counts_censored_requests_at_window_end():
    mix = {"arrival": {"process": "poisson", "rate_rps": 1.0},
           "prompt_len": {"dist": "lognormal", "median": 4, "sigma": 0.1,
                          "min": 1, "max": 8},
           "output_len": {"dist": "lognormal", "median": 4, "sigma": 0.1,
                          "min": 1, "max": 8},
           "greedy_share": 1.0, "temperature": 1.0, "preroll_s": 0}
    s = traffic.generate(mix, seed=0, window_s=10, vocab=50)
    rec = hz.Records()
    served = s.reqs[:5]
    for r in served:                     # first token 0.1 s after due
        rec.emit[r.rid] = [r.due_s + 0.1, r.due_s + 0.2]
    w = hz.window_metrics(s, rec, origin=0.0, w0=0.0, w1=10.0)
    assert w["due"] == 10 and w["censored"] == 5
    expect = [0.1] * 5 + [10.0 - r.due_s for r in s.reqs[5:]]
    assert sorted(w["ttft"]) == pytest.approx(sorted(expect))
    assert w["gaps"] == pytest.approx([0.1] * 5)
