"""Seeded open-loop traffic from a mix's data file.

One general generator reads every mix under ``chipbench/traffic/``:

* ``arrival`` — ``{"process": "poisson", "rate_rps": r}``, or
  ``{"process": "phases", "rate_rps": r, "phases": [[seconds, factor],
  ...]}`` (the factors cycle: rate ``factor * r`` for that many
  seconds), or ``{"process": "backlog", "count": n}`` (every request
  due at the start of the pre-roll);
* ``prompt_len`` and ``output_len`` — ``{"dist": "lognormal",
  "median": m, "sigma": s, "min": lo, "max": hi}``;
* ``greedy_share`` and ``temperature`` — the share of requests decoded
  greedily, and the temperature of the rest;
* ``preroll_s`` — seconds of the same schedule served before the
  measured window opens.

Every seed gets the same work: the gaps between arrivals within a
phase, and the lengths, are stratified quantiles of their
distributions, laid out in one fixed well-mixed order (the golden-ratio
sequence), and the seed draws the token ids and each request's
sampling seed (and, in the harness, the weights). A window holds some
tens of requests, so a seed that reordered them would change the work
inside the window; it does not.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

__all__ = ["Req", "Schedule", "generate", "lengths", "arrival_gaps"]


@dataclasses.dataclass(frozen=True)
class Req:
    rid: int
    due_s: float            # seconds after the pre-roll starts
    prompt: np.ndarray      # (prompt_len,) int32
    max_new_tokens: int
    temperature: float      # 0 -> greedy
    seed: int               # the request's sampling seed


@dataclasses.dataclass(frozen=True)
class Schedule:
    reqs: List[Req]
    preroll_s: float
    window_s: float
    open_loop: bool

    @property
    def window(self) -> tuple:
        return self.preroll_s, self.preroll_s + self.window_s


def _stratified(n: int) -> np.ndarray:
    """n probabilities at the midpoints of n equal strata."""
    return (np.arange(n) + 0.5) / n


def mixed_order(n: int) -> np.ndarray:
    """A fixed permutation of range(n) that spreads neighbouring
    strata far apart: the ranks of ``k * golden ratio mod 1``."""
    return np.argsort((np.arange(n) * 0.6180339887498949) % 1.0,
                      kind="stable")


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths: the stratified quantiles of a lognormal with the
    given median and sigma, rounded and clamped to [min, max]."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(p) for p in _stratified(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def arrival_gaps(n: int, seconds: float) -> np.ndarray:
    """n gaps of a Poisson process that sum to ``seconds``: stratified
    exponential quantiles, scaled."""
    g = -np.log1p(-_stratified(n))
    return g * (seconds / g.sum())


def _due_times(arrival: dict, horizon: float) -> np.ndarray:
    proc = arrival["process"]
    if proc == "backlog":
        return np.zeros(int(arrival["count"]))
    if proc not in ("poisson", "phases"):
        raise ValueError(f"unknown arrival process {proc!r}")
    rate = float(arrival["rate_rps"])
    phases = ([[horizon, 1.0]] if proc == "poisson"
              else [list(p) for p in arrival["phases"]])
    out, t, k = [], 0.0, 0
    while t < horizon:
        dur, factor = phases[k % len(phases)]
        n = int(round(rate * factor * dur))
        if n:
            gaps = arrival_gaps(n, dur)[mixed_order(n)]
            # the gaps follow one another; the phase starts half a gap
            # before its first arrival
            out.extend(t + np.cumsum(gaps) - gaps[0] / 2)
        t += dur
        k += 1
    due = np.sort(np.asarray(out, float))
    return due[due < horizon]


def generate(mix: dict, *, seed: int, window_s: float, vocab: int,
             first_rid: int = 0) -> Schedule:
    """The cell's schedule: requests due over pre-roll plus window
    (a backlog is all due at 0), token ids from ``seed``, request ids
    from ``first_rid`` on."""
    rng = np.random.default_rng(seed)
    preroll = float(mix["preroll_s"])
    arrival = mix["arrival"]
    due = _due_times(arrival, preroll + window_s)
    n = len(due)
    order = mixed_order(n)
    plen = lengths(mix["prompt_len"], n)[order]
    # output lengths run through the strata in another phase, so a long
    # prompt is not always paired with a long answer
    olen = lengths(mix["output_len"], n)[np.roll(order, n // 3)]
    n_greedy = int(round(mix["greedy_share"] * n))
    greedy = (np.arange(n) < n_greedy)[np.roll(order, 2 * n // 3)]
    seeds = rng.integers(0, 2**31 - 1, n)
    reqs = [Req(rid=first_rid + i, due_s=float(due[i]),
                prompt=rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                max_new_tokens=int(olen[i]),
                temperature=0.0 if greedy[i] else float(mix["temperature"]),
                seed=int(seeds[i]))
            for i in range(n)]
    return Schedule(reqs=reqs, preroll_s=preroll, window_s=float(window_s),
                    open_loop=arrival["process"] != "backlog")
