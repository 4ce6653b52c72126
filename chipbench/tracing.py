"""Reduction of a profiler trace to device busy time, per-program
device time, and idle gaps named by the harness's own host spans.

A trace is read with ``jax.profiler.ProfileData`` and kept as plain
tuples, so the reductions below are pure functions that tests drive
with synthetic events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Trace", "load", "union_seconds", "idle_gaps", "attribute_gaps",
           "top_ops", "label_modules", "HOST_SPANS"]

#: the harness's own host spans (``jax.profiler.TraceAnnotation``)
HOST_SPANS = ("tick", "submit", "wait_arrival")

Interval = Tuple[float, float, str]      # (start s, end s, name)


@dataclasses.dataclass
class Trace:
    ops: List[Interval]          # device operations, one chip
    modules: List[Interval]      # device program executions, one chip
    spans: List[Interval]        # harness host spans
    n_devices: int
    summary: str = ""            # planes, lines and event counts read


def load(trace_dir: str, device_prefix: str = "/device:TPU:") -> Trace:
    """The newest ``.xplane.pb`` under ``trace_dir``. Device planes are
    those whose name starts with ``device_prefix``; the first one with
    operations is read (one chip per cell)."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops: List[Interval] = []
    modules: List[Interval] = []
    spans: List[Interval] = []
    n_dev = 0
    seen = []
    for plane in pd.planes:
        seen.append(f"{plane.name}: " + ", ".join(
            f"{line.name} ({sum(1 for _ in line.events)})"
            for line in plane.lines))
        if plane.name.startswith(device_prefix):
            n_dev += 1
            if ops:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [_iv(e) for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [_iv(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(_iv(e) for e in line.events
                             if e.name in HOST_SPANS)
    return Trace(ops=sorted(ops), modules=sorted(modules),
                 spans=sorted(spans), n_devices=n_dev,
                 summary="; ".join(seen)[:2000])


def _iv(e) -> Interval:
    s = e.start_ns * 1e-9
    return (s, s + e.duration_ns * 1e-9, e.name)


def _merged(intervals: Sequence[Interval], lo: float, hi: float):
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_seconds(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] covered by at least one interval."""
    return sum(e - s for s, e in _merged(intervals, lo, hi))


def idle_gaps(intervals: Sequence[Interval], lo: float, hi: float):
    """The stretches of [lo, hi] that no interval covers."""
    gaps, t = [], lo
    for s, e in _merged(intervals, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute_gaps(gaps, spans: Sequence[Interval]) -> Dict[str, float]:
    """Idle seconds by the host span that covers each gap's midpoint
    (the innermost, if spans nest); ``other`` where none does."""
    out: Dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        cover = [sp for sp in spans if sp[0] <= mid < sp[1]]
        name = min(cover, key=lambda sp: sp[1] - sp[0])[2] if cover else "other"
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def top_ops(ops: Sequence[Interval], lo: float, hi: float, k: int = 10):
    """[[name, seconds], ...] of the k operations with most device time
    inside [lo, hi]."""
    tot: Dict[str, float] = {}
    for s, e, name in ops:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            tot[name] = tot.get(name, 0.0) + d
    return [[n, t] for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def label_modules(modules: Sequence[Interval], lo: float, hi: float,
                  expected: Sequence[str], prefix: str
                  ) -> Optional[List[Tuple[str, float]]]:
    """Pair each execution of the step programs (module names starting
    with ``prefix``) inside [lo, hi] with the kind the host issued in
    that order (``expected``). The device runs one program at a time in
    issue order, so the n-th execution is the n-th issued step. None
    when the counts disagree: then nothing is attributed."""
    slack = 1e-3      # host and device clocks agree to a few microseconds
    runs = [(s, e) for s, e, n in modules
            if n.startswith(prefix) and s >= lo - slack and e <= hi + slack]
    if len(runs) != len(expected):
        return None
    return [(kind, e - s) for kind, (s, e) in zip(expected, runs)]
