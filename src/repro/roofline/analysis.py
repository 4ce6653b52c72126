"""Roofline term extraction from compiled XLA artifacts.

    compute    = HLO_FLOPs / (chips × peak)
    memory     = HLO_bytes / (chips × HBM_bw)
    collective = Σ(collective operand bytes × topology factor)
                 / (chips × link_bw)

``cost_analysis`` provides flops/bytes; collective bytes are parsed
from the optimized HLO text (they are NOT in cost_analysis): we sum
the output-shape bytes of every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute op, attributing each
to ICI or DCN by its replica-group span (groups that cross the 'pod'
axis ride DCN).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import numpy as np

from repro.roofline import hlo_parse

__all__ = ["HW", "HARDWARE", "hardware_for", "collective_bytes", "roofline",
           "RooflineReport"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float        # per chip
    hbm_bw: float            # B/s per chip
    ici_bw: float            # B/s per link
    ici_links: int           # usable links per chip on the mesh
    dcn_bw: float            # B/s per chip across pods


#: Per-chip peaks keyed by ``jax.Device.device_kind``. TPU v5e (JAX
#: reports it as "TPU v5 lite"): Google Cloud documentation, "TPU v5e" —
#: 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s chip-to-chip interconnect
#: (4 links of 50 GB/s); the 6.25 GB/s per-chip DCN share is this model's
#: assumption for the multi-pod dry-run, not a published figure.
HARDWARE = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                      ici_links=4, dcn_bw=6.25e9),
}


def hardware_for(device_kind: str) -> HW:
    """The peak table entry of a device kind. A kind not in the table is
    an error: a peak is never assumed for an unknown device."""
    try:
        return HARDWARE[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates recorded for device kind {device_kind!r}; "
            f"known kinds: {sorted(HARDWARE)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# e.g.  bf16[256,4096]{1,0}  or  (f32[8,128], u32[]) tuple shapes
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
    re.M)
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str, *, pod_boundary: Optional[int] = None
                     ) -> dict:
    """Sum collective op bytes from optimized HLO.

    Returns dict with per-op-type byte totals plus 'ici' / 'dcn' split.
    ``pod_boundary``: device-id threshold separating pods (e.g. 256 for
    a (2,16,16) mesh flattened); groups spanning it count as DCN.
    """
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "ici": 0, "dcn": 0}
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # started ops counted once at -start
        shape_str, op = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_str)
        out[op] += nbytes
        is_dcn = False
        if pod_boundary is not None:
            g = _GROUPS_RE.search(line)
            if g:
                for grp in g.group(1).split("},{"):
                    ids = [int(x) for x in re.findall(r"\d+", grp)]
                    if ids and (min(ids) < pod_boundary <= max(ids)):
                        is_dcn = True
                        break
            elif op == "collective-permute":
                pairs = re.findall(r"\{(\d+),(\d+)\}", line)
                is_dcn = any((int(a) < pod_boundary) != (int(b) < pod_boundary)
                             for a, b in pairs)
        out["dcn" if is_dcn else "ici"] += nbytes
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    cell: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_ici_bytes: float
    coll_dcn_bytes: float
    model_flops: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0

    @property
    def dominant(self) -> str:
        terms = dict(compute=self.compute_s, memory=self.memory_s,
                     collective=self.collective_s)
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flop_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """compute_term / max(all terms): 1.0 = perfectly compute-bound."""
        return self.compute_s / self.bound_s if self.bound_s else 0.0

    def row(self) -> str:
        return (f"| {self.arch} | {self.cell} | {self.mesh} "
                f"| {self.compute_s*1e3:.2f} | {self.memory_s*1e3:.2f} "
                f"| {self.collective_s*1e3:.2f} | {self.dominant} "
                f"| {self.useful_flop_ratio:.2f} "
                f"| {self.roofline_fraction:.2f} |")


def roofline(*, arch: str, cell: str, mesh_name: str, chips: int,
             cost: dict, hlo_text: str, model_flops: float,
             hw: HW, pod_boundary: Optional[int] = None
             ) -> RooflineReport:
    """All three terms from the trip-count-aware HLO analyzer
    (``cost_analysis`` under-counts while bodies — DESIGN.md §8);
    the raw cost dict is retained by the caller for cross-checking."""
    st = hlo_parse.analyze(hlo_text, pod_boundary=pod_boundary)
    rep = RooflineReport(
        arch=arch, cell=cell, mesh=mesh_name, chips=chips,
        hlo_flops=st.flops, hlo_bytes=st.traffic_bytes,
        coll_ici_bytes=float(st.coll["ici"]),
        coll_dcn_bytes=float(st.coll["dcn"]),
        model_flops=model_flops)
    # HLO here is the per-device SPMD program: terms are per-chip seconds
    rep.compute_s = st.flops / hw.peak_flops
    rep.memory_s = st.traffic_bytes / hw.hbm_bw
    rep.collective_s = (st.coll["ici"] / (hw.ici_bw * hw.ici_links)
                        + st.coll["dcn"] / hw.dcn_bw)
    return rep
