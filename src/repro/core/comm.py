"""Communicator + ExecutionPlan — compile once, execute many.

The paper's production story (§4.4, §5.2) is not "call a function":
channels, algorithm choice, and optimized programs are set up ONCE and
amortized over millions of invocations (every decode step of a serving
engine re-runs the same AllReduce). This module is that separation:

* :class:`Communicator` — owns an axis, its :class:`~.selector.LinkModel`,
  an optional :class:`~.selector.TuningTable`, default backend /
  ``opt_level``, and a **plan cache** keyed by
  ``(collective, shape, dtype, n, backend, algo, opt_level, link[, root])``.
* :class:`ExecutionPlan` — a frozen artifact bundling the
  post-optimizer :class:`~.dsl.Program`, the chosen algorithm, the
  prepared executor lowering (``XlaExecutor.prepare`` /
  ``PallasExecutor.prepare``), pad/reshape metadata, and its
  ``estimate_us`` / ``comm_stats`` cost card. Plans are inspectable
  (``cost_card()``) and serializable (``to_json`` / ``from_json``) à la
  MSCCL++ execution-plan files.
* :class:`BucketedPlan` — one plan per row-count bucket, padded at
  dispatch with a per-family padding strategy (``_BUCKET_PAD``): tail
  rows for the row-preserving collectives, per-rank-block slots for
  the row-redistributing ones (all_to_all / reduce_scatter — the MoE
  capacity-bucket case). Serializes like ``ExecutionPlan``.

``comm.compile("all_reduce", shape, dtype)`` returns a plan; calling
``plan(x)`` (or ``comm.all_reduce(x)``, which compiles-or-hits-cache)
executes it with zero re-planning inside traced code: the ``passes``
pipeline, the selector, and executor lowering-plan construction all run
exactly once per cache key.

The module-level functions in :mod:`repro.core.api` are thin wrappers
over per-axis process-default communicators (:func:`default_communicator`),
preserving the drop-in NCCL-shaped surface.

The full call-to-replay walkthrough (cache key fields, padding rules,
the serving hot path) is ``docs/plan-lifecycle.md``.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithms as algos
from repro.core import passes
from repro.core import selector as sel
from repro.core import verify as verify_mod
from repro.core.dsl import Program, program_from_dict, program_to_dict
from repro.core.executor import PallasExecutor, XlaExecutor

__all__ = [
    "Communicator", "ExecutionPlan", "BucketedPlan",
    "HierarchicalCommunicator", "HierarchicalPlan",
    "default_communicator", "default_backend",
    "reset_default_communicators", "hierarchical_all_reduce",
    "plan_from_json", "export_plan_set", "load_plan_set",
    "PLAN_FORMAT_VERSION",
]

PLAN_FORMAT_VERSION = 1


def _check_version(d: dict, what: str) -> None:
    """Schema-version gate for plan payloads. Plans are written with
    both ``version`` (the schema field) and ``format`` (its pre-PR-6
    name) so either generation of reader accepts them."""
    if d.get("version") is None and d.get("format") is None:
        raise ValueError(
            f"{what} payload has no schema 'version' field "
            f"(keys: {sorted(d)[:8]}): not a plan file written by "
            f"to_json(), or truncated")
    for k in ("version", "format"):
        v = d.get(k)
        if v is not None and v != PLAN_FORMAT_VERSION:
            raise ValueError(
                f"unsupported plan format version {v!r} (field {k!r}); "
                f"this build reads version {PLAN_FORMAT_VERSION} — "
                f"re-export the plan with to_json()")


def _field(d: dict, key: str, what: str):
    """Required-field access with an actionable error instead of the
    raw KeyError a truncated/hand-edited plan file used to raise."""
    try:
        return d[key]
    except KeyError:
        raise ValueError(
            f"{what} payload missing required field {key!r} "
            f"(has {sorted(d)}): the plan file is truncated or "
            f"corrupted") from None

_COLLECTIVE_IDS = {  # stable barrier-semaphore ids per collective type
    "all_reduce": 8, "all_gather": 9, "reduce_scatter": 10,
    "all_to_all": 11, "broadcast": 12,
}

#: collectives whose output keeps the caller's row count, so rows that
#: don't divide the chunk grid can be padded and sliced back. The others
#: embed the chunk grid in their output layout and instead fall back to
#: an un-split pipeline level (and reject non-divisible rows outright).
_PADDABLE = frozenset({"all_reduce", "broadcast"})

#: Per-family padding strategy for ``plan_for(..., buckets=)`` — how a
#: payload smaller than the compiled bucket is padded at dispatch and
#: where the padding is sliced back out:
#:
#: * ``"rows"``   — row-preserving collectives (all_reduce, broadcast):
#:   zero rows are appended to the payload tail and sliced off the
#:   output tail; padding rows cancel exactly (zero stays zero under
#:   sum / select).
#: * ``"tiled"``  — all_gather: input rows pad at the tail, but the
#:   tiled output interleaves every rank's block, so the padding is
#:   sliced out of each per-rank block of the gathered result.
#: * ``"blocks"`` — row-REDISTRIBUTING collectives (all_to_all,
#:   reduce_scatter), whose (n*rows, cols) input embeds the per-rank
#:   row distribution as n row blocks: buckets count rows PER BLOCK,
#:   and each of the n blocks pads independently to the bucket so the
#:   block boundaries the algorithm routes on stay aligned. all_to_all
#:   slices the padding out of every received block; reduce_scatter's
#:   padded rows reduce to zero and slice off the output tail. This is
#:   the MoE expert-parallel case: the bucket is the per-rank token
#:   CAPACITY of the dispatch/combine all_to_all.
_BUCKET_PAD = {
    "all_reduce": "rows",
    "broadcast": "rows",
    "all_gather": "tiled",
    "all_to_all": "blocks",
    "reduce_scatter": "blocks",
}


def default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve_algo(collective: str, n: int, nbytes: int,
                  algo: Optional[str], link: sel.LinkModel,
                  table: Optional[sel.TuningTable],
                  opt_level: Optional[int]) -> str:
    """Explicit ``algo`` (validated against the candidate set) or the
    selector's pick — costed at the opt level that will actually run."""
    cands = sel.CANDIDATES[collective]
    if algo is not None:
        if algo not in cands:
            raise ValueError(
                f"unknown algorithm {algo!r} for {collective!r}; "
                f"expected one of {cands}")
        if not sel.supports(algo, n):
            raise ValueError(
                f"algorithm {algo!r} does not support n={n} ranks; "
                f"candidates supported at this geometry: "
                f"{[a for a in cands if sel.supports(a, n)]}")
        return algo
    return sel.choose(collective, n=n, nbytes=nbytes, link=link,
                      table=table, opt_level=opt_level)


def _build_executor(program: Program, axis: str, collective: str,
                    backend: str, opt_level: int, n: int):
    if backend == "pallas":
        return PallasExecutor(
            program, axis,
            collective_id=_COLLECTIVE_IDS[collective]).prepare(n)
    return XlaExecutor(program, axis, vectorize=opt_level > 0).prepare(n)


@dataclasses.dataclass(frozen=True, eq=False, repr=False)
class ExecutionPlan:
    """A compiled, frozen, executable collective (see module docstring).

    ``opt_level`` is the level actually applied (it can fall below
    ``requested_opt_level`` when chunk-split would not divide the
    caller's rows); ``pad`` is the number of padding rows applied before
    execution and sliced off after (paddable collectives only).
    """

    collective: str
    algo: str
    axis: str
    n: int
    shape: Tuple[int, int]
    dtype: str
    backend: str
    opt_level: int
    requested_opt_level: int
    root: Optional[int]
    pad: int
    link: sel.LinkModel
    estimate_us: float
    comm_stats: Dict[str, int]
    program: Program
    executor: Any
    #: when True (``Communicator(trace=True)``), every execution records
    #: a per-instruction timeline (``repro.core.trace``), surfaced as
    #: :attr:`last_trace`. Off by default: the untraced replay path is
    #: byte-identical with the flag off (jaxpr-asserted in tests).
    trace: bool = False
    _trace_box: list = dataclasses.field(default_factory=list, repr=False)

    # -- execution ---------------------------------------------------------
    def __call__(self, x: jax.Array) -> jax.Array:
        """Execute on a local shard inside shard_map. Pure replay: no
        selection, no passes, no lowering-plan construction."""
        if tuple(x.shape) != tuple(self.shape):
            raise ValueError(
                f"plan compiled for shape {self.shape}, got {tuple(x.shape)}")
        if np.dtype(x.dtype) != np.dtype(self.dtype):
            raise ValueError(
                f"plan compiled for dtype {self.dtype}, got {x.dtype}")
        if self.trace:
            # capture runs host-side at trace time and adds ZERO
            # instructions to the traced program (the emulation never
            # touches x)
            self.capture_trace()
        if self.pad:
            x = jnp.pad(x, ((0, self.pad), (0, 0)))
        out = self.executor(x)
        if self.pad:
            out = out[: self.shape[0]]
        return out

    # -- profiling ---------------------------------------------------------
    def capture_trace(self):
        """Record (and return) a per-instruction timeline of this plan
        via timed host emulation — no mesh required; see
        :mod:`repro.core.trace`."""
        from repro.core import trace as trace_mod
        tr = trace_mod.capture_plan(self)
        self._trace_box[:] = [tr]
        return tr

    @property
    def last_trace(self):
        """The most recent :class:`~.trace.Trace` this plan recorded
        (``None`` until a traced execution or :meth:`capture_trace`)."""
        return self._trace_box[-1] if self._trace_box else None

    # -- inspection --------------------------------------------------------
    def cost_card(self) -> dict:
        """The plan's analytic cost summary (the selector's view)."""
        return dict(collective=self.collective, algo=self.algo, n=self.n,
                    shape=tuple(self.shape), dtype=self.dtype,
                    backend=self.backend, opt_level=self.opt_level,
                    estimate_us=round(self.estimate_us, 3),
                    **self.comm_stats)

    def __repr__(self):
        return (f"ExecutionPlan({self.collective}/{self.algo} n={self.n} "
                f"shape={tuple(self.shape)} dtype={self.dtype} "
                f"backend={self.backend} O{self.opt_level} "
                f"est={self.estimate_us:.2f}us)")

    # -- serialization -----------------------------------------------------
    def to_dict(self) -> dict:
        """The plan as a JSON-compatible dict (program included) — the
        unit :meth:`to_json` wraps and :class:`BucketedPlan` nests."""
        return dict(
            version=PLAN_FORMAT_VERSION, format=PLAN_FORMAT_VERSION,
            collective=self.collective, algo=self.algo, axis=self.axis,
            n=self.n, shape=list(self.shape), dtype=self.dtype,
            backend=self.backend, opt_level=self.opt_level,
            requested_opt_level=self.requested_opt_level,
            root=self.root, pad=self.pad,
            link=dict(alpha_us=self.link.alpha_us,
                      beta_GBps=self.link.beta_GBps,
                      torus=self.link.torus, sync_us=self.link.sync_us),
            estimate_us=self.estimate_us,
            comm_stats=dict(self.comm_stats),
            program=program_to_dict(self.program),
        )

    def to_json(self, **json_kw) -> str:
        """Serialize the whole plan (program included) to JSON — the
        MSCCL++ execution-plan-file shape: portable, diffable,
        loadable without re-running selection or the pass pipeline."""
        json_kw.setdefault("indent", 2)
        json_kw.setdefault("sort_keys", True)
        return json.dumps(self.to_dict(), **json_kw)

    @classmethod
    def from_dict(cls, d: dict, *, verify: str = "strict") -> "ExecutionPlan":
        """Rebuild a plan from :meth:`to_dict` output: the program is
        reconstructed, **verified** (loaded plan files are validated,
        not trusted — ``verify='off'|'warn'|'strict'``), and the
        executor lowering re-prepared; no selection and no
        pass-pipeline work re-runs."""
        _check_version(d, "ExecutionPlan")
        if d.get("kind") == "bucketed_plan":
            raise ValueError(
                "bucketed plan payload; use BucketedPlan.from_json")
        req = lambda k: _field(d, k, "ExecutionPlan")  # noqa: E731
        program = program_from_dict(req("program"))
        collective, n = req("collective"), req("n")
        root = req("root")
        verify_mod.check(program, n, mode=verify, collective=collective,
                         root=0 if root is None else root)
        try:
            link = sel.LinkModel(**req("link"))
        except TypeError as e:
            raise ValueError(
                f"ExecutionPlan payload has a malformed 'link' field "
                f"({e}): expected LinkModel keys") from None
        executor = _build_executor(program, req("axis"), collective,
                                   req("backend"), req("opt_level"), n)
        return cls(
            collective=collective, algo=req("algo"), axis=req("axis"),
            n=n, shape=tuple(req("shape")), dtype=req("dtype"),
            backend=req("backend"), opt_level=req("opt_level"),
            requested_opt_level=req("requested_opt_level"),
            root=root, pad=req("pad"),
            link=link,
            estimate_us=req("estimate_us"),
            comm_stats=dict(req("comm_stats")),
            program=program, executor=executor)

    @classmethod
    def from_json(cls, s: str, *, verify: str = "strict") -> "ExecutionPlan":
        return cls.from_dict(json.loads(s), verify=verify)


@dataclasses.dataclass(eq=False, repr=False)
class BucketedPlan:
    """A family of :class:`ExecutionPlan` s over row-count buckets —
    compile per bucket, pad at dispatch.

    The continuous-batching shape problem (ROADMAP): a serving stack
    whose active-slot count varies would otherwise compile one plan per
    distinct row count. ``plan_for(shape, buckets=...)`` compiles ONE
    plan per bucket size; ``__call__`` routes a payload to the smallest
    bucket that fits, zero-pads the missing rows, replays that bucket's
    plan, and slices the result back — so any slot count in range
    replays one of a handful of frozen plans. ``hits`` counts dispatches
    per bucket (incremented at trace time: one count per traced step,
    the compile-side analogue of the plan cache's hit counter).

    What a *bucket* counts, and where padding goes, depends on the
    family's padding strategy (``pad_strategy``, see ``_BUCKET_PAD``):

    * ``"rows"`` / ``"tiled"`` (row-preserving): buckets count payload
      rows; pad the tail, slice the output tail (rows) or each per-rank
      output block (tiled all_gather).
    * ``"blocks"`` (row-redistributing: all_to_all, reduce_scatter):
      the payload is ``(n * rows, cols)`` — n per-rank row blocks —
      and buckets count rows PER BLOCK. Each block pads independently
      to the bucket (keeping block boundaries aligned with the routing)
      and the padding is sliced out of every received block
      (all_to_all) or off the reduced output tail (reduce_scatter).
      For MoE expert parallelism the bucket is the per-rank token
      capacity of the dispatch/combine all_to_all.

    Example — an MoE dispatch all_to_all bucketed over capacities::

        bp = comm.plan_for("all_to_all", (n * cap, d_model), jnp.float32,
                           buckets=(8, 16, 32))     # per-rank capacities
        recv = bp(dispatch_buffer)    # pads each block to the bucket,
                                      # replays that bucket's plan
    """

    collective: str
    axis: str
    n: int
    cols: int
    dtype: str
    buckets: Tuple[int, ...]             # ascending row (or block-row) counts
    plans: Dict[int, ExecutionPlan]      # bucket rows -> plan
    hits: Dict[int, int]
    pad_strategy: str = "rows"           # 'rows' | 'tiled' | 'blocks'

    # -- dispatch ----------------------------------------------------------
    def bucket_for(self, rows: int) -> int:
        """Smallest bucket that fits ``rows`` (payload rows for the
        row-preserving strategies, per-rank block rows for 'blocks')."""
        for b in self.buckets:
            if rows <= b:
                return b
        unit = ("rows per per-rank block" if self.pad_strategy == "blocks"
                else "payload rows")
        raise ValueError(
            f"{self.collective} payload of {rows} {unit} exceeds the "
            f"largest bucket of {self!r}: buckets cover "
            f"{list(self.buckets)} {unit}. Recompile the family with a "
            f"bucket that fits — plan_for(..., buckets=(*"
            f"{list(self.buckets)}, {rows})) — or shrink the payload to "
            f"<= {self.buckets[-1]} {unit}")

    def plan_for_rows(self, rows: int) -> ExecutionPlan:
        """The frozen :class:`ExecutionPlan` that would serve a payload
        of ``rows`` rows (per-block rows under the 'blocks' strategy) —
        the bucket's plan, without executing it. Use it to inspect the
        cost card a given occupancy replays::

            bp.plan_for_rows(3).cost_card()   # the 4-bucket's card
        """
        return self.plans[self.bucket_for(rows)]

    def __call__(self, x: jax.Array) -> jax.Array:
        """Execute on a local shard inside shard_map: pad to the bucket
        (per the family's padding strategy), replay its plan, slice the
        result back to the caller's rows."""
        if self.pad_strategy == "blocks":
            return self._call_blocks(x)
        rows = int(x.shape[0])
        b = self.bucket_for(rows)
        self.hits[b] += 1
        plan = self.plans[b]
        if rows == b:
            return plan(x)
        out = plan(jnp.pad(x, ((0, b - rows), (0, 0))))
        if self.pad_strategy == "tiled":
            # tiled output: slice the padding out of every rank's block
            return out.reshape(self.n, b, -1)[:, :rows].reshape(
                self.n * rows, out.shape[1])
        return out[:rows]

    def _call_blocks(self, x: jax.Array) -> jax.Array:
        """Dispatch for the row-redistributing families: ``x`` is
        ``(n * rows, cols)``; pad each of the n per-rank blocks to the
        bucket so the block layout the algorithm routes on is
        preserved, then slice the padding back out of the result."""
        total, cols = int(x.shape[0]), int(x.shape[1])
        if total % self.n != 0:
            raise ValueError(
                f"{self.collective} payload rows={total} not divisible "
                f"by the {self.n} per-rank blocks of {self!r}")
        rows = total // self.n
        b = self.bucket_for(rows)
        self.hits[b] += 1
        plan = self.plans[b]
        if rows == b:
            return plan(x)
        xp = jnp.pad(x.reshape(self.n, rows, cols),
                     ((0, 0), (0, b - rows), (0, 0)))
        out = plan(xp.reshape(self.n * b, cols))
        if self.collective == "reduce_scatter":
            # (b, cols) reduced block: padded rows summed zeros, slice off
            return out[:rows]
        # all_to_all: (n*b, cols) — slice the padding out of every
        # received block
        return out.reshape(self.n, b, cols)[:, :rows].reshape(
            self.n * rows, cols)

    # -- inspection --------------------------------------------------------
    @property
    def last_trace(self):
        """The largest bucket's most recent recorded trace (the full-
        occupancy timeline; ``None`` until a traced execution)."""
        return self.plans[self.buckets[-1]].last_trace

    def last_traces(self) -> Dict[int, Any]:
        """Per-bucket most recent recorded traces (bucket -> Trace|None)."""
        return {b: self.plans[b].last_trace for b in self.buckets}

    def cost_cards(self) -> Dict[int, dict]:
        """Per-bucket cost cards (bucket rows -> card)."""
        return {b: self.plans[b].cost_card() for b in self.buckets}

    def report(self) -> dict:
        """Cost cards + dispatch hit counts — the serving-side view."""
        return dict(collective=self.collective, buckets=list(self.buckets),
                    pad_strategy=self.pad_strategy,
                    cards=self.cost_cards(), hits=dict(self.hits))

    def __repr__(self):
        return (f"BucketedPlan({self.collective}/{self.pad_strategy} "
                f"n={self.n} cols={self.cols} dtype={self.dtype} "
                f"buckets={list(self.buckets)} hits={dict(self.hits)})")

    # -- serialization -----------------------------------------------------
    def to_json(self, **json_kw) -> str:
        """Serialize the whole bucket family — per-bucket plans included
        — to JSON, parity with :meth:`ExecutionPlan.to_json` (the
        MSCCL++ plan-file shape, one file per bucketed collective).
        Dispatch hit counters are metadata and round-trip too."""
        json_kw.setdefault("indent", 2)
        json_kw.setdefault("sort_keys", True)
        return json.dumps(dict(
            version=PLAN_FORMAT_VERSION, format=PLAN_FORMAT_VERSION,
            kind="bucketed_plan",
            collective=self.collective, axis=self.axis, n=self.n,
            cols=self.cols, dtype=self.dtype,
            buckets=list(self.buckets), pad_strategy=self.pad_strategy,
            hits={str(b): h for b, h in self.hits.items()},
            plans={str(b): self.plans[b].to_dict() for b in self.buckets},
        ), **json_kw)

    @classmethod
    def from_json(cls, s: str, *, verify: str = "strict") -> "BucketedPlan":
        """Rebuild a bucket family; every per-bucket program is
        verified on load (``verify='off'|'warn'|'strict'``)."""
        d = json.loads(s)
        _check_version(d, "BucketedPlan")
        if d.get("kind") != "bucketed_plan":
            raise ValueError(
                f"not a bucketed plan payload (kind={d.get('kind')!r}); "
                f"use ExecutionPlan.from_json for single plans")
        if d.get("pad_strategy") not in ("rows", "tiled", "blocks"):
            raise ValueError(
                f"unknown pad_strategy {d.get('pad_strategy')!r}; "
                f"expected one of 'rows', 'tiled', 'blocks'")
        req = lambda k: _field(d, k, "BucketedPlan")  # noqa: E731
        buckets = tuple(int(b) for b in req("buckets"))
        payload_plans = req("plans")
        missing = [b for b in buckets if str(b) not in payload_plans]
        if missing:
            raise ValueError(f"bucketed plan payload missing buckets "
                             f"{missing} (has {sorted(payload_plans)})")
        plans = {b: ExecutionPlan.from_dict(payload_plans[str(b)],
                                            verify=verify)
                 for b in buckets}
        return cls(
            collective=req("collective"), axis=req("axis"), n=req("n"),
            cols=req("cols"), dtype=req("dtype"), buckets=buckets,
            plans=plans,
            hits={b: int(d.get("hits", {}).get(str(b), 0)) for b in buckets},
            pad_strategy=d["pad_strategy"])


class Communicator:
    """Init-once planning object for one mesh axis (see module docstring).

    ``n`` (the axis size) may be given up front — required for
    compiling plans *outside* traced code (e.g. at engine init). When
    omitted it is resolved per call from the live axis environment
    (inside shard_map), so one default communicator serves meshes of
    any size on the same axis name.
    """

    def __init__(self, axis: str, *, n: Optional[int] = None,
                 link: sel.LinkModel = sel.ICI,
                 table: Optional[sel.TuningTable] = None,
                 backend: Optional[str] = None,
                 opt_level: Optional[int] = None,
                 verify: str = "strict",
                 trace: bool = False):
        if verify not in verify_mod.MODES:
            raise ValueError(
                f"verify must be one of {verify_mod.MODES}, got {verify!r}")
        self.axis = axis
        self.n = n
        self.link = link
        self.table = table
        self.backend = backend
        self.opt_level = opt_level
        self.verify = verify
        #: record a per-instruction timeline on every plan execution
        #: (``ExecutionPlan.last_trace``; see repro.core.trace). Off by
        #: default — tracing must cost the replay path nothing.
        self.trace = trace
        self._plans: Dict[tuple, ExecutionPlan] = {}
        self._bucketed: Dict[tuple, BucketedPlan] = {}
        self.stats = {"compiles": 0, "hits": 0}
        #: robustness counters (surfaced through Engine.plan_report):
        #: programs verified clean / verification failures seen /
        #: recompile-once degradations after a failure
        self.health = {"verified": 0, "verify_failures": 0,
                       "recompiles": 0}

    # -- configuration -----------------------------------------------------
    def set_tuning_table(self, table: Optional[sel.TuningTable]) -> None:
        """Install (or clear) a deployment tuning table. Invalidate the
        plan cache: cached algorithm choices may no longer apply."""
        self.table = table
        self._plans.clear()
        self._bucketed.clear()

    def load_bench_tuning(self, payload, *, fit_link: bool = True) -> None:
        """Install measured tuning from a ``BENCH_collectives.json``
        payload (path or dict): a measured-fastest ``TuningTable`` and,
        optionally, fitted α/β link constants."""
        if not isinstance(payload, dict):
            with open(payload) as f:
                payload = json.load(f)
        if fit_link:
            self.link = sel.fit_link_model(payload, base=self.link)
        self.set_tuning_table(sel.TuningTable.from_bench(payload))

    # -- planning ----------------------------------------------------------
    def _axis_size(self, n: Optional[int]) -> int:
        if n is not None:
            return n
        if self.n is not None:
            return self.n
        return jax.lax.axis_size(self.axis)

    def compile(self, collective: str, shape, dtype, *,
                algo: Optional[str] = None, backend: Optional[str] = None,
                opt_level: Optional[int] = None, root: int = 0,
                link: Optional[sel.LinkModel] = None,
                n: Optional[int] = None) -> ExecutionPlan:
        """Compile (or fetch from cache) the plan for one collective
        instance. ``shape`` is the caller's 2D ``(rows, cols)`` payload
        shape; selection, the pass pipeline, and executor lowering run
        at most once per distinct cache key."""
        backend = backend or self.backend or default_backend()
        if backend not in ("xla", "pallas"):
            raise ValueError(
                f"plans require a DSL backend ('xla'|'pallas'), "
                f"got {backend!r}")
        if collective not in _COLLECTIVE_IDS:
            raise ValueError(f"unknown collective {collective!r}")
        n = self._axis_size(n)
        link = link or self.link
        level_req = self.opt_level if opt_level is None else opt_level
        level_req = passes.DEFAULT_OPT_LEVEL if level_req is None else level_req
        rows, cols = (int(shape[0]), int(shape[1]))
        dtype = np.dtype(dtype).name
        key = (collective, (rows, cols), dtype, n, backend, algo, level_req,
               link, root if collective == "broadcast" else None)
        plan = self._plans.get(key)
        if plan is not None:
            self.stats["hits"] += 1
            return plan
        plan = self._build(collective, rows, cols, dtype, n, backend, algo,
                           level_req, root, link)
        self._plans[key] = plan
        self.stats["compiles"] += 1
        return plan

    def plan_for(self, collective: str, shape, dtype, *,
                 buckets=None, algo: Optional[str] = None,
                 backend: Optional[str] = None,
                 opt_level: Optional[int] = None, root: int = 0,
                 link: Optional[sel.LinkModel] = None,
                 n: Optional[int] = None):
        """Bucketed compilation (ROADMAP: continuous batching across
        bucket sizes). With ``buckets=None`` this is :meth:`compile`.
        With ``buckets=(b1, b2, ...)`` it compiles one plan per bucket
        — through the ordinary plan cache, so a later
        ``plan_for``/``compile`` with an overlapping bucket hits — and
        returns a :class:`BucketedPlan` that pads at dispatch. The
        bucketed artifact itself is cached, so engine init and step
        construction share one hit-counter view.

        What buckets count follows the family's padding strategy
        (``_BUCKET_PAD``; see :class:`BucketedPlan`):

        * row-preserving families (all_reduce / broadcast / all_gather)
          — buckets are payload row counts and ``shape`` is the largest
          payload the family must serve::

              bp = comm.plan_for("all_reduce", (8, d_model), jnp.float32,
                                 buckets=(2, 4, 8))
              bp(x)    # x: (rows<=8, d_model) — pads to the bucket

        * row-redistributing families (all_to_all / reduce_scatter) —
          ``shape`` is the full ``(n * rows, cols)`` payload (n per-rank
          row blocks) and buckets count rows PER BLOCK (for MoE expert
          parallelism: the per-rank token capacity)::

              bp = comm.plan_for("all_to_all", (n * cap, d), jnp.float32,
                                 buckets=(8, 16, cap))
              recv = bp(dispatch)   # dispatch: (n*c, d), c <= cap —
                                    # each block pads to the bucket
        """
        if buckets is None:
            return self.compile(collective, shape, dtype, algo=algo,
                                backend=backend, opt_level=opt_level,
                                root=root, link=link, n=n)
        strategy = _BUCKET_PAD.get(collective)
        if strategy is None:
            raise ValueError(
                f"unknown collective {collective!r}: bucketed compilation "
                f"pads per family — " +
                ", ".join(f"{c} ({s})" for c, s in sorted(_BUCKET_PAD.items())))
        rows, cols = int(shape[0]), int(shape[1])
        bs = tuple(sorted({int(b) for b in buckets}))
        if not bs or bs[0] <= 0:
            raise ValueError(f"buckets must be positive row counts: {buckets}")
        backend_r = backend or self.backend or default_backend()
        nn = self._axis_size(n)
        if strategy == "blocks":
            # shape is the full (n * block_rows, cols) payload; buckets
            # count rows per per-rank block
            if rows % nn != 0:
                raise ValueError(
                    f"{collective} rows={rows} not divisible into the "
                    f"{nn} per-rank blocks its '{strategy}' padding "
                    f"strategy buckets over")
            rows //= nn
        if rows > bs[-1]:
            raise ValueError(
                f"shape rows={rows} exceed the largest bucket {bs[-1]}")
        dtype_name = np.dtype(dtype).name
        level_req = self.opt_level if opt_level is None else opt_level
        level_req = passes.DEFAULT_OPT_LEVEL if level_req is None else level_req
        key = (collective, bs, cols, dtype_name, nn, backend_r, algo,
               level_req, link or self.link,
               root if collective == "broadcast" else None)
        cached = self._bucketed.get(key)
        if cached is not None:
            self.stats["hits"] += 1
            return cached
        rows_for = (lambda b: nn * b) if strategy == "blocks" else (lambda b: b)
        plans = {
            b: self.compile(collective, (rows_for(b), cols), dtype, algo=algo,
                            backend=backend, opt_level=opt_level, root=root,
                            link=link, n=nn)
            for b in bs
        }
        bucketed = BucketedPlan(
            collective=collective, axis=self.axis, n=nn, cols=cols,
            dtype=dtype_name, buckets=bs, plans=plans,
            hits={b: 0 for b in bs}, pad_strategy=strategy)
        self._bucketed[key] = bucketed
        return bucketed

    def bucketed_plans(self) -> Dict[tuple, BucketedPlan]:
        """A snapshot of the bucketed-plan cache (key -> plan family)."""
        return dict(self._bucketed)

    def _build(self, collective, rows, cols, dtype, n, backend, algo,
               level_req, root, link) -> ExecutionPlan:
        itemsize = np.dtype(dtype).itemsize
        nbytes = rows * cols * itemsize
        if collective == "all_gather":
            nbytes *= n          # selection is on the full gathered message
        if collective == "broadcast":
            name = "broadcast_allpairs"
            source = algos.broadcast_allpairs(n, root)
        else:
            name = _resolve_algo(collective, n, nbytes, algo, link,
                                 self.table, level_req)
            source = algos.REGISTRY[name](n)

        # run the pass pipeline; chunk-split (O3) falls back when the
        # caller's rows don't divide the split chunk grid (collectives
        # whose output layout embeds the grid cannot pad)
        level = level_req
        prog = passes.optimize(source, level, n)
        if collective not in _PADDABLE:
            while level > 2 and rows % prog.chunks[prog.in_buffer] != 0:
                level -= 1
                prog = passes.optimize(source, level, n)
            if level != level_req and algo is None:
                # the selector ranked candidates under the chunk-split
                # cost model; the plan will run unsplit — re-select at
                # the level that actually executes
                name = _resolve_algo(collective, n, nbytes, algo, link,
                                     self.table, level)
                source = algos.REGISTRY[name](n)
                prog = passes.optimize(source, level, n)

        # static verification (compile-time only — the replay hot path
        # executes the verified artifact with zero added work). On a
        # verifier failure the cached optimized form is abandoned and
        # the plan recompiles ONCE unoptimized (O0 = the hand-written
        # source); only if that still fails does strict mode raise.
        if self.verify != "off":
            vroot = root if collective == "broadcast" else 0
            report = verify_mod.verify_program(
                prog, n, collective=collective, root=vroot)
            if report.findings and level > 0:
                self.health["verify_failures"] += 1
                self.health["recompiles"] += 1
                warnings.warn(
                    f"plan verification failed at O{level} for "
                    f"{collective}/{name} (n={n}): {report.findings[0]} "
                    f"— recompiling unoptimized", stacklevel=3)
                level = 0
                prog = passes.optimize(source, level, n)
                report = verify_mod.verify_program(
                    prog, n, collective=collective, root=vroot)
            if report.findings:
                self.health["verify_failures"] += 1
                if self.verify == "strict":
                    report.raise_if_failed()
                warnings.warn(
                    f"plan verification: {report.summary()} — serving "
                    f"unverified (verify='warn')", stacklevel=3)
            else:
                self.health["verified"] += 1

        n_in = prog.chunks[prog.in_buffer]
        pad = (-rows) % n_in if collective in _PADDABLE else 0
        if pad == 0 and rows % n_in != 0:
            raise ValueError(
                f"{collective} rows={rows} not divisible by the "
                f"{n_in}-chunk input grid of {name!r} at n={n}")

        stats = prog.comm_stats(n, max(nbytes // n_in, 1))
        bytes_key = "wire_bytes_per_rank" if link.torus else "bytes_per_rank"
        est = link.time_us(
            stats["comm_rounds"] + stats["barriers"], stats[bytes_key],
            extra_syncs=max(0, stats["sync_steps"] - stats["comm_rounds"]))
        if backend == "pallas" and not PallasExecutor(prog, self.axis).fits(
                rows + pad, cols, dtype):
            # the kernel holds the whole payload in VMEM: plan the same
            # verified program on the xla lowering (plan.backend says so)
            backend = "xla"
        executor = _build_executor(prog, self.axis, collective, backend,
                                   level, n)
        return ExecutionPlan(
            collective=collective, algo=name, axis=self.axis, n=n,
            shape=(rows, cols), dtype=dtype, backend=backend,
            opt_level=level, requested_opt_level=level_req,
            root=root if collective == "broadcast" else None, pad=pad,
            link=link, estimate_us=est, comm_stats=stats,
            program=prog, executor=executor, trace=self.trace)

    def plans(self) -> Dict[tuple, ExecutionPlan]:
        """A snapshot of the plan cache (key -> plan)."""
        return dict(self._plans)

    def __repr__(self):
        return (f"Communicator(axis={self.axis!r}, n={self.n}, "
                f"backend={self.backend or default_backend()!r}, "
                f"plans={len(self._plans)}, stats={self.stats})")

    # -- collectives (call inside shard_map) -------------------------------
    def all_reduce(self, x, *, backend: Optional[str] = None,
                   algo: Optional[str] = None,
                   link: Optional[sel.LinkModel] = None,
                   opt_level: Optional[int] = None):
        """x: (rows, cols) -> same shape, summed over the axis."""
        backend = backend or self.backend or default_backend()
        if backend == "xla_native":
            return jax.lax.psum(x, self.axis)
        return self.compile("all_reduce", x.shape, x.dtype, algo=algo,
                            backend=backend, opt_level=opt_level,
                            link=link)(x)

    def all_gather(self, x, *, backend: Optional[str] = None,
                   algo: Optional[str] = None,
                   link: Optional[sel.LinkModel] = None,
                   opt_level: Optional[int] = None):
        """x: (rows, cols) shard -> (N*rows, cols) gathered (tiled)."""
        backend = backend or self.backend or default_backend()
        if backend == "xla_native":
            return jax.lax.all_gather(x, self.axis, tiled=True)
        return self.compile("all_gather", x.shape, x.dtype, algo=algo,
                            backend=backend, opt_level=opt_level,
                            link=link)(x)

    def reduce_scatter(self, x, *, backend: Optional[str] = None,
                       algo: Optional[str] = None,
                       link: Optional[sel.LinkModel] = None,
                       opt_level: Optional[int] = None):
        """x: (N*rows, cols) -> (rows, cols): my reduced row-block."""
        backend = backend or self.backend or default_backend()
        if backend == "xla_native":
            return jax.lax.psum_scatter(x, self.axis, scatter_dimension=0,
                                        tiled=True)
        return self.compile("reduce_scatter", x.shape, x.dtype, algo=algo,
                            backend=backend, opt_level=opt_level,
                            link=link)(x)

    def all_to_all(self, x, *, backend: Optional[str] = None,
                   algo: Optional[str] = None,
                   link: Optional[sel.LinkModel] = None,
                   opt_level: Optional[int] = None):
        """x: (N*rows, cols): row-block b -> device b; returns blocks
        received from each device, stacked."""
        backend = backend or self.backend or default_backend()
        if backend == "xla_native":
            n = self._axis_size(None)
            xs = x.reshape(n, x.shape[0] // n, x.shape[1])
            out = jax.lax.all_to_all(xs, self.axis, split_axis=0,
                                     concat_axis=0, tiled=False)
            return out.reshape(x.shape)
        return self.compile("all_to_all", x.shape, x.dtype, algo=algo,
                            backend=backend, opt_level=opt_level,
                            link=link)(x)

    def broadcast(self, x, root: int = 0, *,
                  backend: Optional[str] = None,
                  link: Optional[sel.LinkModel] = None,
                  opt_level: Optional[int] = None):
        """x: (rows, cols) -> root's buffer on every device."""
        backend = backend or self.backend or default_backend()
        if backend == "xla_native":
            me = jax.lax.axis_index(self.axis)
            masked = jnp.where(me == root, x, jnp.zeros_like(x))
            return jax.lax.psum(masked, self.axis)
        return self.compile("broadcast", x.shape, x.dtype, root=root,
                            backend=backend, opt_level=opt_level,
                            link=link)(x)

    def tree_all_reduce(self, tree, *, backend: Optional[str] = None,
                        lane: int = 128, **kw):
        """Pytree bucket fusion: flatten -> one all_reduce -> unflatten
        (see :func:`repro.core.api.tree_all_reduce`)."""
        leaves, treedef = jax.tree.flatten(tree)
        if not leaves:
            return tree
        dtype = jnp.result_type(*leaves)
        sizes = [leaf.size for leaf in leaves]
        flat = jnp.concatenate(
            [leaf.reshape(-1).astype(dtype) for leaf in leaves])
        pad = (-flat.size) % lane
        flat = jnp.pad(flat, (0, pad))
        buf = flat.reshape(-1, lane)
        red = self.all_reduce(buf, backend=backend, **kw).reshape(-1)
        out, off = [], 0
        for leaf, size in zip(leaves, sizes):
            out.append(red[off:off + size].reshape(leaf.shape)
                       .astype(leaf.dtype))
            off += size
        return jax.tree.unflatten(treedef, out)


def hierarchical_all_reduce(x, *, local: Communicator, node: Communicator,
                            backend: Optional[str] = None,
                            small_message_bytes: int = 1 << 20,
                            opt_level: Optional[int] = None,
                            node_link: Optional[sel.LinkModel] = None):
    """2PH AllReduce (paper §4.4-2PH) over two communicators:
    RS(local) → AR(node) → AG(local).

    The cross-node phase moves 1/L of the data (L = local axis size) —
    the pod-boundary bandwidth saving that motivates the hierarchy. For
    small messages the cross-node hop uses 1PA (the paper's first 2PH
    variant); for large, whatever ``node``'s selector picks on
    ``node_link`` (defaults to the node communicator's own link).
    """
    lnum = local._axis_size(None)
    rows = x.shape[0]
    nbytes = x.size * x.dtype.itemsize
    pad = (-rows) % lnum
    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x

    shard = local.reduce_scatter(xp, backend=backend, opt_level=opt_level)
    shard = node.all_reduce(
        shard, backend=backend, link=node_link,
        algo="allreduce_1pa" if nbytes <= small_message_bytes else None,
        opt_level=opt_level)
    out = local.all_gather(shard, backend=backend, opt_level=opt_level)
    return out[:rows] if pad else out


# ---------------------------------------------------------------------------
# hierarchical (multi-axis) composition — RS(local) → AR(node) → AG(local)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(eq=False, repr=False)
class HierarchicalPlan:
    """A frozen 2-axis AllReduce: three per-axis :class:`ExecutionPlan` s
    composed RS(local) → AR(node) → AG(local) (paper §4.4-2PH; HiCCL's
    compositional decomposition), or ONE flat plan when the mesh
    degenerates to a single axis.

    The cross-node phase carries 1/L of the payload (L = local axis
    size) — the pod-boundary bandwidth saving that motivates the
    hierarchy. Like :class:`ExecutionPlan`, the artifact is frozen
    (pure replay, no re-selection), inspectable (:meth:`cost_card`) and
    serializable (:meth:`to_json` / :meth:`from_json`, nested
    plan-file payloads under ``kind="hierarchical_plan"``).
    """

    shape: Tuple[int, int]
    dtype: str
    local_axis: str
    node_axis: Optional[str]
    #: rows appended before RS-intra and sliced back off after AG-intra
    #: so the payload divides the local axis
    pad: int
    rs_plan: Optional[ExecutionPlan]
    ar_plan: Optional[ExecutionPlan]
    ag_plan: Optional[ExecutionPlan]
    #: set instead of the three phases on the single-axis fallback
    flat_plan: Optional[ExecutionPlan] = None

    # -- execution ---------------------------------------------------------
    def __call__(self, x: jax.Array) -> jax.Array:
        """Execute on a local shard inside shard_map over BOTH axes
        (the flat fallback needs only the local axis). Pure replay."""
        if tuple(x.shape) != tuple(self.shape):
            raise ValueError(
                f"hierarchical plan compiled for shape {self.shape}, "
                f"got {tuple(x.shape)}")
        if self.flat_plan is not None:
            return self.flat_plan(x)
        rows = x.shape[0]
        if self.pad:
            x = jnp.pad(x, ((0, self.pad), (0, 0)))
        shard = self.rs_plan(x)
        shard = self.ar_plan(shard)
        out = self.ag_plan(shard)
        return out[:rows] if self.pad else out

    # -- inspection --------------------------------------------------------
    @property
    def phases(self) -> Dict[str, ExecutionPlan]:
        if self.flat_plan is not None:
            return {"flat": self.flat_plan}
        return {"rs": self.rs_plan, "ar": self.ar_plan, "ag": self.ag_plan}

    @property
    def estimate_us(self) -> float:
        """Analytic span: the phases run back-to-back (each phase is a
        global dependency barrier for the next), so costs add."""
        return sum(p.estimate_us for p in self.phases.values())

    @property
    def algo(self) -> str:
        """Phase algorithms as one label, e.g. ``ring_rs+allreduce_1pa+
        ring_ag`` (or the flat plan's algorithm)."""
        return "+".join(p.algo for p in self.phases.values())

    def cost_card(self) -> dict:
        return dict(collective="all_reduce", kind="hierarchical",
                    shape=tuple(self.shape), dtype=self.dtype,
                    axes=[a for a in (self.local_axis, self.node_axis)
                          if a is not None],
                    algo=self.algo, pad=self.pad,
                    estimate_us=round(self.estimate_us, 3),
                    phases={k: p.cost_card()
                            for k, p in self.phases.items()})

    def __repr__(self):
        axes = (self.local_axis if self.node_axis is None
                else f"{self.local_axis}x{self.node_axis}")
        return (f"HierarchicalPlan({self.algo} axes={axes} "
                f"shape={tuple(self.shape)} dtype={self.dtype} "
                f"est={self.estimate_us:.2f}us)")

    # -- serialization -----------------------------------------------------
    def to_dict(self) -> dict:
        return dict(
            version=PLAN_FORMAT_VERSION, format=PLAN_FORMAT_VERSION,
            kind="hierarchical_plan", collective="all_reduce",
            shape=list(self.shape), dtype=self.dtype,
            local_axis=self.local_axis, node_axis=self.node_axis,
            pad=self.pad, estimate_us=self.estimate_us,
            plans={k: p.to_dict() for k, p in self.phases.items()},
        )

    def to_json(self, **json_kw) -> str:
        json_kw.setdefault("indent", 2)
        json_kw.setdefault("sort_keys", True)
        return json.dumps(self.to_dict(), **json_kw)

    @classmethod
    def from_dict(cls, d: dict, *,
                  verify: str = "strict") -> "HierarchicalPlan":
        """Rebuild from :meth:`to_dict` output; every nested phase plan
        is verified and its executor re-prepared (same trust boundary
        as :meth:`ExecutionPlan.from_dict`)."""
        _check_version(d, "HierarchicalPlan")
        if d.get("kind") != "hierarchical_plan":
            raise ValueError(
                f"not a hierarchical plan payload (kind="
                f"{d.get('kind')!r}); use ExecutionPlan/BucketedPlan")
        req = lambda k: _field(d, k, "HierarchicalPlan")  # noqa: E731
        plans = {k: ExecutionPlan.from_dict(p, verify=verify)
                 for k, p in req("plans").items()}
        if "flat" in plans:
            phase = dict(rs_plan=None, ar_plan=None, ag_plan=None,
                         flat_plan=plans["flat"])
        else:
            missing = {"rs", "ar", "ag"} - set(plans)
            if missing:
                raise ValueError(
                    f"hierarchical plan payload missing phase plans "
                    f"{sorted(missing)} (has {sorted(plans)})")
            phase = dict(rs_plan=plans["rs"], ar_plan=plans["ar"],
                         ag_plan=plans["ag"], flat_plan=None)
        return cls(shape=tuple(req("shape")), dtype=req("dtype"),
                   local_axis=req("local_axis"),
                   node_axis=req("node_axis"), pad=req("pad"), **phase)

    @classmethod
    def from_json(cls, s: str, *,
                  verify: str = "strict") -> "HierarchicalPlan":
        return cls.from_dict(json.loads(s), verify=verify)


class HierarchicalCommunicator:
    """Two-axis planning object for 2D meshes (ICI intra × DCN inter):
    owns a local-axis and a node-axis :class:`Communicator` and
    compiles frozen :class:`HierarchicalPlan` s composing
    RS(local) → AR(node) → AG(local).

    With ``node_axis=None`` (or a size-1 node axis at compile time) it
    degrades to a flat single-axis plan on the local communicator — the
    composition is strictly additive over the single-axis machinery.

    Each axis keeps its own :class:`~.selector.LinkModel` (defaults:
    ICI intra, DCN inter), so per-phase selection sees the fabric it
    actually crosses; the cross-node AR uses 1PA for messages at or
    under ``small_message_bytes`` (paper §4.4's first 2PH variant),
    else that axis's selector choice.
    """

    def __init__(self, local_axis: str, node_axis: Optional[str] = None, *,
                 local_n: Optional[int] = None,
                 node_n: Optional[int] = None,
                 local_link: sel.LinkModel = sel.ICI,
                 node_link: sel.LinkModel = sel.DCN,
                 backend: Optional[str] = None,
                 opt_level: Optional[int] = None,
                 small_message_bytes: int = 1 << 20,
                 verify: str = "strict"):
        self.local = Communicator(local_axis, n=local_n, link=local_link,
                                  backend=backend, opt_level=opt_level,
                                  verify=verify)
        self.node = (Communicator(node_axis, n=node_n, link=node_link,
                                  backend=backend, opt_level=opt_level,
                                  verify=verify)
                     if node_axis is not None else None)
        self.small_message_bytes = small_message_bytes
        self._plans: Dict[tuple, HierarchicalPlan] = {}
        self.stats = {"compiles": 0, "hits": 0}

    @property
    def local_axis(self) -> str:
        return self.local.axis

    @property
    def node_axis(self) -> Optional[str]:
        return None if self.node is None else self.node.axis

    def compile(self, shape, dtype, *, backend: Optional[str] = None,
                opt_level: Optional[int] = None,
                local_n: Optional[int] = None,
                node_n: Optional[int] = None) -> HierarchicalPlan:
        """Compile (or fetch) the hierarchical AllReduce plan for one
        2D ``(rows, cols)`` payload. Axis sizes resolve like
        :meth:`Communicator.compile` (pass ``local_n``/``node_n``
        outside traced code)."""
        rows, cols = int(shape[0]), int(shape[1])
        dtype_name = np.dtype(dtype).name
        lnum = self.local._axis_size(local_n)
        nnum = 1 if self.node is None else self.node._axis_size(node_n)
        key = ((rows, cols), dtype_name, lnum, nnum, backend, opt_level)
        plan = self._plans.get(key)
        if plan is not None:
            self.stats["hits"] += 1
            return plan
        if nnum <= 1:
            flat = self.local.compile(
                "all_reduce", (rows, cols), dtype, backend=backend,
                opt_level=opt_level, n=lnum)
            plan = HierarchicalPlan(
                shape=(rows, cols), dtype=dtype_name,
                local_axis=self.local.axis, node_axis=self.node_axis,
                pad=0, rs_plan=None, ar_plan=None, ag_plan=None,
                flat_plan=flat)
        else:
            pad = (-rows) % lnum
            padded = rows + pad
            nbytes = rows * cols * np.dtype(dtype).itemsize
            rs = self.local.compile(
                "reduce_scatter", (padded, cols), dtype, backend=backend,
                opt_level=opt_level, n=lnum)
            shard_rows = padded // lnum
            ar = self.node.compile(
                "all_reduce", (shard_rows, cols), dtype, backend=backend,
                opt_level=opt_level, n=nnum,
                algo=("allreduce_1pa" if nbytes <= self.small_message_bytes
                      else None))
            ag = self.local.compile(
                "all_gather", (shard_rows, cols), dtype, backend=backend,
                opt_level=opt_level, n=lnum)
            plan = HierarchicalPlan(
                shape=(rows, cols), dtype=dtype_name,
                local_axis=self.local.axis, node_axis=self.node.axis,
                pad=pad, rs_plan=rs, ar_plan=ar, ag_plan=ag)
        self._plans[key] = plan
        self.stats["compiles"] += 1
        return plan

    def all_reduce(self, x, **kw):
        """x: (rows, cols) local shard inside shard_map over both axes
        -> same shape, summed over the full 2D mesh."""
        return self.compile(x.shape, x.dtype, **kw)(x)

    def plans(self) -> Dict[tuple, HierarchicalPlan]:
        """A snapshot of the hierarchical plan cache."""
        return dict(self._plans)

    def __repr__(self):
        return (f"HierarchicalCommunicator(local={self.local.axis!r}, "
                f"node={self.node_axis!r}, plans={len(self._plans)}, "
                f"stats={self.stats})")


# ---------------------------------------------------------------------------
# plan sets: the §4.4 deployment artifact (compile once, ship JSON files)
# ---------------------------------------------------------------------------
def plan_from_json(text: str, *, verify: str = "strict"):
    """Load any plan flavor from its JSON payload, dispatching on the
    payload's ``kind`` (``bucketed_plan`` / ``hierarchical_plan`` /
    plain :class:`ExecutionPlan`). Loaded programs are re-verified
    before the executor lowering is prepared — plan files cross a trust
    boundary and are validated, not trusted (docs/robustness.md)."""
    kind = json.loads(text).get("kind")
    if kind == "bucketed_plan":
        return BucketedPlan.from_json(text, verify=verify)
    if kind == "hierarchical_plan":
        return HierarchicalPlan.from_json(text, verify=verify)
    return ExecutionPlan.from_json(text, verify=verify)


def export_plan_set(plans: Dict[str, Any], path) -> pathlib.Path:
    """Write a NAMED set of compiled plans as one JSON file per plan
    plus a ``plan_set.json`` manifest — the paper's §4.4 deployment
    model made concrete: compile the decode plans once on a planner
    host, ship the directory to every serving replica, and each replica
    replays the identical programs (``load_plan_set``) without running
    selection, passes, or verification-compile again.

    ``plans`` is any ``{name: plan}`` dict (e.g. the output of
    :func:`repro.distributed.step.compile_decode_plans`). Returns the
    manifest path."""
    path = pathlib.Path(path)
    path.mkdir(parents=True, exist_ok=True)
    entries = {}
    for name, plan in sorted(plans.items()):
        if not hasattr(plan, "to_json"):
            raise TypeError(
                f"plan set entry {name!r} is {type(plan).__name__}, which "
                f"has no to_json(): only ExecutionPlan/BucketedPlan/"
                f"HierarchicalPlan belong in a plan set")
        text = plan.to_json()
        fname = f"{name}.json"
        (path / fname).write_text(text)
        entries[name] = {"file": fname,
                         "kind": json.loads(text).get("kind",
                                                      "execution_plan")}
    manifest = {"version": PLAN_FORMAT_VERSION, "kind": "plan_set",
                "plans": entries}
    out = path / "plan_set.json"
    out.write_text(json.dumps(manifest, indent=2, sort_keys=True))
    return out


def load_plan_set(path, *, verify: str = "strict") -> Dict[str, Any]:
    """Load a plan set written by :func:`export_plan_set` (pass the
    directory or the manifest path). Every plan file is dispatched on
    its ``kind`` and re-verified on load; the returned ``{name: plan}``
    dict drops straight into ``Engine(decode_plans=...)`` /
    ``make_serve_step(plans=...)`` — fresh plan objects per call, so
    each replica keeps its own bucket-hit counters like a real per-host
    plan load would."""
    p = pathlib.Path(path)
    if p.is_dir():
        p = p / "plan_set.json"
    if not p.exists():
        raise ValueError(
            f"no plan set at {p}: expected a plan_set.json manifest "
            f"written by export_plan_set()")
    d = json.loads(p.read_text())
    if d.get("kind") != "plan_set":
        raise ValueError(
            f"{p} is not a plan-set manifest (kind={d.get('kind')!r}); "
            f"single plan files load via api.load_plan")
    _check_version(d, "plan set manifest")
    out = {}
    for name, ent in _field(d, "plans", "plan set manifest").items():
        f = p.parent / _field(ent, "file", f"plan set entry {name!r}")
        if not f.exists():
            raise ValueError(
                f"plan set entry {name!r} points at missing file {f}: "
                f"the exported directory is incomplete")
        out[name] = plan_from_json(f.read_text(), verify=verify)
    return out


# ---------------------------------------------------------------------------
# process-default communicators (the api.py wrappers' backing store)
# ---------------------------------------------------------------------------
_DEFAULTS: Dict[str, Communicator] = {}


def default_communicator(axis: str) -> Communicator:
    """The process-default Communicator for a mesh axis (created on
    first use; size resolved per call, so it serves any mesh carrying
    the axis name). Install a ``TuningTable`` or fitted link on it to
    retune the module-level ``repro.core.api`` collectives."""
    comm = _DEFAULTS.get(axis)
    if comm is None:
        comm = _DEFAULTS[axis] = Communicator(axis)
    return comm


def reset_default_communicators() -> None:
    """Drop all process-default communicators (tests)."""
    _DEFAULTS.clear()
