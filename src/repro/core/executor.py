"""DSL Executors: lower a ``dsl.Program`` to runnable code.

Two lowerings of the *same* declared algorithm (paper §3.1/§4.3 —
declaration vs. implementation separation):

* ``PallasExecutor`` — generates a TPU kernel whose instructions are the
  MSCCL++ channel primitives (put/wait/barrier as remote DMAs and
  semaphores). Paper-faithful; runs on TPU hardware or the interpret
  emulator. Consumes optimizer output directly: a coalesced multi-chunk
  put issues its DMAs back-to-back on one semaphore pair, a batched
  wait spins its chunk set at one program point.
* ``XlaExecutor``   — lowers put rounds to ``jax.lax`` collectives and
  local chunk ops to jnp. Portable to any XLA backend; used inside the
  pjit'd model code and the multi-pod dry-run. Synchronization
  instructions (wait/flush/barrier) erase to data dependence, which
  XLA enforces structurally.

The XLA executor has two modes:

* ``vectorize=False`` — the reference lowering: every chunk-put is its
  own ``ppermute``, every chunk access its own dynamic slice. This is
  the ``opt_level=0`` baseline benchmarks compare against.
* ``vectorize=True`` (default) — a cached *lowering plan* (keyed on
  (program, n), built once per program) classifies each put
  instruction and emits the cheapest collective:

  - a full fan-out put whose every peer receives its own chunk lowers
    to ONE ``jax.lax.all_to_all`` (all-pairs RS / AllToAll rounds);
  - a full fan-out put whose every peer receives the same chunk lowers
    to ONE ``jax.lax.all_gather`` (1PA broadcast, AG phases);
  - a coalesced same-shift group lowers to ONE stacked ``ppermute``
    over the chunk-stacked payload (pipelined ring rounds);
  - reductions gather their operand chunks with one ``take`` per
    contiguous operand run, then left-fold in declaration order, so
    results stay bit-identical to the reference lowering;
  - any rank-independent ``IndexExpr`` (``is_static()``) folds to a
    Python int at trace time and uses static slicing.

Both operate on 2D chunk payloads: the caller supplies ``x`` shaped
``(chunks_in * rows, cols)`` and receives ``(chunks_out * rows, cols)``.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import primitives as prim
from repro.core.channels import MemoryChannel
from repro.core.dsl import IndexExpr, Instr, Op, Program, full_fanout

__all__ = ["XlaExecutor", "PallasExecutor", "execute"]

# Pallas executor rotates among this many DMA semaphore pairs so that
# byte credits of distinct communication rounds can never alias (the
# cross-round hazard of §2.2.2 'Inflexible Synchronization'); a barrier
# is auto-inserted if a program has more comm rounds than pairs.
_NUM_SEM_PAIRS = 4

#: The Pallas kernel keeps its input, output and every local buffer
#: whole in VMEM. Mosaic's default scoped VMEM limit on TPU v5e is
#: 16 MiB (its refusal reads "scoped allocation ... limit 16.00M");
#: ``PallasExecutor.fits`` holds a payload to it so that oversize
#: payloads are planned on the XLA backend instead of failing to compile.
VMEM_LIMIT_BYTES = 16 * 2**20
#: TPU vector tiles are (8 * packing) sublanes x 128 lanes; the executor
#: pads a payload's columns to a whole number of lanes at dispatch.
_LANES = 128


def _tile_rows(rows: int, itemsize: int) -> int:
    """Chunk rows padded to a shape Mosaic slices without refusal: a
    whole number of sublane tiles (8 rows of 32-bit words, packing
    ``4 // itemsize`` narrower rows into each), or below one tile a
    power of two of at least one packed row group."""
    packing = max(1, 4 // itemsize)
    tile = 8 * packing
    if rows >= tile:
        return -(-rows // tile) * tile
    return max(packing, 1 << (rows - 1).bit_length())


# ---------------------------------------------------------------------------
# lowering plan (vectorized XLA path)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _PutAction:
    """One lowered put instruction.

    kind: 'a2a' (one all_to_all), 'gather' (one all_gather), or
    'groups' (one stacked ppermute per same-shift triple group).
    """

    kind: str
    sb: str = ""
    db: str = ""
    src_expr: Optional[IndexExpr] = None
    groups: Tuple[Tuple[Any, Tuple], ...] = ()   # (peer key, triples)


def _peer_key(to: IndexExpr, n: int):
    """Grouping/lowering key for a put's peer map: the uniform ring
    shift as a plain int when one exists, else the peer ``IndexExpr``
    itself (rank-dependent maps such as swing's parity-alternating
    exchanges). Both compare by value, so consecutive puts to the same
    peer map coalesce either way."""
    try:
        return to.shift() % n
    except ValueError:
        return to


def _peer_perm(key, n: int):
    """``(perm, inv)`` for a put key: the (sender, receiver) pairs fed
    to ``ppermute`` plus the static receiver->sender inverse map. The
    peer map must be a permutation of the ranks — anything else cannot
    be a point-to-point put round."""
    if isinstance(key, int):
        return ([(r, (r + key) % n) for r in range(n)],
                np.asarray([(r - key) % n for r in range(n)]))
    dests = [key(r, n) % n for r in range(n)]
    if sorted(dests) != list(range(n)):
        raise ValueError(
            f"put peer map {key!r} is not a permutation of {n} ranks "
            f"(destinations {dests}); rank-dependent puts must pair "
            f"every sender with a distinct receiver")
    inv = np.empty(n, dtype=np.int32)
    for r, d in enumerate(dests):
        inv[d] = r
    return [(r, d) for r, d in enumerate(dests)], inv


def _group_by_shift(triples, n) -> Tuple[Tuple[Any, Tuple], ...]:
    groups: List[Tuple[Any, List]] = []
    for t in triples:
        s = _peer_key(t[2], n)
        if groups and groups[-1][0] == s:
            groups[-1][1].append(t)
        else:
            groups.append((s, [t]))
    return tuple((s, tuple(ts)) for s, ts in groups)


def _classify_put(instr: Instr, n: int, chunks: dict) -> _PutAction:
    triples = instr.put_triples()
    fo = full_fanout(triples, n) if len(triples) > 1 else None
    if fo is not None:
        sb, db = fo
        if chunks[db] == n:
            # pattern A: each peer receives its own chunk (src index ==
            # destination rank) -> all_to_all
            if (chunks[sb] == n
                    and all(si == to for (_, si), _, to in triples)):
                return _PutAction("a2a", sb=sb, db=db)
            # pattern B: every peer receives the same chunk -> all_gather
            sis = {si for (_, si), _, _ in triples}
            if len(sis) == 1:
                return _PutAction("gather", sb=sb, db=db,
                                  src_expr=next(iter(sis)))
    return _PutAction("groups", groups=_group_by_shift(triples, n))


# weak identity memo: library programs stay planned for the process
# lifetime, user-built programs are released with their last reference
_PLAN_MEMO: "weakref.WeakKeyDictionary[Program, dict]" = \
    weakref.WeakKeyDictionary()


def _lowering_plan(program: Program, n: int):
    """Per-(program, n) classification of every PUT instruction,
    memoized so repeated jit traces of one collective reuse the plan."""
    memo = _PLAN_MEMO.setdefault(program, {})
    if n not in memo:
        memo[n] = {
            id(instr): _classify_put(instr, n, program.chunks)
            for instr in program.instructions() if instr.op is Op.PUT
        }
    return memo[n]


def _slab(exprs: Sequence[IndexExpr]) -> Optional[IndexExpr]:
    """If ``exprs`` address k contiguous sub-chunks ``k*base + j``
    (j = 0..k-1) of one split buffer, return the base expression —
    the whole group then moves as one dynamic slice."""
    k = len(exprs)
    e0 = exprs[0]
    if e0.scale != k or e0.post != 0:
        return None
    for j, e in enumerate(exprs):
        if dataclasses.replace(e, post=0) != dataclasses.replace(e0, post=0) \
                or e.post != j:
            return None
    return dataclasses.replace(e0, scale=1, post=0)


class XlaExecutor:
    """Interpret a Program with jax.lax collectives (portable path)."""

    def __init__(self, program: Program, axis: str, *, vectorize: bool = True):
        self.program = program.freeze() if not program._frozen else program
        self.axis = axis
        self.vectorize = vectorize
        self._prepared: Optional[Tuple[int, dict]] = None

    def prepare(self, n: int) -> "XlaExecutor":
        """Prebuild the lowering plan for an ``n``-rank axis — the
        compile-once path: an ``ExecutionPlan`` calls this at plan-build
        time so later traced executions do zero classification work."""
        if self.vectorize:
            self._prepared = (n, _lowering_plan(self.program, n))
        return self

    # -- shared helpers ----------------------------------------------------
    def _idx(self, e: IndexExpr, me, n):
        """Chunk index: a Python int when rank-independent (static
        fast path), else a traced value."""
        return e(0, n) if e.is_static() else e(me, n)

    def _get(self, bufs, b, e, me, n):
        if e.is_static():
            return bufs[b][e(0, n)]
        return jax.lax.dynamic_index_in_dim(bufs[b], e(me, n), axis=0,
                                            keepdims=False)

    def _set(self, bufs, b, e, val, me, n):
        val = val.astype(bufs[b].dtype)
        if e.is_static():
            bufs[b] = bufs[b].at[e(0, n)].set(val)
        else:
            bufs[b] = jax.lax.dynamic_update_index_in_dim(
                bufs[b], val, e(me, n), axis=0)
        return bufs

    # -- reference (opt_level=0 style) put lowering ------------------------
    def _run_put_reference(self, bufs, instr, me, n):
        for (sb, si), (db, di), to in instr.put_triples():
            key = _peer_key(to, n)
            perm, inv = _peer_perm(key, n)
            val = jax.lax.dynamic_index_in_dim(
                bufs[sb], si(me, n), axis=0, keepdims=False)
            val = jax.lax.ppermute(val, self.axis, perm)
            sender = ((me - key) % n if isinstance(key, int)
                      else jnp.asarray(inv)[me])
            bufs[db] = jax.lax.dynamic_update_index_in_dim(
                bufs[db], val.astype(bufs[db].dtype), di(sender, n), axis=0)
        return bufs

    # -- vectorized put lowering -------------------------------------------
    def _run_put_vectorized(self, bufs, action: _PutAction, me, n):
        axis = self.axis
        if action.kind == "a2a":
            # peer j's chunk-for-me is its bufs[sb][me]; one collective
            # moves the whole round. Restore my own slot afterwards: a
            # real put never targets self, so slot `me` must keep its
            # pre-round value for bit-equivalence.
            out = jax.lax.all_to_all(bufs[action.sb], axis,
                                     split_axis=0, concat_axis=0,
                                     tiled=False)
            prev_own = jax.lax.dynamic_index_in_dim(
                bufs[action.db], me, axis=0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out.astype(bufs[action.db].dtype), prev_own, me, axis=0)
            bufs[action.db] = out
            return bufs
        if action.kind == "gather":
            val = self._get(bufs, action.sb, action.src_expr, me, n)
            g = jax.lax.all_gather(val, axis)          # g[j] = rank j's val
            prev_own = jax.lax.dynamic_index_in_dim(
                bufs[action.db], me, axis=0, keepdims=False)
            g = jax.lax.dynamic_update_index_in_dim(
                g.astype(bufs[action.db].dtype), prev_own, me, axis=0)
            bufs[action.db] = g
            return bufs
        for key, triples in action.groups:
            bufs = self._run_shift_group(bufs, key, triples, me, n)
        return bufs

    def _run_shift_group(self, bufs, key, triples, me, n):
        """One stacked ppermute for k same-peer-map chunk puts."""
        axis = self.axis
        perm, inv = _peer_perm(key, n)
        sender = ((me - key) % n if isinstance(key, int)
                  else jnp.asarray(inv)[me])
        if len(triples) == 1:
            (sb, si), (db, di), _ = triples[0]
            val = self._get(bufs, sb, si, me, n)
            val = jax.lax.ppermute(val, axis, perm)
            val = val.astype(bufs[db].dtype)
            if di.is_static():
                bufs[db] = bufs[db].at[di(0, n)].set(val)
            else:
                bufs[db] = jax.lax.dynamic_update_index_in_dim(
                    bufs[db], val, di(sender, n), axis=0)
            return bufs

        srcs = [t[0] for t in triples]
        dsts = [t[1] for t in triples]
        sb0, db0 = srcs[0][0], dsts[0][0]
        src_slab = _slab([e for _, e in srcs]) \
            if all(b == sb0 for b, _ in srcs) else None
        dst_slab = _slab([e for _, e in dsts]) \
            if all(b == db0 for b, _ in dsts) else None
        k = len(triples)

        if src_slab is not None:
            start = k * self._idx(src_slab, me, n)
            stacked = jax.lax.dynamic_slice_in_dim(bufs[sb0], start, k, axis=0)
        else:
            stacked = jnp.stack(
                [self._get(bufs, b, e, me, n) for b, e in srcs])
        stacked = jax.lax.ppermute(stacked, axis, perm)
        if dst_slab is not None:
            start = k * (dst_slab(0, n) if dst_slab.is_static()
                         else dst_slab(sender, n))
            bufs[db0] = jax.lax.dynamic_update_slice_in_dim(
                bufs[db0], stacked.astype(bufs[db0].dtype), start, axis=0)
        else:
            for i, (db, di) in enumerate(dsts):
                val = stacked[i].astype(bufs[db].dtype)
                if di.is_static():
                    bufs[db] = bufs[db].at[di(0, n)].set(val)
                else:
                    bufs[db] = jax.lax.dynamic_update_index_in_dim(
                        bufs[db], val, di(sender, n), axis=0)
        return bufs

    # -- reduce lowering ----------------------------------------------------
    def _reduce_operands(self, bufs, srcs, me, n):
        """Operand values in declaration order, gathering contiguous
        same-buffer runs with one ``take`` each (vectorized mode)."""
        vals: List[Any] = []
        i = 0
        while i < len(srcs):
            b, e = srcs[i]
            j = i + 1
            while (j < len(srcs) and srcs[j][0] == b
                   and srcs[j][1].sign == e.sign
                   and srcs[j][1].relative == e.relative
                   and srcs[j][1].scale == e.scale
                   and srcs[j][1].post == e.post):
                j += 1
            run = srcs[i:j]
            if len(run) == 1:
                vals.append(self._get(bufs, b, e, me, n))
            else:
                offs = np.array([se.offset for _, se in run])
                if e.is_static():
                    if e.relative:
                        idx = e.scale * (offs % n) + e.post
                    else:
                        idx = e.scale * offs + e.post
                    stacked = bufs[b][np.asarray(idx)]
                else:
                    idx = e.scale * ((e.sign * me + offs) % n) + e.post
                    stacked = jnp.take(bufs[b], idx, axis=0)
                vals += [stacked[t] for t in range(len(run))]
            i = j
        return vals

    def _run_reduce(self, bufs, instr, me, n, vectorize: bool):
        db, di = instr.dst
        if vectorize:
            vals = self._reduce_operands(bufs, list(instr.srcs), me, n)
        else:
            vals = [jax.lax.dynamic_index_in_dim(bufs[b], e(me, n), axis=0,
                                                 keepdims=False)
                    for b, e in instr.srcs]
        acc = vals[0]
        for v in vals[1:]:    # left fold: bit-identical to the reference
            acc = acc + v
        if vectorize:
            return self._set(bufs, db, di, acc, me, n)
        bufs[db] = jax.lax.dynamic_update_index_in_dim(
            bufs[db], acc.astype(bufs[db].dtype), di(me, n), axis=0)
        return bufs

    # -- profiling -----------------------------------------------------------
    def trace_emissions(self, n: int):
        """The backend-lowered emission stream this executor issues for
        an ``n``-rank axis (see :mod:`repro.core.trace`): what the
        vectorized lowering actually emits — one ``all_to_all`` /
        ``all_gather`` emission per fan-out round, one (stacked)
        ``ppermute`` per same-shift group — or per-triple ``ppermute``
        emissions in reference mode. Synchronization instructions erase
        to data dependence on this backend, so their emissions are
        labelled ``data_dep``."""
        from repro.core.trace import Emission
        p = self.program
        plan = None
        if self.vectorize:
            if self._prepared is not None and self._prepared[0] == n:
                plan = self._prepared[1]
            else:
                plan = _lowering_plan(p, n)
        out = []
        for iid, instr in enumerate(p.instructions()):
            rid = instr.round_id
            if instr.op is Op.PUT:
                triples = instr.put_triples()
                if plan is None:
                    for sub, t in enumerate(triples):
                        k = _peer_key(t[2], n)
                        out.append(Emission(
                            iid, sub, "put", "ppermute", rid,
                            shift=k if isinstance(k, int) else None,
                            puts=(t,)))
                    continue
                action = plan[id(instr)]
                if action.kind == "a2a":
                    out.append(Emission(iid, 0, "put", "all_to_all", rid,
                                        puts=tuple(triples)))
                elif action.kind == "gather":
                    out.append(Emission(iid, 0, "put", "all_gather", rid,
                                        puts=tuple(triples)))
                else:
                    for sub, (s, ts) in enumerate(action.groups):
                        out.append(Emission(
                            iid, sub, "put",
                            "stacked_ppermute" if len(ts) > 1 else "ppermute",
                            rid, shift=s % n if isinstance(s, int) else None,
                            puts=tuple(ts)))
            elif instr.op is Op.WAIT:
                out.append(Emission(iid, 0, "wait", "data_dep", rid,
                                    waits=tuple(instr.wait_chunks())))
            elif instr.op is Op.BARRIER:
                out.append(Emission(iid, 0, "barrier", "data_dep", rid))
            elif instr.op is Op.FLUSH:
                continue  # no-op on this backend (flushed at issue)
            elif instr.op in (Op.COPY, Op.REDUCE):
                out.append(Emission(iid, 0, instr.op.value, "jnp", rid,
                                    dst=instr.dst, srcs=tuple(instr.srcs)))
            else:  # pragma: no cover
                raise NotImplementedError(instr.op)
        return out

    # -- entry point ---------------------------------------------------------
    def __call__(self, x: jax.Array) -> jax.Array:
        from repro.core import faults
        inj = faults.active()
        if inj is not None:       # chaos harness hook (trace time only)
            x = inj.on_execute(x)
        p = self.program
        axis = self.axis
        n = jax.lax.axis_size(axis)
        me = jax.lax.axis_index(axis)
        n_in = p.chunks[p.in_buffer]
        rows = x.shape[0] // n_in
        cols = x.shape[1]
        from repro.core import trace as trace_mod
        col = trace_mod.active()
        if col is not None:       # profiler hook (trace time only)
            col.record(self, n=n, chunk_rows=rows, cols=cols,
                       dtype=np.dtype(x.dtype).name, backend="xla")
        if not self.vectorize:
            plan = None
        elif self._prepared is not None and self._prepared[0] == n:
            plan = self._prepared[1]
        else:
            plan = _lowering_plan(p, n)

        bufs: dict[str, jax.Array] = {}
        for name, k in p.chunks.items():
            if name == p.in_buffer:
                bufs[name] = x.reshape(n_in, rows, cols)
            else:
                bufs[name] = jnp.zeros((k, rows, cols), x.dtype)

        for instr in p.instructions():
            if instr.op is Op.PUT:
                if plan is not None:
                    bufs = self._run_put_vectorized(
                        bufs, plan[id(instr)], me, n)
                else:
                    bufs = self._run_put_reference(bufs, instr, me, n)
            elif instr.op in (Op.WAIT, Op.FLUSH, Op.BARRIER):
                continue  # data dependence IS the synchronization here
            elif instr.op is Op.COPY:
                sb, si = instr.srcs[0]
                db, di = instr.dst
                if self.vectorize:
                    val = self._get(bufs, sb, si, me, n)
                    bufs = self._set(bufs, db, di, val, me, n)
                else:
                    val = jax.lax.dynamic_index_in_dim(
                        bufs[sb], si(me, n), axis=0, keepdims=False)
                    bufs[db] = jax.lax.dynamic_update_index_in_dim(
                        bufs[db], val, di(me, n), axis=0)
            elif instr.op is Op.REDUCE:
                bufs = self._run_reduce(bufs, instr, me, n, self.vectorize)
            else:  # pragma: no cover
                raise NotImplementedError(instr.op)

        out = bufs[p.out_buffer]
        return out.reshape(out.shape[0] * rows, cols)


class PallasExecutor:
    """Trace a Program into a Pallas TPU kernel over channel primitives.

    Understands the optimizer's multi-chunk forms: a coalesced put
    issues its DMAs consecutively on the round's semaphore pair; a
    batched wait performs its recv-waits at one program point. When a
    coalesced group's k chunks address one *contiguous slab* of a split
    buffer (the chunk-split pass's ``k*base + j`` layout, detected with
    the same ``_slab`` test the XLA lowering uses), the whole group
    moves as ONE multi-chunk DMA descriptor per peer — a strided copy —
    instead of k per-chunk descriptors, and the matching batched wait
    waits on the slab with one matching descriptor (DMA semaphores
    count bytes, so descriptor granularity must agree on both sides).
    This closes the ROADMAP item "coalesced puts still issue k
    descriptors".

    ``descriptor_count(n)`` reports the per-rank DMA put descriptors one
    kernel invocation issues; ``last_trace_descriptors`` is the count
    actually issued by the most recent kernel trace (tests assert the
    two agree).
    """

    def __init__(self, program: Program, axis: str, *, collective_id: int = 7,
                 interpret=None):
        self.program = program.freeze() if not program._frozen else program
        self.axis = axis
        self.collective_id = collective_id
        self.interpret = interpret
        self._prepared: Optional[Tuple[int, dict, dict, dict]] = None
        #: DMA put descriptors issued by the most recent kernel trace
        self.last_trace_descriptors: int = 0

    def vmem_bytes(self, rows: int, cols: int, dtype) -> int:
        """VMEM one invocation on a ``(rows, cols)`` payload allocates:
        input, output and local buffers, each chunk at the padded shape
        the kernel runs at (``_tile_rows`` rows, whole 128-lane
        columns)."""
        p = self.program
        itemsize = np.dtype(dtype).itemsize
        chunk_rows = _tile_rows(rows // p.chunks[p.in_buffer], itemsize)
        lanes = -(-cols // _LANES) * _LANES
        return sum(p.chunks.values()) * chunk_rows * lanes * itemsize

    def fits(self, rows: int, cols: int, dtype) -> bool:
        """Whether a ``(rows, cols)`` payload lowers within the scoped
        VMEM limit (see ``VMEM_LIMIT_BYTES``)."""
        return self.vmem_bytes(rows, cols, dtype) <= VMEM_LIMIT_BYTES

    def prepare(self, n: int) -> "PallasExecutor":
        """Prebuild the wait→put-round matching and the per-instruction
        slab/descriptor plans — put AND wait side — for an ``n``-rank
        axis (the static analysis every kernel trace otherwise redoes)."""
        wait_rounds = self._wait_put_rounds(n)
        self._prepared = (n, wait_rounds, self._put_plan(n),
                          self._wait_plan(n, wait_rounds))
        return self

    # -- slab/descriptor planning -------------------------------------------
    def _put_emissions(self, instr, n: int):
        """The DMA descriptors one PUT instruction issues, grouped by
        peer map: ``(key, triples, slab)`` where ``key`` is the uniform
        int shift or the peer ``IndexExpr`` (see ``_peer_key``) and
        ``slab`` is ``(sb, db, src_base, dst_base, k)`` when the
        group's k chunks move as one contiguous-slab descriptor, else
        None."""
        out = []
        for shift, triples in _group_by_shift(instr.put_triples(), n):
            slab = None
            if len(triples) > 1:
                sb0 = triples[0][0][0]
                db0 = triples[0][1][0]
                if all(sb == sb0 for (sb, _), _, _ in triples) \
                        and all(db == db0 for _, (db, _), _ in triples):
                    s_base = _slab([si for (_, si), _, _ in triples])
                    d_base = _slab([di for _, (_, di), _ in triples])
                    if s_base is not None and d_base is not None:
                        slab = (sb0, db0, s_base, d_base, len(triples))
            out.append((shift, tuple(triples), slab))
        return out

    def _put_plan(self, n: int) -> dict:
        return {id(i): self._put_emissions(i, n)
                for i in self.program.instructions() if i.op is Op.PUT}

    def _wait_emissions(self, instr, n: int, rounds):
        """The recv-wait descriptors for one WAIT: consecutive chunks of
        one buffer matching one put round collapse into a slab wait when
        their indices form a contiguous slab (mirroring the sender's
        slab descriptor, so byte credits match one-to-one)."""
        chunks = instr.wait_chunks()
        out = []
        i = 0
        while i < len(chunks):
            (db, _), _ = chunks[i]
            rid = rounds[i]
            j = i + 1
            while j < len(chunks) and rounds[j] == rid \
                    and chunks[j][0][0] == db:
                j += 1
            run = chunks[i:j]
            base = _slab([e for (_, e), _ in run]) if len(run) > 1 else None
            if base is not None:
                out.append((rid, db, base, len(run)))
            else:
                for (b, e), _ in run:
                    out.append((rid, b, e, 1))
            i = j
        return out

    def _wait_plan(self, n: int, wait_rounds: dict) -> dict:
        return {id(w): self._wait_emissions(w, n, wait_rounds[id(w)])
                for w in self.program.instructions() if w.op is Op.WAIT}

    def descriptor_count(self, n: int) -> int:
        """Per-rank DMA put descriptors one invocation issues — the
        quantity the slab lowering minimizes (a coalesced k-chunk slab
        put counts 1, not k)."""
        if self._prepared is not None and self._prepared[0] == n:
            put_plan = self._prepared[2]
        else:
            put_plan = self._put_plan(n)
        cnt = 0
        for emissions in put_plan.values():
            for _, triples, slab in emissions:
                cnt += 1 if slab is not None else len(triples)
        return cnt

    def chunk_put_count(self) -> int:
        """Per-rank chunk puts (the descriptor count of the pre-slab
        lowering; bytes moved are identical)."""
        return sum(len(i.put_triples())
                   for i in self.program.instructions() if i.op is Op.PUT)

    # -- profiling -----------------------------------------------------------
    def trace_emissions(self, n: int):
        """The kernel's emission stream at descriptor granularity (see
        :mod:`repro.core.trace`): one ``dma_slab`` emission per
        contiguous-slab descriptor, one ``dma`` per per-chunk
        descriptor, matching ``sem_wait``/``sem_wait_slab`` recv-waits,
        and ``device_barrier`` emissions — exactly what
        ``descriptor_count(n)`` counts."""
        from repro.core.trace import Emission
        p = self.program
        if self._prepared is not None and self._prepared[0] == n:
            _, wait_rounds, put_plan, _ = self._prepared
        else:
            wait_rounds = self._wait_put_rounds(n)
            put_plan = self._put_plan(n)
        out = []
        for iid, instr in enumerate(p.instructions()):
            rid = instr.round_id
            if instr.op is Op.PUT:
                sub = 0
                for shift, triples, slab in put_plan[id(instr)]:
                    s = shift % n if isinstance(shift, int) else None
                    if slab is not None:
                        out.append(Emission(iid, sub, "put", "dma_slab",
                                            rid, shift=s,
                                            puts=tuple(triples)))
                        sub += 1
                    else:
                        for t in triples:
                            out.append(Emission(iid, sub, "put", "dma",
                                                rid, shift=s,
                                                puts=(t,)))
                            sub += 1
            elif instr.op is Op.WAIT:
                # mirror _wait_emissions' slab grouping, but keep the
                # concrete (chunk, frm) pairs each descriptor covers so
                # the emulator can resolve wait→put dependencies
                chunks = instr.wait_chunks()
                rounds = wait_rounds[id(instr)]
                sub = 0
                i = 0
                while i < len(chunks):
                    (db, _), _ = chunks[i]
                    rid_p = rounds[i]
                    j = i + 1
                    while j < len(chunks) and rounds[j] == rid_p \
                            and chunks[j][0][0] == db:
                        j += 1
                    run = chunks[i:j]
                    base = _slab([e for (_, e), _ in run]) \
                        if len(run) > 1 else None
                    if base is not None:
                        out.append(Emission(iid, sub, "wait",
                                            "sem_wait_slab", rid,
                                            waits=tuple(run)))
                        sub += 1
                    else:
                        for c in run:
                            out.append(Emission(iid, sub, "wait",
                                                "sem_wait", rid,
                                                waits=(c,)))
                            sub += 1
                    i = j
            elif instr.op is Op.BARRIER:
                out.append(Emission(iid, 0, "barrier", "device_barrier",
                                    rid))
            elif instr.op is Op.FLUSH:
                continue  # puts are flushed at issue in this executor
            elif instr.op in (Op.COPY, Op.REDUCE):
                out.append(Emission(iid, 0, instr.op.value, "vmem", rid,
                                    dst=instr.dst, srcs=tuple(instr.srcs)))
            else:  # pragma: no cover
                raise NotImplementedError(instr.op)
        return out

    # -- static analysis ----------------------------------------------------
    def _wait_put_rounds(self, n: int):
        """Map each WAIT instr (by id) to the rounds of its chunks'
        matching PUTs — the wait must spin on the semaphore pair that
        put signals. Programs are rank-symmetric, so matching at rank 0
        suffices."""
        p = self.program
        put_dsts = [(put.round_id, to, dst) for put in p.instructions()
                    if put.op is Op.PUT for _, dst, to in put.put_triples()]
        mapping: dict = {}
        for w in p.instructions():
            if w.op is not Op.WAIT:
                continue
            rounds = []
            for (wbuf, widx), frm in w.wait_chunks():
                src_rank = frm(0, n)
                want_idx = widx(0, n)
                for rid, to, (db, di) in put_dsts:
                    if (to(src_rank, n) % n == 0 and db == wbuf
                            and di(src_rank, n) == want_idx):
                        rounds.append(rid)
                        break
                else:
                    raise ValueError(f"wait {w} has no matching put")
            mapping[id(w)] = rounds
        return mapping

    # -- kernel body --------------------------------------------------------
    def _kernel(self, x_ref, out_ref, locals_refs, bar_sem, *sems):
        p = self.program
        axis = self.axis
        n = jax.lax.axis_size(axis)
        me = jax.lax.axis_index(axis)
        prim.start_barrier(axis)

        refs = {p.in_buffer: x_ref.at[0], p.out_buffer: out_ref}
        refs.update(locals_refs)

        sem_pairs = [(sems[2 * i], sems[2 * i + 1])
                     for i in range(len(sems) // 2)]
        # semaphore pairs rotate over PUT rounds; a WAIT uses the pair of
        # its matching put round (phase credits can then never alias —
        # the §2.2.2 'Inflexible Synchronization' hazard, solved with sem
        # separation instead of extra barriers).
        put_rounds = sorted({i.round_id for i in p.instructions()
                             if i.op is Op.PUT})
        round_to_pair = {r: i % _NUM_SEM_PAIRS for i, r in enumerate(put_rounds)}
        if self._prepared is not None and self._prepared[0] == n:
            _, wait_to_rounds, put_plan, wait_plan = self._prepared
        else:
            wait_to_rounds = self._wait_put_rounds(n)
            put_plan = self._put_plan(n)
            wait_plan = self._wait_plan(n, wait_to_rounds)
        wrap = len(put_rounds) > _NUM_SEM_PAIRS
        self.last_trace_descriptors = 0

        for ri, rnd in enumerate(p.rounds):
            if (wrap and ri in round_to_pair and round_to_pair[ri] == 0
                    and ri != put_rounds[0]):
                prim.device_barrier(bar_sem, axis)  # safe pair reuse on wrap
            for instr in rnd.instrs:
                if instr.op is Op.PUT:
                    send_sem, recv_sem = sem_pairs[round_to_pair[ri]]
                    for shift, triples, slab in put_plan[id(instr)]:
                        peer = ((me + shift) % n if isinstance(shift, int)
                                else shift(me, n) % n)
                        chan = MemoryChannel(axis, peer, send_sem, recv_sem)
                        if slab is not None:
                            # one strided (contiguous-slab) descriptor
                            # moves all k chunks of the group
                            sb, db, s_base, d_base, k = slab
                            chan.put(
                                refs[sb].at[pl.ds(k * s_base(me, n), k)],
                                refs[db].at[pl.ds(k * d_base(me, n), k)],
                            ).flush()
                            self.last_trace_descriptors += 1
                        else:
                            for (sb, si), (db, di), _ in triples:
                                chan.put(refs[sb].at[si(me, n)],
                                         refs[db].at[di(me, n)]).flush()
                                self.last_trace_descriptors += 1
                elif instr.op is Op.WAIT:
                    for rid, db, base, k in wait_plan[id(instr)]:
                        send_sem, recv_sem = sem_pairs[round_to_pair[rid]]
                        if k > 1:
                            prim.wait_recv_into(
                                refs[db].at[pl.ds(k * base(me, n), k)],
                                send_sem, recv_sem, {axis: me})
                        else:
                            prim.wait_recv_into(refs[db].at[base(me, n)],
                                                send_sem, recv_sem,
                                                {axis: me})
                elif instr.op is Op.FLUSH:
                    continue  # puts are flushed at issue in this executor
                elif instr.op is Op.BARRIER:
                    prim.device_barrier(bar_sem, axis)
                elif instr.op is Op.COPY:
                    sb, si = instr.srcs[0]
                    db, di = instr.dst
                    refs[db][di(me, n)] = refs[sb][si(me, n)]
                elif instr.op is Op.REDUCE:
                    db, di = instr.dst
                    acc = None
                    for sb, si in instr.srcs:
                        val = refs[sb][si(me, n)]
                        acc = val if acc is None else acc + val
                    refs[db][di(me, n)] = acc
                else:  # pragma: no cover
                    raise NotImplementedError(instr.op)

        prim.device_barrier(bar_sem, axis)  # exit barrier (see kernels/)

    def __call__(self, x: jax.Array) -> jax.Array:
        from repro.core import faults
        from repro.kernels import comm_utils

        inj = faults.active()
        if inj is not None:       # chaos harness hook (trace time only)
            x = inj.on_execute(x)
        p = self.program
        interpret = (comm_utils.interpret_mode() if self.interpret is None
                     else self.interpret)
        n_in = p.chunks[p.in_buffer]
        n_out = p.chunks[p.out_buffer]
        rows = x.shape[0] // n_in
        cols = x.shape[1]
        # Mosaic refuses slices of a partial lane tile or of a partial
        # sublane tile: pad each chunk's rows (``_tile_rows``) and the
        # columns to whole lanes. Every op here moves or adds whole
        # chunks elementwise, so the zero padding is exact.
        rpad = _tile_rows(rows, np.dtype(x.dtype).itemsize) - rows
        cpad = (-cols) % _LANES
        if rpad or cpad:
            x = jnp.pad(x.reshape(n_in, rows, cols),
                        ((0, 0), (0, rpad), (0, cpad)))
        from repro.core import trace as trace_mod
        col = trace_mod.active()
        if col is not None:       # profiler hook (trace time only)
            col.record(self, n=jax.lax.axis_size(self.axis), chunk_rows=rows,
                       cols=cols, dtype=np.dtype(x.dtype).name,
                       backend="pallas")
        # every buffer that is neither the kernel input nor output gets
        # its own VMEM scratch allocation (scratch, acc, ... — composed
        # algorithms may stage through several local buffers)
        local_names = [b for b in p.chunks
                       if b not in (p.in_buffer, p.out_buffer)]
        scratch_shapes: list[Any] = [
            pltpu.VMEM((p.chunks[b], rows + rpad, cols + cpad), x.dtype)
            for b in local_names]
        scratch_shapes.append(pltpu.SemaphoreType.REGULAR)
        scratch_shapes += [pltpu.SemaphoreType.DMA] * (2 * _NUM_SEM_PAIRS)

        def kernel(x_ref, out_ref, *rest):
            locals_refs = dict(zip(local_names, rest[:len(local_names)]))
            bar_sem, *sems = rest[len(local_names):]
            self._kernel(x_ref, out_ref, locals_refs, bar_sem, *sems)

        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(
                (n_out, rows + rpad, cols + cpad), x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=scratch_shapes,
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(
                collective_id=self.collective_id),
        )(x.reshape(1, n_in, rows + rpad, cols + cpad))
        return out[:, :rows, :cols].reshape(n_out * rows, cols)


def execute(program: Program, x: jax.Array, *, axis: str,
            backend: str = "xla", opt_level: Optional[int] = None,
            **kw) -> jax.Array:
    """Run a DSL program on a local shard inside shard_map.

    ``opt_level``: when given, the program is first run through
    ``passes.optimize`` (None = run exactly as passed). Level 0
    additionally selects the reference (non-vectorized) XLA lowering —
    the before/after baseline the benchmarks measure.
    """
    if opt_level is not None:
        from repro.core import passes
        program = passes.optimize(program, opt_level)
    if backend == "xla":
        vectorize = opt_level is None or opt_level > 0
        return XlaExecutor(program, axis, vectorize=vectorize)(x)
    if backend == "pallas":
        return PallasExecutor(program, axis, **kw)(x)
    raise ValueError(f"unknown backend {backend!r}")
