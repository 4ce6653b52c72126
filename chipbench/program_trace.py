"""The program's own marks in a profiler trace: the scheduler's
``sched.*`` host spans with their arguments, and the named scope
(``mask_slots``, ``cache_write``) of each device operation.

``tracing.load`` keeps the harness's spans and bare operation names;
this reads the same file again for what the program adds, so that the
metrics reading it (``step_cache_ms``, ``sample_ms``, ``tick_host_ms``)
leave every older metric as it was. The reductions are pure functions
of plain tuples, driven by tests with synthetic events.

An operation's scope comes from the optimized HLO of its program, which
the trace carries (the ``Hlo Proto`` of each program on its
``/host:metadata`` plane), through each instruction's ``op_name``: the
path ``jax.named_scope`` writes, e.g.
``jit(step_sched)/while/body/closed_call/cache_write/select_n``. A
fusion belongs to a scope when every operation in it that computes lies
under that scope; the layer scan's own reads and writes of its carried
cache (``dynamic-slice``, ``dynamic-update-slice``) and other data
movement do not decide. So the one-hot cache write fused into the
scan's write of the layer's cache is ``cache_write``, while the
attention products that XLA fuses the same select into are not. Where
the trace holds no HLO for a program, the operation's own statistics
decide (its root's ``op_name``, where the trace records it).
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["ProgramTrace", "load", "for_view", "hlo_scopes",
           "self_times", "scope_seconds", "step_kinds",
           "tick_host_seconds", "sample_seconds", "SCOPES"]

SPAN_PREFIX = "sched."
#: the step's named scopes that ``step_cache_ms`` reads
SCOPES = ("mask_slots", "cache_write")
_SCOPE_RE = re.compile(r"(?:^|/)(%s)(?:/|$)" % "|".join(SCOPES))
#: HLO opcodes that move or lay out data and compute nothing
DATA_MOVEMENT = frozenset((
    "parameter", "constant", "bitcast", "broadcast", "reshape", "copy",
    "transpose", "slice", "dynamic-slice", "dynamic-update-slice", "iota",
    "tuple", "get-tuple-element"))

Span = Tuple[float, float, str, dict]    # (start s, end s, name, args)
Op = Tuple[float, float, str, str]       # (start s, end s, name, scope)


@dataclasses.dataclass
class ProgramTrace:
    spans: List[Span]            # the program's sched.* host spans
    ops: List[Op]                # device operations, with their scope
    scope_source: str = ""       # "hlo", an op statistic's name, or ""


def _scope_of(op_name: str) -> str:
    m = _SCOPE_RE.search(op_name)
    return m.group(1) if m else ""


# -- the programs' HLO, from the trace file --------------------------------
def _varint(b, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << shift
        if c < 0x80:
            return v, i
        shift += 7


def _fields(b):
    """(field number, value) of a protobuf message's fields: an int for
    a varint, a memoryview for a length-delimited field, None else."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _ints(v) -> List[int]:
    """A repeated integer field's value: packed (bytes) or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def hlo_scopes(hlo_proto) -> Dict[str, str]:
    """Instruction name -> named scope, for the instructions of one
    serialized ``xla.HloProto`` that lie under a scope (see the module's
    docstring for a fusion's rule)."""
    # computation id -> [(name, opcode, op_name, called computation ids)]
    comps: Dict[int, list] = {}
    for f, mod in _fields(hlo_proto):
        if f != 1:                          # HloProto.hlo_module
            continue
        for f2, comp in _fields(mod):
            if f2 != 3:                     # HloModuleProto.computations
                continue
            cid, instrs = None, []
            for f3, v in _fields(comp):
                if f3 == 5:                 # HloComputationProto.id
                    cid = v
                elif f3 == 2:               # .instructions
                    name = opcode = op_name = ""
                    calls: List[int] = []
                    for f4, w in _fields(v):
                        if f4 == 1:
                            name = bytes(w).decode()
                        elif f4 == 2:
                            opcode = bytes(w).decode()
                        elif f4 == 7:       # OpMetadata
                            for f5, x in _fields(w):
                                if f5 == 2:
                                    op_name = bytes(x).decode()
                        elif f4 == 38:      # called_computation_ids
                            calls += _ints(w)
                    instrs.append((name, opcode, op_name, calls))
            comps[cid] = instrs

    def work(cid, seen):
        """The computing operations of a fused computation, nested
        fusions included."""
        for name, opcode, op_name, calls in comps.get(cid, ()):
            if opcode == "fusion":
                for c in calls:
                    if c not in seen:
                        seen.add(c)
                        yield from work(c, seen)
            elif opcode not in DATA_MOVEMENT:
                yield op_name

    out: Dict[str, str] = {}
    for instrs in comps.values():
        for name, opcode, op_name, calls in instrs:
            scope = _scope_of(op_name)
            if opcode == "fusion":
                inner = {_scope_of(o) for c in calls for o in work(c, {c})}
                if inner:
                    scope = inner.pop() if len(inner) == 1 else ""
            if scope:
                out[name] = scope
    return out


def _program_scopes(path: str) -> Dict[int, Dict[str, str]]:
    """Program id -> ``hlo_scopes`` of each program whose HLO the trace
    holds and names a scope in."""
    with open(path, "rb") as fh:
        space = fh.read()
    out: Dict[int, Dict[str, str]] = {}
    for f, plane in _fields(space):
        if f != 1:                                  # XSpace.planes
            continue
        fields = list(_fields(plane))
        if not any(g == 2 and bytes(v) == b"/host:metadata"
                   for g, v in fields):
            continue
        stat_ids = set()
        for g, entry in fields:
            if g == 5:                              # XPlane.stat_metadata
                for h, meta in _fields(entry):
                    if h == 2 and any(k == 2 and bytes(x) == b"Hlo Proto"
                                      for k, x in _fields(meta)):
                        stat_ids |= {x for k, x in _fields(meta) if k == 1}
        for g, entry in fields:
            if g != 4:                              # XPlane.event_metadata
                continue
            for h, meta in _fields(entry):
                if h != 2:
                    continue
                pid, protos = None, []
                for k, x in _fields(meta):
                    if k == 1:                      # XEventMetadata.id
                        pid = x
                    elif k == 5:                    # .stats
                        st = dict(_fields(x))
                        if st.get(1) in stat_ids and 6 in st:
                            protos.append(bytes(st[6]))
                for proto in protos:
                    if any(s.encode() in proto for s in SCOPES):
                        out[pid] = hlo_scopes(proto)
        break
    return out


def _op_scope(name: str, stats, program_scopes,
              module_id) -> Tuple[str, str]:
    """(scope, its source) of one device operation: by its instruction
    (the ``hlo_op`` statistic, else the event's name, ``%fusion.3 = ...``
    or ``fusion.3``) in its program's HLO."""
    st = dict(stats)
    pid = st.get("program_id", module_id)
    if pid in program_scopes:
        instr = st.get("hlo_op") or re.match(r"%?([^ ]*)", name).group(1)
        return program_scopes[pid].get(instr, ""), "hlo"
    for key, value in stats:
        if isinstance(value, str) and _scope_of(value):
            return _scope_of(value), key
    return "", ""


@functools.lru_cache(maxsize=1)
def _load(path: str, mtime_ns: int, device_prefix: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    try:
        scopes = _program_scopes(path)
    except (ValueError, IndexError) as e:      # a message this reader misparses
        print(f"trace: the programs' HLO was not read ({e!r}); scopes from "
              f"op statistics", file=sys.stderr, flush=True)
        scopes = {}
    pd = ProfileData.from_file(path)
    spans: List[Span] = []
    ops: List[Op] = []
    source = ""
    for plane in pd.planes:
        if plane.name.startswith(device_prefix) and not ops:
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                ((e.start_ns, e.end_ns, _module_id(e.name))
                 for e in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ())),
                key=lambda m: m[:2])
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                scope, src = _op_scope(e.name, e.stats, scopes,
                                       _enclosing(modules, e.start_ns))
                source = source or (src if scope else "")
                s = e.start_ns * 1e-9
                ops.append((s, s + e.duration_ns * 1e-9, e.name, scope))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((s, s + e.duration_ns * 1e-9, e.name,
                                      dict(e.stats)))
    spans.sort(key=_outer_first)
    return ProgramTrace(spans=spans, ops=sorted(ops, key=_outer_first),
                        scope_source=source)


def _module_id(name: str) -> Optional[int]:
    """The program id in a program execution's name, ``jit_f(29)``."""
    m = re.search(r"\((\d+)\)$", name)
    return int(m.group(1)) if m else None


def _enclosing(modules, t_ns) -> Optional[int]:
    """Id of the program execution that holds an operation starting at
    ``t_ns`` (executions do not overlap on one device)."""
    i = bisect.bisect_right(modules, (t_ns, float("inf"))) - 1
    return modules[i][2] if i >= 0 and modules[i][1] >= t_ns else None


def load(trace_dir: str, device_prefix: str = "/device:TPU:") -> ProgramTrace:
    """The program's marks in the newest ``.xplane.pb`` under
    ``trace_dir``, from the same device plane ``tracing.load`` reads
    (the first one with operations). Kept for the last file read."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return _load(files[-1], os.stat(files[-1]).st_mtime_ns, device_prefix)


def _run_trace_dir() -> Optional[str]:
    """The directory the running ``run.run_cell`` traced into: the
    harness hands a metric reader its reduced trace but not the path,
    which is ``run_cell``'s own argument."""
    f = sys._getframe(1)
    while f is not None:
        if f.f_code.co_name == "run_cell" and "trace_dir" in f.f_locals:
            return str(f.f_locals["trace_dir"])
        f = f.f_back
    return None


def for_view(view) -> Optional[ProgramTrace]:
    """The program's marks of a traced run's window, or None in a run
    that was not traced or wrote no trace."""
    if view.traced is None:
        return None
    d = _run_trace_dir()
    if d is None:
        return None
    try:
        return load(d)
    except FileNotFoundError:
        return None


# -- reductions ------------------------------------------------------------
def _outer_first(iv) -> tuple:
    """Sort key: by start, an enclosing interval before those it holds."""
    return (iv[0], -iv[1])


def _inside(spans: Sequence[Span], lo: float, hi: float, name: str):
    return [sp for sp in spans if sp[2] == name and lo <= sp[0]
            and sp[1] <= hi]


def self_times(ops: Sequence[Tuple], lo: float, hi: float) -> List[float]:
    """Each operation's self time inside [lo, hi]: its duration less the
    part its nested operations cover (an operation that runs others,
    such as a ``while``, contains their intervals on the same line), so
    that no device second is counted twice. In the order of ``ops``,
    which must be sorted by start, an enclosing operation before the
    operations it holds."""
    out = [0.0] * len(ops)
    stack: List[int] = []            # indices of the open enclosing ops
    for i, op in enumerate(ops):
        s, e = op[0], op[1]
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        d = max(0.0, min(e, hi) - max(s, lo))
        out[i] += d
        if stack and e <= ops[stack[-1]][1]:
            out[stack[-1]] -= d
        stack.append(i)
    return out


def scope_seconds(ops: Sequence[Op], lo: float, hi: float) -> Dict[str, float]:
    """Device self seconds inside [lo, hi] by named scope."""
    out: Dict[str, float] = {}
    for op, t in zip(ops, self_times(ops, lo, hi)):
        if op[3] and t > 0:
            out[op[3]] = out.get(op[3], 0.0) + t
    return out


def step_kinds(spans: Sequence[Span], lo: float, hi: float) -> List[str]:
    """The kind of each step the program issued inside [lo, hi], in issue
    order, in the harness's words (a combined step is its decode)."""
    return ["decode" if sp[3].get("kind") == "combined" else "prefill"
            for sp in _inside(spans, lo, hi, "sched.step")]


def tick_host_seconds(spans: Sequence[Span], lo: float,
                      hi: float) -> List[float]:
    """Per ``sched.tick`` inside [lo, hi] that ran steps (one that waited
    on the device in ``sched.sync``), its duration less that wait."""
    syncs = _inside(spans, lo, hi, "sched.sync")
    out = []
    for t0, t1, _, _ in _inside(spans, lo, hi, "sched.tick"):
        waits = [e - s for s, e, _, _ in syncs if t0 <= s and e <= t1]
        if waits:
            out.append((t1 - t0) - sum(waits))
    return out


def sample_seconds(spans: Sequence[Span], lo: float,
                   hi: float) -> List[float]:
    """Per tick that emitted a token inside [lo, hi], the seconds of its
    ``sched.sample``."""
    return [e - s for s, e, _, a in _inside(spans, lo, hi, "sched.sample")
            if int(a.get("rows", 0)) > 0]
