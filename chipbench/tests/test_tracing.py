"""The reductions of the program's own trace marks (``program_trace``)
on small synthetic events, the readers of ``step_cache_ms``,
``sample_ms`` and ``tick_host_ms``, and a traced run on the CPU that
reads the scheduler's spans."""
import importlib.util
import pathlib
import sys
import types

import jax
import pytest

HERE = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import program_trace as pt  # noqa: E402
import run  # noqa: E402
import tracing  # noqa: E402
from small import PEAKS, small_cell  # noqa: E402


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"test_metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# Two ticks on the host. The first admits, runs a prefill micro-step and
# the combined step, waits for the logits, samples two rows and releases
# one; the second runs one combined step and emits nothing.
SPANS = [
    (1.00, 1.10, "sched.tick", {}),
    (1.00, 1.01, "sched.admit", {"rids": "4 5"}),
    (1.01, 1.02, "sched.step", {"kind": "prefill", "bucket": 2}),
    (1.02, 1.03, "sched.step", {"kind": "combined", "bucket": 2}),
    (1.03, 1.08, "sched.sync", {}),
    (1.08, 1.09, "sched.sample", {"rows": 2, "sampled": 1}),
    (1.09, 1.10, "sched.release", {"rids": 5}),
    (1.20, 1.26, "sched.tick", {}),
    (1.20, 1.20, "sched.admit", {}),
    (1.20, 1.21, "sched.step", {"kind": "combined", "bucket": 1}),
    (1.21, 1.25, "sched.sync", {}),
    (1.25, 1.255, "sched.sample", {"rows": 0, "sampled": 0}),
    (1.255, 1.26, "sched.release", {}),
]


def test_step_kinds_in_issue_order():
    assert pt.step_kinds(SPANS, 0.0, 2.0) == ["prefill", "decode", "decode"]
    # a window that cuts the second tick keeps the first tick's steps
    assert pt.step_kinds(SPANS, 0.9, 1.15) == ["prefill", "decode"]


def test_program_kinds_pair_the_step_programs():
    """The program's kinds label the step executions as the harness's
    reconstruction from TickInfo does: (micro-steps, bucket) per tick."""
    mods = [(1.011, 1.019, "jit_step_sched"), (1.022, 1.05, "jit_step_sched"),
            (1.05, 1.051, "jit_convert"), (1.211, 1.24, "jit_step_sched")]
    from_ticks = []
    for micro, bucket in [(1, 2), (0, 1)]:
        from_ticks += ["prefill"] * micro + (["decode"] if bucket else [])
    kinds = pt.step_kinds(SPANS, 0.0, 2.0)
    assert kinds == from_ticks
    assert tracing.label_modules(mods, 0.0, 2.0, kinds, "jit_step") == \
        tracing.label_modules(mods, 0.0, 2.0, from_ticks, "jit_step")


def test_tick_host_and_sample_seconds():
    assert pt.tick_host_seconds(SPANS, 0.0, 2.0) == \
        pytest.approx([0.10 - 0.05, 0.06 - 0.04])
    # only the tick that emitted counts for sampling
    assert pt.sample_seconds(SPANS, 0.0, 2.0) == pytest.approx([0.01])


def test_idle_goes_to_the_innermost_program_span():
    """Harness ``tick`` around the program's spans: each gap is named by
    the innermost span over its midpoint."""
    ops = [(1.011, 1.019, "a"), (1.022, 1.079, "b"), (1.105, 1.20, "c"),
           (1.211, 1.24, "d")]
    harness = [(0.999, 1.101, "tick"), (1.199, 1.261, "tick")]
    gaps = tracing.idle_gaps(ops, 1.0, 1.26)
    assert tracing.attribute_gaps(gaps, harness) == pytest.approx(
        {"tick": 0.071})
    named = tracing.attribute_gaps(gaps, harness + [s[:3] for s in SPANS])
    assert named == pytest.approx({
        "sched.admit": 0.011, "sched.step": 0.003 + 0.011,
        "sched.release": 0.026, "sched.sample": 0.02})


# A step with a layer scan: ``while`` holds the per-layer ops, two of
# them under ``cache_write``; ``mask_slots`` runs after the scan.
OPS = sorted([
    (0.0, 10.0, "while.18", ""),
    (1.0, 2.0, "fusion.1", ""),
    (2.0, 3.0, "select_dynamic-update-slice_fusion.3", "cache_write"),
    (5.0, 6.0, "fusion.1", ""),
    (6.0, 7.5, "select_dynamic-update-slice_fusion.3", "cache_write"),
    (10.0, 13.0, "broadcast_select_fusion.2", "mask_slots"),
    (13.0, 14.0, "fusion.9", ""),
], key=lambda o: (o[0], -o[1]))
OPS3 = [o[:3] for o in OPS]              # as ``tracing`` keeps them


def test_self_time_counts_nested_ops_once():
    st = pt.self_times(OPS, 0.0, 20.0)
    by = dict(zip([o[2] + str(o[0]) for o in OPS], st))
    assert by["while.180.0"] == pytest.approx(10.0 - 4.5)
    assert sum(st) == pytest.approx(tracing.union_seconds(OPS3, 0.0, 20.0))
    # the old per-name sum counts the scan's body twice
    assert sum(t for _, t in tracing.top_ops(OPS3, 0.0, 20.0)) > sum(st)


def test_self_time_clipped_to_the_window():
    st = pt.self_times(OPS, 2.5, 11.0)
    assert sum(st) == pytest.approx(tracing.union_seconds(OPS3, 2.5, 11.0))
    assert all(t >= 0 for t in st)


def test_scope_seconds_are_self_times():
    assert pt.scope_seconds(OPS, 0.0, 20.0) == pytest.approx(
        {"cache_write": 2.5, "mask_slots": 3.0})
    assert pt.scope_seconds(OPS, 0.0, 10.0) == pytest.approx(
        {"cache_write": 2.5})


def _msg(*fields) -> bytes:
    """A protobuf message from (field number, int | bytes | str) pairs."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _instr(name, opcode, op_name, calls=()):
    return _msg((1, name), (2, opcode), (7, _msg((1, "x"), (2, op_name))),
                *[(38, c) for c in calls])


def _hlo_proto():
    """A step's HLO as XLA fuses it: the one-hot write fused into the
    layer scan's write of its cache (root outside any scope), the same
    select fused into an attention product, and the slot mask."""
    body = "jit(step_sched)/while/body"
    write = f"{body}/closed_call/cache_write/jit(_where)/select_n"
    comps = [
        (1, [_instr("p", "parameter", ""),
             _instr("ds", "dynamic-slice", f"{body}/dynamic_slice"),
             _instr("sel", "select", write),
             _instr("dus", "dynamic-update-slice",
                    f"{body}/dynamic_update_slice")]),
        (2, [_instr("sel2", "select", write),
             _instr("dot", "convolution", f"{body}/closed_call/dot_general")]),
        (3, [_instr("sel3", "select",
                    "jit(step_sched)/mask_slots/jit(_where)/select_n"),
             _instr("b", "broadcast", "jit(step_sched)/mask_slots")]),
        (4, [_instr("select_dynamic-update-slice_fusion.3", "fusion",
                    f"{body}/dynamic_update_slice", [1]),
             _instr("fusion.469", "fusion", f"{body}/closed_call/dot", [2]),
             _instr("add.1", "add", f"{body}/closed_call/add")]),
        (5, [_instr("while.18", "while", "jit(step_sched)/while", [4]),
             _instr("broadcast_select_fusion.2", "fusion",
                    "jit(step_sched)/mask_slots/jit(_where)/select_n", [3]),
             _instr("select.9", "select",
                    "jit(step_sched)/mask_slots/select_n")]),
    ]
    module = _msg((1, "jit_step_sched"), *[
        (3, _msg((1, f"c{cid}"), *[(2, i) for i in instrs], (5, cid)))
        for cid, instrs in comps])
    return _msg((1, module))


def test_hlo_scopes_judge_a_fusion_by_what_it_computes():
    assert pt.hlo_scopes(_hlo_proto()) == {
        "select_dynamic-update-slice_fusion.3": "cache_write",
        "broadcast_select_fusion.2": "mask_slots", "select.9": "mask_slots",
        # inside the fused computations; no operation of the trace's
        "sel": "cache_write", "sel2": "cache_write", "sel3": "mask_slots",
        "b": "mask_slots"}


def test_program_scopes_from_the_trace_file(tmp_path):
    """The programs' HLO that a profiler trace carries names the step's
    scoped instructions, by program id."""
    import jax.numpy as jnp

    def body(c, x):
        with jax.named_scope("cache_write"):
            c = jnp.where(x > 0, c * 2, c)
        return c + 1, None

    @jax.jit
    def step_sched(c, xs):
        c, _ = jax.lax.scan(body, c, xs)
        with jax.named_scope("mask_slots"):
            return jnp.where(c > 3, c, -c)

    args = jnp.ones((8, 8)), jnp.ones((3, 8, 8))
    step_sched(*args).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        step_sched(*args).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    progs = pt._program_scopes(str(path))
    assert len(progs) == 1
    (pid, scopes), = progs.items()
    assert set(scopes.values()) == {"cache_write", "mask_slots"}
    name = next(n for n, s in scopes.items() if s == "mask_slots")
    # an operation of that program, named by its statistics or its event
    stats = [("hlo_op", name), ("hlo_module", "jit_step_sched"),
             ("program_id", pid)]
    assert pt._op_scope(name, stats, progs, None) == ("mask_slots", "hlo")
    assert pt._op_scope(f"%{name} = f32[8,8] fusion(...)", [], progs, pid) \
        == ("mask_slots", "hlo")
    assert pt._op_scope("add.1", [("program_id", pid)], progs, None) == \
        ("", "hlo")


def _xspace() -> bytes:
    """A TPU trace as the profiler writes it: one step program's execution
    and its operations on the device plane, the program's HLO on the
    metadata plane, and one ``sched.step`` span on the host."""
    def plane(name, lines=(), events=(), stats=()):
        return _msg((2, name), *[(3, ln) for ln in lines],
                    *[(4, _msg((1, i), (2, _msg((1, i), (2, n), *st))))
                      for i, n, st in events],
                    *[(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
                      for i, n in stats])

    def line(name, t0_ns, evs):
        return _msg((2, name), (3, t0_ns), *[
            (4, _msg((1, md), (2, off_us * 10**6), (3, dur_us * 10**6), *st))
            for md, off_us, dur_us, st in evs])

    pid = _msg((1, 1), (4, 29))                  # program_id 29
    device = plane(
        "/device:TPU:0",
        lines=[line("XLA Modules", 10**9, [(1, 0, 20, [])]),
               line("XLA Ops", 10**9, [
                   (2, 0, 10, [(4, pid)]), (3, 2, 1, [(4, pid)]),
                   (4, 4, 2, [(4, pid)]), (5, 10, 3, [])])],
        events=[(1, "jit_step_sched(29)", []),
                (2, "%while.18 = (s32[]) while(%t)", []),
                (3, "%select_dynamic-update-slice_fusion.3 = bf16[2] fusion",
                 []),
                (4, "%fusion.469 = f32[2] fusion(%a)", []),
                (5, "%broadcast_select_fusion.2 = bf16[2] fusion(%b)", [])],
        stats=[(1, "program_id")])
    meta = plane("/host:metadata", events=[
        (29, "jit_step_sched(29)", [(5, _msg((1, 1), (6, _hlo_proto())))])],
        stats=[(1, "Hlo Proto")])
    host = plane("/host:CPU", lines=[line("python", 10**9, [
        (1, 0, 1, [(4, _msg((1, 2), (5, "combined"))),
                   (4, _msg((1, 3), (4, 8)))])])],
        events=[(1, "sched.step", [])], stats=[(2, "kind"), (3, "bucket")])
    return _msg((1, meta), (1, device), (1, host))


def test_load_a_tpu_trace(tmp_path):
    (tmp_path / "t.xplane.pb").write_bytes(_xspace())
    prog = pt.load(str(tmp_path))
    assert prog.scope_source == "hlo"
    assert [(round(s - 1.0, 9), n) for s, _, n, _ in prog.spans] == \
        [(0.0, "sched.step")]
    assert prog.spans[0][3] == {"kind": "combined", "bucket": 8}
    scopes = {o[2].split(" ")[0]: o[3] for o in prog.ops}
    assert scopes == {"%while.18": "", "%fusion.469": "",
                      "%select_dynamic-update-slice_fusion.3": "cache_write",
                      # no program_id statistic: the execution around it
                      "%broadcast_select_fusion.2": "mask_slots"}
    assert pt.scope_seconds(prog.ops, 0.0, 2.0) == pytest.approx(
        {"cache_write": 1e-6, "mask_slots": 3e-6})


def test_unreadable_hlo_leaves_op_statistics(tmp_path, monkeypatch, capsys):
    """A trace whose HLO this reader cannot parse still loads."""
    def bad(path):
        raise ValueError("protobuf wire type 3 at byte 7")

    monkeypatch.setattr(pt, "_program_scopes", bad)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("sched.tick"):
            jax.numpy.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    prog = pt.load(str(tmp_path), device_prefix="/device:CPU:")
    assert [s[2] for s in prog.spans] == ["sched.tick"]
    assert "HLO was not read" in capsys.readouterr().err


def test_scope_from_op_statistics_without_hlo():
    """Where the trace holds no HLO for a program, an operation's own
    ``op_name`` statistic decides."""
    assert pt._op_scope("fusion.3", [("hlo_op", "fusion.3"), (
        "tf_op", "jit(step_sched)/while/body/cache_write/select_n")], {},
        None) == ("cache_write", "tf_op")
    # a scope name inside another word is no scope
    assert pt._op_scope("f", [("tf_op", "jit(f)/not_mask_slots_x/add")],
                        {}, None) == ("", "")
    assert pt._op_scope("f", [("program_id", 7)], {}, 7) == ("", "")


def test_program_ids_of_operations():
    mods = [(0, 10, 29), (12, 20, 31)]
    assert [pt._enclosing(mods, t) for t in (0, 5, 11, 12, 25)] == \
        [29, 29, None, 31, None]
    assert pt._module_id("jit_step_sched(29)") == 29
    assert pt._module_id("jit_step_sched") is None


def _view(spans=SPANS, ops=OPS, modules=(), ticks=()):
    traced = types.SimpleNamespace(
        lo=0.0, hi=20.0, ticks=list(ticks),
        trace=types.SimpleNamespace(modules=list(modules)))
    return types.SimpleNamespace(traced=traced), pt.ProgramTrace(
        spans=list(spans), ops=list(ops), scope_source="hlo")


def test_readers(monkeypatch):
    view, prog = _view(modules=[(0.0, 10.0, "jit_step_sched"),
                                (10.0, 14.0, "jit_step_sched"),
                                (15.0, 16.0, "jit_step_prefill(3)")],
                       ticks=[(0, 1, 1, 2, []), (2, 3, 0, 1, [])])
    monkeypatch.setattr(pt, "for_view", lambda v: prog)
    assert _reader("sample_ms")(view) == pytest.approx(10.0)
    assert _reader("tick_host_ms")(view) == pytest.approx((50 + 20) / 2)
    # 5.5 s under the two scopes, over three step executions
    assert _reader("step_cache_ms")(view) == pytest.approx(1e3 * 5.5 / 3)


def test_readers_find_nothing_without_program_marks(monkeypatch):
    """A trace of a program without spans or scopes: no value, no error."""
    view, prog = _view(spans=[], ops=[(o[0], o[1], o[2], "") for o in OPS],
                       modules=[(0.0, 10.0, "jit_step")])
    monkeypatch.setattr(pt, "for_view", lambda v: prog)
    for name in ("sample_ms", "tick_host_ms", "step_cache_ms"):
        assert _reader(name)(view) is None
    monkeypatch.setattr(pt, "for_view", lambda v: None)
    for name in ("sample_ms", "tick_host_ms", "step_cache_ms"):
        assert _reader(name)(view) is None


def test_traced_run_reads_program_spans(tmp_path):
    cell = small_cell("qwen3-1.7b.chat")
    res, code = run.run_cell(cell, seed=2**31 + 29, seconds=1.0, trace=True,
                             devices=jax.devices()[:1], peaks=PEAKS,
                             trace_dir=tmp_path / "trace")
    assert code == 0 and res["correct"]
    got = res["metrics"]
    assert {"sample_ms.online", "tick_host_ms.online"} <= set(got)
    # the host's own share of a tick is less than the tick
    assert 0 < got["tick_host_ms.online"]["value"] \
        <= got["tick_ms.online"]["value"]
    # the CPU's operations carry no scope
    assert "step_cache_ms.online" not in got
