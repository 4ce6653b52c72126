"""Roofline machinery: HLO analyzer (trip counts, dot flops, collective
bytes, ICI/DCN split) against crafted HLO and real compiled programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.roofline import analysis, hlo_parse


def _mesh4():
    # Auto is the modern default; legacy jax has no axis_types at all.
    axis_types = (jax.sharding.AxisType.Auto,) \
        if hasattr(jax.sharding, "AxisType") else None
    return jax.make_mesh((4,), ("x",), axis_types=axis_types)


def test_dot_flops_exact():
    mesh = _mesh4()
    A = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
    B = jax.ShapeDtypeStruct((512, 256), jnp.float32)
    f = jax.jit(lambda a, b: a @ b,
                in_shardings=(NamedSharding(mesh, P("x", None)),
                              NamedSharding(mesh, P(None, None))))
    st = hlo_parse.analyze(f.lower(A, B).compile().as_text())
    assert st.flops == pytest.approx(2 * 1024 * 512 * 256 / 4, rel=0.01)


def test_scan_trip_count_multiplies():
    mesh = _mesh4()
    A = jax.ShapeDtypeStruct((256, 256), jnp.float32)

    def scan_mm(a):
        def body(x, _):
            return jnp.tanh(x @ x), ()
        y, _ = jax.lax.scan(body, a, None, length=13)
        return y

    f = jax.jit(scan_mm, in_shardings=NamedSharding(mesh, P(None, None)))
    st = hlo_parse.analyze(f.lower(A).compile().as_text())
    assert st.flops == pytest.approx(13 * 2 * 256 ** 3, rel=0.01)


def test_collective_bytes_counted():
    mesh = _mesh4()
    A = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    # out_shardings pins the replicated output; without it some jax
    # versions let SPMD propagation keep the output sharded and elide
    # the all-gather this test is about.
    f = jax.jit(lambda a: jax.lax.with_sharding_constraint(
        a, NamedSharding(mesh, P(None, None))),
        in_shardings=NamedSharding(mesh, P("x", None)),
        out_shardings=NamedSharding(mesh, P(None, None)))
    st = hlo_parse.analyze(f.lower(A).compile().as_text())
    assert st.coll["all-gather"] == pytest.approx(1024 * 1024 * 4, rel=0.01)
    assert st.coll["ici"] > 0 and st.coll["dcn"] == 0


def test_dcn_split_by_replica_groups():
    hlo = """
ENTRY %main (p: f32[256]) -> f32[256] {
  %p = f32[256]{0} parameter(0)
  %ar1 = f32[256]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %ar2 = f32[256]{0} all-reduce(%ar1), replica_groups={{0,256},{1,257}}, to_apply=%add
}
%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}
"""
    st = hlo_parse.analyze(hlo, pod_boundary=256)
    assert st.coll["ici"] == pytest.approx(1024)   # group within pod 0
    assert st.coll["dcn"] == pytest.approx(1024)   # group crosses 256


def test_roofline_report_terms():
    hw = analysis.hardware_for("TPU v5 lite")
    rep = analysis.RooflineReport(
        arch="a", cell="c", mesh="m", chips=256,
        hlo_flops=1e15, hlo_bytes=1e12, coll_ici_bytes=1e11,
        coll_dcn_bytes=0.0, model_flops=8e14,
        compute_s=1e15 / hw.peak_flops,
        memory_s=1e12 / hw.hbm_bw,
        collective_s=1e11 / (hw.ici_bw * hw.ici_links))
    assert rep.dominant == "compute"
    assert 0 < rep.roofline_fraction <= 1
    assert rep.useful_flop_ratio == pytest.approx(0.8)


def test_hardware_table_is_keyed_by_device_kind():
    assert analysis.hardware_for("TPU v5 lite").peak_flops == 197e12
    with pytest.raises(ValueError, match="no peak rates"):
        analysis.hardware_for("cpu")


def test_nested_scan_multiplies():
    """Chunked attention inside a layer scan: trip counts compose."""
    mesh = _mesh4()
    A = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def nested(a):
        def outer(x, _):
            def inner(y, _):
                return jnp.tanh(y @ y), ()
            y, _ = jax.lax.scan(inner, x, None, length=3)
            return y, ()
        x, _ = jax.lax.scan(outer, a, None, length=5)
        return x

    f = jax.jit(nested, in_shardings=NamedSharding(mesh, P(None, None)))
    st = hlo_parse.analyze(f.lower(A).compile().as_text())
    assert st.flops == pytest.approx(15 * 2 * 128 ** 3, rel=0.02)
