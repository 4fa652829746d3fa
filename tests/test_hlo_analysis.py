"""HLO analyzer: trip-count-correct flops/bytes/collectives."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.launch.hlo_analysis import (analyze_hlo_module, collective_bytes,
                                       roofline_terms)


def test_plain_dot_matches_xla():
    f = jax.jit(lambda a, b: a @ b)
    s = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = f.lower(s, s).compile()
    st = analyze_hlo_module(c.as_text())
    ca = c.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    np.testing.assert_allclose(st.flops, ca["flops"], rtol=1e-6)


def test_scan_trip_count_multiplied():
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), ()
        c, _ = jax.lax.scan(body, x, w)
        return c
    xs = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    ws = jax.ShapeDtypeStruct((13, 32, 32), jnp.float32)
    c = jax.jit(f).lower(xs, ws).compile()
    st = analyze_hlo_module(c.as_text())
    np.testing.assert_allclose(st.flops, 13 * 2 * 32 ** 3, rtol=1e-6)
    assert 13 in st.while_trips.values()


def test_nested_scan_trips():
    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return jnp.tanh(c2 @ wi), ()
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, ()
        c, _ = jax.lax.scan(outer, x, w)
        return c
    xs = jax.ShapeDtypeStruct((16, 16), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 16, 16), jnp.float32)
    c = jax.jit(f).lower(xs, ws).compile()
    st = analyze_hlo_module(c.as_text())
    np.testing.assert_allclose(st.flops, 7 * 3 * 2 * 16 ** 3, rtol=1e-6)


def test_collective_regex_on_synthetic_hlo():
    text = """
  %ar = f32[1024,16]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%sum
  %ag = bf16[2048]{0} all-gather(%y), replica_groups=[2,8]<=[16], dimensions={0}
  %rs = f32[128]{0} reduce-scatter(%z), replica_groups={{0,1}}, dimensions={0}
"""
    st = collective_bytes(text)
    assert st.counts == {"all-reduce": 1, "all-gather": 1,
                         "reduce-scatter": 1}
    # all-reduce: 2*(3/4)*1024*16*4
    np.testing.assert_allclose(st.bytes_by_kind["all-reduce"],
                               2 * 0.75 * 1024 * 16 * 4)
    # all-gather over groups of 8: (7/8)*2048*2
    np.testing.assert_allclose(st.bytes_by_kind["all-gather"],
                               (7 / 8) * 2048 * 2)
    # reduce-scatter groups of 2: (2-1)*128*4
    np.testing.assert_allclose(st.bytes_by_kind["reduce-scatter"], 128 * 4)


def test_roofline_bottleneck_selection():
    r = roofline_terms(flops=197e12, hbm_bytes=0, coll_bytes=0,
                       model_flops_total=197e12, n_devices=1)
    assert r.bottleneck == "compute"
    np.testing.assert_allclose(r.compute_s, 1.0)
    np.testing.assert_allclose(r.useful_ratio, 1.0)
    r2 = roofline_terms(flops=1, hbm_bytes=819e9 * 2, coll_bytes=0)
    assert r2.bottleneck == "memory"
    np.testing.assert_allclose(r2.memory_s, 2.0)
    r3 = roofline_terms(flops=1, hbm_bytes=1, coll_bytes=50e9 * 3)
    assert r3.bottleneck == "collective"
    np.testing.assert_allclose(r3.collective_s, 3.0)


def test_sharded_module_collectives_detected():
    n = len(jax.devices())
    if n < 2:
        pytest.skip("needs >=2 devices")
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((n,), ("data",))
    f = jax.jit(lambda a: a.sum(),
                in_shardings=NamedSharding(mesh, P("data")),
                out_shardings=NamedSharding(mesh, P()))
    c = f.lower(jax.ShapeDtypeStruct((n * 8,), jnp.float32)).compile()
    st = analyze_hlo_module(c.as_text())
    assert sum(st.collectives.counts.values()) >= 1


def test_cache_threading_scan_not_overcounted():
    """Decode pattern: per-layer cache DUS inside scan must charge the
    update region, not the full stacked cache, per iteration."""
    import os
    L, B, S, D = 8, 2, 1024, 64

    def f(x, cache):
        def body(c, layer_cache):
            new = jax.lax.dynamic_update_slice(layer_cache, c[:, None, :],
                                               (0, 5, 0))
            return jnp.tanh(c), new
        return jax.lax.scan(body, x, cache)

    xs = jax.ShapeDtypeStruct((B, D), jnp.float32)
    cs = jax.ShapeDtypeStruct((L, B, S, D), jnp.float32)
    comp = jax.jit(f, donate_argnums=(1,)).lower(xs, cs).compile()
    st = analyze_hlo_module(comp.as_text())
    full_cache = L * B * S * D * 4
    # L x full-cache-per-iteration (the bug) would be ~2x this bound;
    # one-time donation copies/initialisation stay well under it.
    assert st.bytes < 7.5 * full_cache, st.bytes


# --------------------------------------------------------------------------
# synthetic-HLO regressions: the parser paths that real jax traces only
# exercise incidentally (trip-count recovery, iota replica_groups inside
# a multiplied body, fusion multiplicity vs fused-internal bytes)
# --------------------------------------------------------------------------

_SYNTH_WHILE = """
HloModule synth_while

%cond (p: (f32[4,4], s32[])) -> pred[] {
  %p = (f32[4,4], s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=1
  %lim = s32[] constant(11)
  ROOT %lt = pred[] compare(%i, %lim), direction=LT
}

%body (q: (f32[4,4], s32[])) -> (f32[4,4], s32[]) {
  %q = (f32[4,4], s32[]) parameter(0)
  %x = f32[4,4]{1,0} get-tuple-element(%q), index=0
  %d = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %j = s32[] get-tuple-element(%q), index=1
  %one = s32[] constant(1)
  %n = s32[] add(%j, %one)
  ROOT %t = (f32[4,4], s32[]) tuple(%d, %n)
}

ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (f32[4,4], s32[]) tuple(%a, %z)
  %w = (f32[4,4], s32[]) while(%t0), condition=%cond, body=%body
  ROOT %r = f32[4,4]{1,0} get-tuple-element(%w), index=0
}
"""


def test_synthetic_while_trip_recovery():
    """Trip count comes from the loop-condition constant (11), NOT the
    body's own constant(1) — and multiplies the body's dot flops."""
    st = analyze_hlo_module(_SYNTH_WHILE)
    assert st.while_trips == {"body": 11}
    np.testing.assert_allclose(st.flops, 11 * 2 * 4 * 4 * 4)


_SYNTH_COLL_WHILE = """
HloModule synth_coll

%ccond (p: (f32[2048], s32[])) -> pred[] {
  %p = (f32[2048], s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=1
  %lim = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %lim), direction=LT
}

%cbody (q: (f32[2048], s32[])) -> (f32[2048], s32[]) {
  %q = (f32[2048], s32[]) parameter(0)
  %x = f32[2048]{0} get-tuple-element(%q), index=0
  %ar = f32[2048]{0} all-reduce(%x), replica_groups=[4,8]<=[32], to_apply=%sum
  %j = s32[] get-tuple-element(%q), index=1
  %one = s32[] constant(1)
  %n = s32[] add(%j, %one)
  ROOT %t = (f32[2048], s32[]) tuple(%ar, %n)
}

ENTRY %cmain (a: f32[2048]) -> f32[2048] {
  %a = f32[2048]{0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (f32[2048], s32[]) tuple(%a, %z)
  %w = (f32[2048], s32[]) while(%t0), condition=%ccond, body=%cbody
  ROOT %r = f32[2048]{0} get-tuple-element(%w), index=0
}
"""


def test_synthetic_iota_replica_groups_in_while():
    """Iota-form replica_groups=[4,8]<=[32] means groups of EIGHT (the
    second factor), and a collective in a trip-3 body is charged 3x."""
    st = analyze_hlo_module(_SYNTH_COLL_WHILE)
    assert st.collectives.counts == {"all-reduce": 3}
    per_call = 2.0 * (8 - 1) / 8 * 2048 * 4       # ring all-reduce, G=8
    np.testing.assert_allclose(st.collectives.bytes_by_kind["all-reduce"],
                               3 * per_call)


_SYNTH_FUSION_WHILE = """
HloModule synth_fusion

%fcomp (fp: f32[4,4]) -> f32[4,4] {
  %fp = f32[4,4]{1,0} parameter(0)
  ROOT %fd = f32[4,4]{1,0} dot(%fp, %fp), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%fcond (p: (f32[4,4], s32[])) -> pred[] {
  %p = (f32[4,4], s32[]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=1
  %lim = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %lim), direction=LT
}

%fbody (q: (f32[4,4], s32[])) -> (f32[4,4], s32[]) {
  %q = (f32[4,4], s32[]) parameter(0)
  %x = f32[4,4]{1,0} get-tuple-element(%q), index=0
  %f = f32[4,4]{1,0} fusion(%x), kind=kLoop, calls=%fcomp
  %j = s32[] get-tuple-element(%q), index=1
  %one = s32[] constant(1)
  %n = s32[] add(%j, %one)
  ROOT %t = (f32[4,4], s32[]) tuple(%f, %n)
}

ENTRY %fmain (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (f32[4,4], s32[]) tuple(%a, %z)
  %w = (f32[4,4], s32[]) while(%t0), condition=%fcond, body=%fbody
  ROOT %r = f32[4,4]{1,0} get-tuple-element(%w), index=0
}
"""


def test_synthetic_fusion_multiplicity_and_fused_bytes():
    """A dot reached through fusion -> calls= inside a trip-5 while is
    charged 5x flops, while the fusion INTERNAL ops contribute no HBM
    bytes (register traffic) — only the fusion's own result + params."""
    st = analyze_hlo_module(_SYNTH_FUSION_WHILE)
    assert st.while_trips == {"fbody": 5}
    np.testing.assert_allclose(st.flops, 5 * 2 * 4 * 4 * 4)
    # bytes: fusion charges result(64) + param(64) per call = 128/call;
    # the s32 add is 12/call; the cond compare (1+4+4)=9 runs trips+1
    # times.  If fused internals leaked in, the dot would add >= 192/call.
    expected = 5 * (128 + 12) + 6 * 9
    np.testing.assert_allclose(st.bytes, expected)
