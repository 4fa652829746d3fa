"""The serving kernels compiled through the stack — differential matrix.

Flash attention, decode attention, and the Mamba SSD scan expressed as
TensorIR (``frontend.flash_attention_graph`` & friends), lowered through
the PassManager pipeline under every legal schedule, and executed through
``backend_ref`` / ``backend_jax`` / the general pallas emitter.  Every
cell of the matrix is checked against a closed-form numpy oracle
(softmax attention / the scan recurrence written directly), and decode
attention also against the served Mosaic kernel
(``repro/kernels/decode_attention.py``) on the corresponding input
slice, within 1e-4 in fp32.

Also here: the property-based reduce/scan printer/parser/verifier tests
(print→parse→print fixpoint, line-numbered diagnostics on malformed
carry shapes, canonicalize idempotence) and the DSE acceptance check
(non-empty Pareto frontier on flash and ssd whose top points cosim).
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.frontend as fe
from repro.core import backend_ref, ir_text, pipeline, schedule
from repro.core.ir_text import IRParseError
from repro.core.lowering import LoweringOptions, lower_graph

NEG = -1e30
TOL = dict(rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# oracles + graph-input builders
# --------------------------------------------------------------------------


def _attn_mask(sq, sk, causal=True, window=None, valid=None):
    """The additive mask input (0 attendable / -1e30 masked): query t
    sits at cache position t+(sk-sq)."""
    qpos = np.arange(sq)[:, None] + (sk - sq)
    kpos = np.arange(sk)[None, :]
    keep = np.ones((sq, sk), bool)
    if causal:
        keep &= kpos <= qpos
    if window is not None:
        keep &= kpos > qpos - window
    if valid is not None:
        keep &= kpos < valid
    return np.where(keep, 0.0, NEG).astype(np.float32)


def _softmax_oracle(qs, kt, v, mask):
    """Closed-form softmax attention on the graph's own inputs."""
    s = qs.astype(np.float64) @ kt + mask
    m = s.max(axis=1, keepdims=True)
    p = np.exp(s - m)
    return ((p @ v) / p.sum(axis=1, keepdims=True)).astype(np.float32)


def _scan_oracle(a, u, ct, g):
    """Sequential h_t = a_t*h_{t-1} + u_t, then (h*ct) @ g."""
    h = np.zeros_like(u[0], dtype=np.float64)
    hs = np.empty(u.shape, np.float64)
    for t in range(u.shape[0]):
        h = a[t] * h + u[t]
        hs[t] = h
    return ((hs * ct) @ g).astype(np.float32)


def _flash_case(sq, sk, d, seed=0, window=None):
    """Graph + inputs on the same data."""
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((sq, d)).astype(np.float32)
    k = rng.standard_normal((sk, d)).astype(np.float32)
    v = rng.standard_normal((sk, d)).astype(np.float32)
    graph = fe.flash_attention_graph(sq, sk, d)
    inputs = [q / np.sqrt(d).astype(np.float32), k.T.copy(), v,
              _attn_mask(sq, sk, causal=True, window=window)]
    return graph, inputs


def _ssd_case(s, p, n, head, seed=0):
    """Graph + one head's inputs of a multi-head SSD problem."""
    H = head + 1
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((s, H, p)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (s, H)).astype(np.float32)
    A = rng.uniform(-1.0, -0.1, (H,)).astype(np.float32)
    B = rng.standard_normal((s, n)).astype(np.float32)
    C = rng.standard_normal((s, n)).astype(np.float32)
    graph = fe.ssd_scan_graph(s, p, n)
    a = np.repeat(np.exp(dt[:, head] * A[head])[:, None], p * n, axis=1)
    u = ((dt[:, head, None] * x[:, head, :])[:, :, None]
         * B[:, None, :]).reshape(s, p * n)
    ct = np.broadcast_to(C[:, None, :], (s, p, n)).reshape(s, p * n).copy()
    g = np.kron(np.eye(p), np.ones((n, 1))).astype(np.float32)
    inputs = [a.astype(np.float32), u.astype(np.float32), ct, g]
    return graph, inputs


def _compile_and_check(graph, inputs, pipe, hand=None):
    """One matrix cell: compile under ``pipe`` and check every backend
    against the numpy oracle, and against ``hand`` (a served kernel's
    answer) where given."""
    oracle = (_softmax_oracle(*inputs) if graph.name.startswith(("flash",
                                                                 "decode"))
              else _scan_oracle(*inputs))
    if hand is not None:
        np.testing.assert_allclose(hand, oracle, **TOL)

    ck = pipeline.compile_traced(graph, pipeline=pipe)
    (ref,) = ck.run_ref(*inputs)
    np.testing.assert_allclose(ref, oracle, **TOL)
    (jx,) = ck.run_jax(*inputs)
    np.testing.assert_allclose(np.asarray(jx), oracle, **TOL)
    assert ck.run_pallas is not None, \
        f"pallas emitter refused legal schedule {pipe!r}"
    pal = np.asarray(ck.run_pallas(*inputs))
    np.testing.assert_allclose(pal, oracle, **TOL)
    if hand is not None:
        np.testing.assert_allclose(pal, hand, **TOL)
    return ck


def _pipe(template, tile):
    tm, tn, tk = tile
    return template.format(t=f"tile_m={tm},tile_n={tn},tile_k={tk}")


# every legal schedule family for a carried-reduction kernel; the ssd
# list stops before grid{vars=2}, which would grid the scan's time axis
# (pinned as a diagnostic in tests/test_loop_ir_passes.py)
ATTN_PIPES = [
    "lower{{{t}}}",
    "lower{{{t}}},fuse-epilogue",
    "lower{{{t}}},fuse-epilogue,grid{{vars=1}}",
    "lower{{{t}}},fuse-epilogue,grid{{vars=2}}",
]
SSD_PIPES = ATTN_PIPES[:3]

FLASH_SIZES = [
    pytest.param((8, 16, 4), (4, 4, 4), id="small"),
    pytest.param((16, 32, 8), (8, 8, 4), id="medium",
                 marks=pytest.mark.slow),
]
SSD_SIZES = [
    pytest.param((8, 2, 2), (4, 4, 4), id="small"),
    pytest.param((16, 2, 4), (8, 8, 8), id="medium",
                 marks=pytest.mark.slow),
]


# --------------------------------------------------------------------------
# the differential matrix
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dims,tile", FLASH_SIZES)
@pytest.mark.parametrize("sched", ATTN_PIPES)
def test_flash_matrix(dims, tile, sched):
    graph, inputs = _flash_case(*dims)
    _compile_and_check(graph, inputs, _pipe(sched, tile))


def test_flash_window_mask_is_data():
    """A local-window mask is just different mask *data* — the compiled
    artifact is bit-for-bit the same pipeline."""
    graph, inputs = _flash_case(8, 16, 4, window=4)
    _compile_and_check(graph, inputs, _pipe(ATTN_PIPES[1], (4, 4, 4)))


@pytest.mark.parametrize("sched", [ATTN_PIPES[0], ATTN_PIPES[3]])
def test_decode_matrix(sched):
    """Decode attention: per-(batch, kv-group) slice of the hand kernel
    vs the compiled graph, KV-cache validity arriving as mask data."""
    from repro.kernels.decode_attention import decode_attention

    B, KV, rep, smax, hd = 2, 2, 4, 16, 4
    rng = np.random.default_rng(3)
    q = rng.standard_normal((B, KV, rep, hd)).astype(np.float32)
    k = rng.standard_normal((B, KV, smax, hd)).astype(np.float32)
    v = rng.standard_normal((B, KV, smax, hd)).astype(np.float32)
    valid = np.array([smax, smax // 2 + 1], np.int32)
    hand = np.asarray(decode_attention(q, k, v, valid, interpret=True))

    for b, g in ((0, 0), (1, 1)):
        graph = fe.decode_attention_graph(rep, smax, hd)
        inputs = [q[b, g] / np.sqrt(hd).astype(np.float32),
                  k[b, g].T.copy(), v[b, g],
                  _attn_mask(rep, smax, causal=False, valid=valid[b])]
        _compile_and_check(graph, inputs, _pipe(sched, (4, 4, 4)),
                           hand[b, g])


@pytest.mark.parametrize("dims,tile", SSD_SIZES)
@pytest.mark.parametrize("sched", SSD_PIPES)
def test_ssd_matrix(dims, tile, sched):
    graph, inputs = _ssd_case(*dims, head=1)
    _compile_and_check(graph, inputs, _pipe(sched, tile))


# --------------------------------------------------------------------------
# reproc: the driver exposes the kernels, and --emit=loop round-trips
# --------------------------------------------------------------------------


@pytest.mark.parametrize("kspec", ["flash:8x16x4", "decode:4x8x4",
                                   "ssd:8x2x4"])
def test_reproc_kernel_emit_loop_fixpoint(kspec):
    import io

    from repro.core import reproc

    buf = io.StringIO()
    assert reproc.main(["--kernel", kspec, "--emit", "loop"], out=buf) == 0
    text = buf.getvalue()
    kern = ir_text.parse_ir(text)
    assert ir_text.print_ir(kern) + "\n" == text


def test_reproc_kernel_flag_conflicts_and_typos():
    import io

    from repro.core import reproc

    assert reproc.main(["--kernel", "flash", "--gemm", "4x4x4"],
                       out=io.StringIO()) == 2
    # unknown kernel names are a usage diagnostic (exit 2, with a
    # did-you-mean hint — see test_sharing.py); bad dims stay exit 1
    assert reproc.main(["--kernel", "mamba"], out=io.StringIO()) == 2
    assert reproc.main(["--kernel", "ssd:2x2"], out=io.StringIO()) == 1


# --------------------------------------------------------------------------
# property-based: printer/parser/verifier on the new carried ops
# --------------------------------------------------------------------------


@settings(max_examples=12, deadline=None)
@given(sq=st.sampled_from([2, 4, 8]), sk=st.sampled_from([4, 8, 16]),
       d=st.sampled_from([2, 4]), tile=st.sampled_from([1, 2, 3, 4, 8]))
def test_reduce_print_parse_fixpoint(sq, sk, d, tile):
    kern = lower_graph(fe.flash_attention_graph(sq, sk, d),
                       LoweringOptions(tile_m=tile, tile_n=tile, tile_k=tile))
    text = ir_text.print_ir(kern)
    assert ir_text.print_ir(ir_text.parse_ir(text)) == text


@settings(max_examples=12, deadline=None)
@given(s=st.sampled_from([2, 4, 8, 16]), p=st.sampled_from([1, 2]),
       n=st.sampled_from([2, 4]), tile=st.sampled_from([1, 2, 3, 4, 8]))
def test_scan_print_parse_fixpoint(s, p, n, tile):
    kern = lower_graph(fe.ssd_scan_graph(s, p, n),
                       LoweringOptions(tile_m=tile, tile_n=tile, tile_k=tile))
    text = ir_text.print_ir(kern)
    assert ir_text.print_ir(ir_text.parse_ir(text)) == text


def _corrupt_line(text, needle, old, new):
    """Rewrite ``old``→``new`` on the first line containing ``needle``."""
    lines = text.splitlines()
    for i, ln in enumerate(lines):
        if needle in ln:
            assert old in ln, f"expected {old!r} in {ln!r}"
            lines[i] = ln.replace(old, new, 1)
            return "\n".join(lines)
    raise AssertionError(f"no line contains {needle!r}")


def test_parse_rejects_scan_carry_shape_mismatch_with_line_number():
    kern = lower_graph(fe.ssd_scan_graph(8, 2, 4),
                       LoweringOptions(tile_m=4, tile_n=4, tile_k=4))
    text = ir_text.print_ir(kern)
    bad = _corrupt_line(text, "scan<linear>", "1x4]", "1x2]")
    with pytest.raises(IRParseError, match="carry mismatch") as ei:
        ir_text.parse_ir(bad)
    assert "line " in str(ei.value)


def test_parse_rejects_reduce_rank_mismatch_with_line_number():
    kern = lower_graph(fe.flash_attention_graph(8, 16, 4),
                       LoweringOptions(tile_m=2, tile_n=2, tile_k=2))
    text = ir_text.print_ir(kern)
    bad = _corrupt_line(text, "reduce<max,acc>", "2x2]", "1x2]")
    with pytest.raises(IRParseError, match="reduce tile mismatch") as ei:
        ir_text.parse_ir(bad)
    assert "line " in str(ei.value)


def test_parse_rejects_bad_reduce_and_scan_kinds():
    kern = lower_graph(fe.ssd_scan_graph(8, 2, 4),
                       LoweringOptions(tile_m=4, tile_n=4, tile_k=4))
    text = ir_text.print_ir(kern)
    with pytest.raises(IRParseError, match="bad kind"):
        ir_text.parse_ir(_corrupt_line(text, "scan<linear>",
                                       "scan<linear>", "scan<median>"))


@settings(max_examples=8, deadline=None)
@given(kernel=st.sampled_from(["flash", "ssd"]),
       tile=st.sampled_from([1, 2, 4]))
def test_canonicalize_idempotent_on_carry_kernels(kernel, tile):
    from repro.core.passes import PassManager

    graph = (fe.flash_attention_graph(4, 8, 2) if kernel == "flash"
             else fe.ssd_scan_graph(4, 2, 2))
    opts = LoweringOptions(tile_m=tile, tile_n=tile, tile_k=tile)
    k1 = PassManager().add("canonicalize").run(lower_graph(graph, opts)) \
                      .artifact
    once = ir_text.print_ir(k1)
    k2 = PassManager().add("canonicalize").run(k1).artifact
    assert ir_text.print_ir(k2) == once


def test_canonicalize_preserves_carry_semantics():
    graph, inputs = _ssd_case(8, 2, 2, head=0)
    ck = pipeline.compile_traced(graph, pipeline="lower{tile_m=2,tile_n=2,"
                                                 "tile_k=2}",
                                 canonicalize=True)
    (out,) = ck.run_ref(*inputs)
    np.testing.assert_allclose(out, _scan_oracle(*inputs), **TOL)


# --------------------------------------------------------------------------
# DSE: the explorer prices and validates the carried kernels
# --------------------------------------------------------------------------


@pytest.mark.parametrize("graph", [fe.flash_attention_graph(8, 16, 4),
                                   fe.ssd_scan_graph(16, 2, 4)],
                         ids=["flash", "ssd"])
def test_dse_explore_serving_kernels(graph):
    from repro.core import dse

    res = dse.explore(graph, validate_top=2, tiles=(8, 4), use_cache=False)
    assert res.frontier, "empty Pareto frontier"
    assert res.validations, "no frontier point was validated"
    bad = [v for v in res.validations if not v.ok]
    assert not bad, f"frontier points failed cosim: {bad}"
