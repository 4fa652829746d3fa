"""Differential raising test matrix: traced JAX -> TensorIR.

Covers the PR-7 tentpole end to end:

  * for every config in the registry, every raisable forward-pass block
    must raise into TensorIR and match the traced JAX function on the
    example inputs (graph interpreter, then the compiled ref and jax
    backends through the full PassManager pipeline);
  * the raised flash / decode / ssd mirrors must be structurally
    ``is_equivalent`` to the hand-written ``frontend.*_graph`` builders;
  * scan lengths recovered by raising must agree with the ``while`` trip
    counts ``launch.hlo_analysis`` walks out of the XLA-optimized HLO;
  * a property-based fuzzer round-trips random programs from the
    supported vocabulary (raise -> print/parse fixpoint -> backends);
  * everything outside the vocabulary must fail with a diagnostic naming
    the primitive and the offending equation.

The general pallas emitter's numerics on non-matmul graphs are a known
pre-existing gap (tracked by test_kernels' xfails), so pallas is only
smoke-tested for successful emission here — numeric assertions run on
the ref and jax backends.
"""

import functools
import importlib
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro.core.frontend as fe
from repro.core import ir_text, reproc
from repro.configs.base import ARCHS

raising = importlib.import_module("repro.core.raise")

# big configs ride the slow lane (the reduced() shrink keeps shapes tiny,
# but MoE/MLA tracing is still the long pole of the matrix)
_SLOW = {"qwen1_5_32b", "deepseek_v2_236b", "kimi_k2_1t", "pixtral_12b"}
ARCH_PARAMS = [pytest.param(a, marks=pytest.mark.slow) if a in _SLOW else a
               for a in ARCHS]

_TILE8 = {"m": 8, "n": 8, "k": 8}


@functools.lru_cache(maxsize=None)
def _reports(arch):
    return {r.block: r for r in raising.raise_model_blocks(arch)}


def _expected(rep):
    return np.asarray(rep.fn(*rep.example_inputs), np.float32)


def _tol(exp, rel=1e-4):
    return rel * max(1.0, float(np.max(np.abs(exp))))


# --------------------------------------------------------------------------
# the differential matrix
# --------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_matrix_raises_and_matches_ref(arch):
    """Every raisable block of every config: raised TensorIR executed by
    the graph interpreter matches the traced JAX function at 1e-4."""
    reps = _reports(arch)
    ok = [r for r in reps.values() if r.ok]
    assert ok, f"{arch}: no raisable blocks"
    for rep in ok:
        exp = _expected(rep)
        (got,) = rep.raised.run_ref(*rep.example_inputs)
        assert got.shape == exp.shape, rep.block
        np.testing.assert_allclose(got, exp, atol=_tol(exp), rtol=0,
                                   err_msg=f"{arch}:{rep.block}")
        assert rep.raised.lowerable, \
            f"{arch}:{rep.block} raised ops outside the lowerable set: " \
            f"{rep.raised.unlowerable_ops}"


@pytest.mark.parametrize("arch", ARCH_PARAMS)
def test_matrix_compiles_through_pipeline(arch):
    """The largest raised block of each config compiles through the full
    PassManager pipeline and both the ref and jax backends match the
    traced JAX function at 1e-4."""
    reps = _reports(arch)
    rep = max((r for r in reps.values() if r.ok),
              key=lambda r: len(r.raised.graph.ops))
    sched = "nested" if rep.raised.scan_lengths else "tpu_mxu"
    ck = rep.raised.compile(tile=_TILE8, schedule=sched, want_pallas=False)
    exp = _expected(rep)
    for backend in ("ref", "jax"):
        (got,) = rep.raised.run_compiled(ck, *rep.example_inputs,
                                         backend=backend)
        np.testing.assert_allclose(
            got, exp, atol=_tol(exp), rtol=0,
            err_msg=f"{arch}:{rep.block} backend={backend}")


def test_expected_block_coverage():
    """Regression-pin which blocks raise per config family so a raiser
    change that silently loses a block fails loudly."""
    assert {b for b, r in _reports("qwen2_7b").items() if r.ok} == \
        {"rmsnorm", "mlp", "head", "attn_softmax"}
    assert {b for b, r in _reports("mamba2_130m").items() if r.ok} == \
        {"rmsnorm", "head", "ssd_core"}
    assert {b for b, r in _reports("recurrentgemma_2b").items() if r.ok} == \
        {"rmsnorm", "mlp", "head", "attn_softmax", "rglru_core"}
    # negatives stay negative, with real diagnostics
    rope = _reports("qwen2_7b")["rope"]
    assert not rope.ok and "slice" in rope.error
    router = _reports("deepseek_v2_236b")["moe_router"]
    assert not router.ok and "top_k" in router.error


def test_pallas_emission_smoke():
    """Raised graphs must at least *emit* a pallas kernel (numerics of the
    general emitter on ewise graphs are a pre-existing, separately
    tracked gap)."""
    rep = _reports("qwen2_7b")["rmsnorm"]
    ck = rep.raised.compile(tile=_TILE8)
    assert ck.run_pallas is not None


# --------------------------------------------------------------------------
# equivalence against the hand-written frontend builders
# --------------------------------------------------------------------------


def _assert_numeric_identical(rg, hand, shapes, rng, atol=1e-5):
    args = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    got = rg.graph.eval_np(*rg.bind(*args))
    want = hand.eval_np(*[a.reshape(v.type.shape)
                          for a, v in zip(args, hand.inputs)])
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=atol, rtol=0)


def test_flash_mirror_is_equivalent():
    rg = raising.reference_flash(8, 16, 4)
    hand = fe.flash_attention_graph(8, 16, 4)
    assert rg.graph.is_equivalent(hand), \
        f"raised:\n{ir_text.print_graph(rg.graph)}\n" \
        f"hand:\n{ir_text.print_graph(hand)}"
    _assert_numeric_identical(rg, hand, rg.arg_shapes,
                              np.random.default_rng(0))


def test_decode_mirror_is_equivalent():
    rg = raising.reference_decode(4, 16, 4)
    hand = fe.decode_attention_graph(4, 16, 4)
    assert rg.graph.is_equivalent(hand)
    _assert_numeric_identical(rg, hand, rg.arg_shapes,
                              np.random.default_rng(1))


def test_ssd_mirror_is_equivalent():
    rg = raising.reference_ssd(8, 2, 4)
    hand = fe.ssd_scan_graph(8, 2, 4)
    assert rg.graph.is_equivalent(hand)
    rng = np.random.default_rng(2)
    # decay in (0, 1) like the real kernel
    a = rng.uniform(0.2, 0.95, rg.arg_shapes[0]).astype(np.float32)
    rest = [rng.standard_normal(s).astype(np.float32)
            for s in rg.arg_shapes[1:]]
    got = rg.graph.eval_np(*rg.bind(a, *rest))
    want = hand.eval_np(a, *rest)
    np.testing.assert_allclose(got[0], want[0], atol=1e-5, rtol=0)


# --------------------------------------------------------------------------
# scan raising + HLO trip-count cross-check
# --------------------------------------------------------------------------


def test_cumsum_raises_to_scan():
    rg = raising.raise_jaxpr(lambda x: jnp.cumsum(x, axis=0), (8, 4))
    ops = {op.opname: op for op in rg.graph.ops}
    assert "scan" in ops
    assert ops["scan"].attrs["kind"] == "cumsum"
    assert rg.scan_lengths == [8]
    x = np.random.default_rng(3).standard_normal((8, 4)).astype(np.float32)
    np.testing.assert_allclose(rg.run_ref(x)[0], np.cumsum(x, axis=0),
                               atol=1e-5, rtol=0)


def test_linear_scan_raises_with_hlo_trip_crosscheck():
    """lax.scan of h = a*h + u raises to a linear scan op AND the recovered
    scan length must appear among the while-loop trip counts that
    launch.hlo_analysis walks out of the XLA-optimized module."""
    def fn(a, u, ct, g):
        return (raising._scan_linear(a, u) * ct) @ g

    rg = raising.raise_jaxpr(fn, (8, 16), (8, 16), (8, 16), (16, 2),
                             check_hlo_trips=True)
    ops = {op.opname: op for op in rg.graph.ops}
    assert ops["scan"].attrs["kind"] == "linear"
    assert rg.scan_lengths == [8]
    assert rg.hlo_trips and 8 in rg.hlo_trips.values()


def test_scan_rejects_nonlinear_body():
    def fn(u):
        def step(h, x):
            h = h * h + x          # quadratic in the carry
            return h, h
        return jax.lax.scan(step, jnp.zeros((4,)), u)[1]

    with pytest.raises(raising.RaiseError) as ei:
        raising.raise_jaxpr(fn, (8, 4))
    assert "scan" in str(ei.value)


# --------------------------------------------------------------------------
# wiring: frontend delegators, reproc CLI, DSE
# --------------------------------------------------------------------------


def test_frontend_delegators():
    rg = fe.raise_jaxpr(lambda x: jnp.tanh(x) + 1.0, (4, 4))
    assert isinstance(rg, raising.RaisedGraph)
    reps = fe.raise_model_blocks("mamba2_130m")
    assert any(r.ok for r in reps)


def test_const_inputs_are_deduped():
    w = np.random.default_rng(4).standard_normal((4, 4)).astype(np.float32)
    rg = raising.raise_jaxpr(lambda x: (x + w) * w, (4, 4))
    # one user arg + ONE captured const, despite two uses of w
    assert rg.n_args == 1
    assert len(rg.graph.inputs) == 2
    assert set(rg.const_bindings) == {"c0"}


def test_reproc_raise_emits_tensorir():
    buf = io.StringIO()
    assert reproc.main(["--raise", "qwen2_7b:mlp"], out=buf) == 0
    text = buf.getvalue()
    assert "stagecc.func" in text and "matmul" in text


def test_reproc_raise_report_mode():
    buf = io.StringIO()
    assert reproc.main(["--raise", "qwen2_7b"], out=buf) == 0
    text = buf.getvalue()
    assert "RAISED" in text and "NOT RAISABLE" in text


@pytest.mark.filterwarnings("ignore:invalid value encountered")
def test_reproc_raise_pipeline_and_simulate():
    # random CLI inputs can drive rsqrt negative in BOTH cosim legs —
    # the outputs still agree bitwise, so the warning is noise here
    buf = io.StringIO()
    rc = reproc.main(["--raise", "qwen2_7b:rmsnorm",
                      "--pipeline", "lower{tile_m=8,tile_n=8,tile_k=8}",
                      "--simulate"], out=buf)
    assert rc == 0
    assert "cosim" in buf.getvalue()


def test_reproc_raise_cli_errors(capsys):
    # --raise is exclusive with the other graph sources
    assert reproc.main(["--raise", "qwen2_7b:mlp", "--gemm", "4x4x4"],
                       out=io.StringIO()) == 2
    # report mode takes no pipeline
    assert reproc.main(["--raise", "qwen2_7b", "--emit", "loop"],
                       out=io.StringIO()) == 2
    # unknown block names the available ones (diagnostic goes to stderr)
    assert reproc.main(["--raise", "qwen2_7b:nope"], out=io.StringIO()) == 1
    assert "mlp" in capsys.readouterr().err


def test_dse_explores_raised_region():
    rep = _reports("qwen2_7b")["rmsnorm"]
    res = rep.raised.explore(tiles=(8,), validate_top=1)
    assert res.frontier, "no feasible frontier point for the raised graph"
    assert res.validations and all(v.ok for v in res.validations)


# --------------------------------------------------------------------------
# property-based round-trip fuzzer
# --------------------------------------------------------------------------

# every step is shape-preserving over a (rows, cols) value, so random
# programs compose freely; consts are captured numpy arrays (exercising
# the lazy const materialization + dedup path)


def _const(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


_STEP_POOL = [
    ("tanh", lambda rng, r, c: (lambda v: jnp.tanh(v))),
    ("abs", lambda rng, r, c: (lambda v: jnp.abs(v))),
    ("neg", lambda rng, r, c: (lambda v: -v)),
    ("exp", lambda rng, r, c: (lambda v: jnp.exp(-jnp.abs(v)))),
    ("sigmoid", lambda rng, r, c: (lambda v: jax.nn.sigmoid(v))),
    ("sqrt", lambda rng, r, c: (lambda v: jnp.sqrt(jnp.abs(v) + 0.5))),
    ("log1p", lambda rng, r, c: (lambda v: jnp.log1p(jnp.abs(v)))),
    ("add", lambda rng, r, c: (lambda v, w=None: v + w,
                               _const(rng, r, c))),
    ("sub", lambda rng, r, c: (lambda v, w=None: v - w,
                               _const(rng, r, c))),
    ("mul_row", lambda rng, r, c: (lambda v, w=None: v * w,
                                   _const(rng, 1, c))),
    ("maximum", lambda rng, r, c: (lambda v, w=None: jnp.maximum(v, w),
                                   _const(rng, r, c))),
    ("div", lambda rng, r, c: (lambda v, w=None: v / (jnp.abs(w) + 0.7),
                               _const(rng, r, c))),
    ("softmax_shift", lambda rng, r, c:
        (lambda v: v - jnp.max(v, axis=1, keepdims=True))),
    ("l1_norm", lambda rng, r, c:
        (lambda v: v / (jnp.sum(jnp.abs(v), axis=1, keepdims=True) + 1.0))),
    ("matmul", lambda rng, r, c: (lambda v, w=None: v @ w,
                                  _const(rng, c, c))),
    ("cumsum", lambda rng, r, c: (lambda v: jnp.cumsum(v, axis=0))),
    ("scan_linear", lambda rng, r, c:
        (lambda v: raising._scan_linear(jax.nn.sigmoid(v), v))),
]


def _build_program(seed):
    rng = np.random.default_rng(seed)
    rows = int(rng.integers(2, 7))
    cols = int(rng.integers(2, 6))
    n = int(rng.integers(2, 7))
    steps, names = [], []
    for _ in range(n):
        name, build = _STEP_POOL[int(rng.integers(len(_STEP_POOL)))]
        built = build(rng, rows, cols)
        if isinstance(built, tuple):
            f, w = built
            steps.append(functools.partial(lambda v, f, w: f(v, w), f=f, w=w))
        else:
            steps.append(built)
        names.append(name)

    def fn(x):
        v = x
        for s in steps:
            v = s(v)
        return v

    return fn, (rows, cols), names


@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_fuzz_roundtrip(seed):
    fn, (rows, cols), names = _build_program(seed)
    rg = raising.raise_jaxpr(fn, (rows, cols), name=f"fuzz{seed}")

    # textual round-trip fixpoint: print(parse(print(g))) == print(g)
    text = ir_text.print_graph(rg.graph)
    assert ir_text.print_graph(ir_text.parse_graph(text)) == text, names

    x = np.random.default_rng(seed ^ 0x5EED).standard_normal(
        (rows, cols)).astype(np.float32)
    exp = np.asarray(fn(jnp.asarray(x)), np.float32)
    tol = _tol(exp)

    (got,) = rg.run_ref(x)
    np.testing.assert_allclose(got, exp, atol=tol, rtol=0, err_msg=str(names))

    # through the pipeline to the LoopIR reference interpreter; the
    # default tpu_mxu schedule (correctly) refuses to grid a scan's time
    # axis or a carried reduction axis, so such programs take the nested
    # schedule
    carried = rg.scan_lengths or any(op.opname == "reduce"
                                     for op in rg.graph.ops)
    sched = "nested" if carried else "tpu_mxu"
    ck = rg.compile(tile={"m": 4, "n": 4, "k": 4}, schedule=sched,
                    want_jax=False, want_pallas=False)
    (got,) = rg.run_compiled(ck, x, backend="ref")
    np.testing.assert_allclose(got, exp, atol=tol, rtol=0, err_msg=str(names))


# --------------------------------------------------------------------------
# handler edge cases: the corners of the vocabulary
# --------------------------------------------------------------------------


def _check_fn(fn, *shapes, seed=7, atol=1e-5, **kw):
    rg = raising.raise_jaxpr(fn, *shapes, **kw)
    rng = np.random.default_rng(seed)
    args = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    exp = np.asarray(fn(*map(jnp.asarray, args)), np.float32)
    (got,) = rg.run_ref(*args)
    np.testing.assert_allclose(got, exp, atol=atol * max(1.0, float(
        np.max(np.abs(exp)))), rtol=0)
    return rg


def test_dot_rhs_transposed_emits_transpose():
    # einsum "ij,kj->ik": rhs contracts its LAST axis, so raising must
    # transpose the traced rhs before the matmul
    rg = _check_fn(lambda x, w: jnp.einsum("ij,kj->ik", x, w),
                   (6, 4), (5, 4))
    assert "transpose" in {op.opname for op in rg.graph.ops}


def test_dot_lhs_is_traced_transpose():
    rg = _check_fn(lambda x: x.T @ x, (6, 4))
    assert "transpose" in {op.opname for op in rg.graph.ops}


def test_dot_const_lhs_contraction_moved():
    # const lhs contracting axis 0 is fixed by folding a moveaxis
    w = np.random.default_rng(8).standard_normal((6, 3)).astype(np.float32)
    _check_fn(lambda x: jnp.einsum("ji,jk->ik", w, x), (6, 4))


def test_integer_pow():
    _check_fn(lambda x: x ** 2 + x ** 3, (4, 4))


def test_scalar_and_rank1_inputs():
    rg = _check_fn(lambda s: s * 2.0 + 1.0, ())
    assert rg.arg_shapes == [()]
    _check_fn(lambda v: jnp.exp(v) / 3.0, (5,))


def test_remat_call_is_inlined():
    _check_fn(jax.checkpoint(lambda x: jnp.tanh(x) * 2.0), (4, 4))


def test_nan_guard_select_is_identity():
    rg = _check_fn(
        lambda x: jnp.where(jnp.isnan(x), jnp.zeros_like(x), x), (4, 4))
    # the isnan/where pair folds away entirely — output is the input
    assert not rg.graph.ops


def test_broadcast_of_reduce_output():
    _check_fn(lambda x: jnp.broadcast_to(
        jnp.sum(x, axis=1, keepdims=True), x.shape) + x, (5, 3))


def test_bind_arity_error():
    rg = raising.raise_jaxpr(lambda x: x + 1.0, (4, 4))
    with pytest.raises(ValueError):
        rg.bind(np.zeros((4, 4), np.float32), np.zeros((4, 4), np.float32))


def test_scan_body_vocabulary():
    """The linearity analysis must see through div/neg/max/unaries/outer
    consts in the body as long as the carry enters linearly."""
    def f_div(a, u):
        def step(h, xs):
            at, ut = xs
            h = at * h + ut / (jnp.abs(ut) + 1.5)
            return h, h
        return jax.lax.scan(step, jnp.zeros((4,)), (a, u))[1]

    def f_neg_max(a, u):
        def step(h, xs):
            at, ut = xs
            h = at * h - jnp.maximum(ut, 0.25)
            return h, h
        return jax.lax.scan(step, jnp.zeros((4,)), (a, u))[1]

    w = np.random.default_rng(9).standard_normal((4,)).astype(np.float32)

    def f_outer_const(a, u):
        def step(h, xs):
            at, ut = xs
            h = jnp.tanh(at) * h + ut * w
            return h, h
        return jax.lax.scan(step, jnp.zeros((4,)), (a, u))[1]

    rng = np.random.default_rng(10)
    a = rng.uniform(0.2, 0.95, (6, 4)).astype(np.float32)
    u = rng.standard_normal((6, 4)).astype(np.float32)
    for f in (f_div, f_neg_max, f_outer_const):
        rg = raising.raise_jaxpr(f, a, u)
        ops = {op.opname: op for op in rg.graph.ops}
        assert ops["scan"].attrs["kind"] == "linear", f.__name__
        exp = np.asarray(f(a, u), np.float32)
        np.testing.assert_allclose(rg.run_ref(a, u)[0], exp, atol=1e-5,
                                   rtol=0, err_msg=f.__name__)


def test_scan_body_neg_and_reshape_views():
    def fn(a, u):
        def step(h, xs):
            at, ut = xs
            h = at * h + (-ut).reshape(4)
            return h, h
        return jax.lax.scan(step, jnp.zeros((4,)), (a, u))[1]

    def fn_neg_carry(a, u):
        def step(h, xs):
            at, ut = xs
            h = (-h) * (-at) + ut          # carry enters through a neg
            return h, h
        return jax.lax.scan(step, jnp.zeros((4,)), (a, u))[1]

    def fn_jit_in_body(a, u):
        helper = jax.jit(lambda t: t * 2.0)
        def step(h, xs):
            at, ut = xs
            h = at * h + helper(ut)        # jit call inlined in the body
            return h, h
        return jax.lax.scan(step, jnp.zeros((4,)), (a, u))[1]

    rng = np.random.default_rng(11)
    a = rng.uniform(0.2, 0.95, (6, 4)).astype(np.float32)
    u = rng.standard_normal((6, 4)).astype(np.float32)
    for f in (fn, fn_neg_carry, fn_jit_in_body):
        rg = raising.raise_jaxpr(f, a, u)
        np.testing.assert_allclose(rg.run_ref(a, u)[0],
                                   np.asarray(f(a, u), np.float32),
                                   atol=1e-5, rtol=0, err_msg=f.__name__)


def test_rank1_reduce_output_orientation():
    # keepdims-free reduce leaves an (N, 1) value for a (N,) result; the
    # output leg must transpose it back to the (1, N) canonical layout
    rg = _check_fn(lambda x: jnp.sum(x, axis=1), (5, 3))
    assert rg.out_shapes == [(5,)]


def test_const_only_output_materialized():
    rg = raising.raise_jaxpr(lambda x: jnp.ones((3, 2), jnp.float32) * 2.0,
                             (4, 4))
    (got,) = rg.run_ref(np.zeros((4, 4), np.float32))
    np.testing.assert_allclose(got, np.full((3, 2), 2.0))


def test_unit_dim_reshape_is_identity():
    _check_fn(lambda x: (x[:, None, :] * 1.0).reshape(4, 4), (4, 4))


def test_scan_final_carry_only_rejected():
    def fn(u):
        return jax.lax.scan(lambda c, xt: (c + xt, c + xt),
                            jnp.zeros(4), u)[0]
    with pytest.raises(raising.RaiseError):
        raising.raise_jaxpr(fn, (6, 4))


_SCAN_REJECTS = [
    ("div_by_carry", lambda h, ut: ut / h),
    ("max_over_carry", lambda h, ut: jnp.maximum(h, ut)),
    ("tanh_of_carry", lambda h, ut: jnp.tanh(h) + ut),
]


@pytest.mark.parametrize("name,upd", _SCAN_REJECTS,
                         ids=[c[0] for c in _SCAN_REJECTS])
def test_scan_rejects_nonlinear_carry_uses(name, upd):
    def fn(u):
        def step(h, ut):
            h = upd(h, ut)
            return h, h
        return jax.lax.scan(step, jnp.zeros((4,)), u)[1]

    with pytest.raises(raising.RaiseError):
        raising.raise_jaxpr(fn, (6, 4))


_EDGE_NEGATIVES = [
    ("double contraction", "dot_general",
     lambda x: jax.lax.dot_general(x, x, (((0, 1), (0, 1)), ((), ())))),
    ("traced lhs contracts axis 0", "dot_general",
     lambda x: jnp.einsum("ji,jk->ik", x, x)),
    ("reduce over rows", "reduce_sum", lambda x: jnp.sum(x, axis=0)),
    ("reduce_min", "reduce_min", lambda x: jnp.min(x, axis=1)),
    ("cumsum along cols", "cumsum", lambda x: jnp.cumsum(x, axis=1)),
    ("reverse cumsum", "cumsum",
     lambda x: jax.lax.cumsum(x, axis=0, reverse=True)),
    ("data-dependent select", "select_n",
     lambda x: jnp.where(x > 0, x, -x)),
    ("integer_pow 4", "integer_pow", lambda x: x ** 4),
    ("comparison consumed as data", "gt", lambda x: (x > 0.0) * 1.0),
    ("int conversion", "convert_element_type",
     lambda x: x.astype(jnp.int32).astype(jnp.float32) * 1.0),
    ("non-unit reshape", "reshape", lambda x: x.reshape(2, 8)),
    ("reverse scan", "scan",
     lambda x: jax.lax.scan(lambda c, xt: (c + xt, c + xt),
                            jnp.zeros(4), x, reverse=True)[1]),
    ("two carries", "scan",
     lambda x: jax.lax.scan(
         lambda c, xt: ((c[0] + xt, c[1] + xt), c[0]),
         (jnp.zeros(4), jnp.zeros(4)), x)[1]),
    ("nonzero init", "scan",
     lambda x: jax.lax.scan(
         lambda c, xt: (c + xt, c + xt), jnp.ones(4), x)[1]),
]


@pytest.mark.parametrize("label,prim,fn", _EDGE_NEGATIVES,
                         ids=[c[0].replace(" ", "-") for c in _EDGE_NEGATIVES])
def test_edge_negatives_name_the_primitive(label, prim, fn):
    with pytest.raises(raising.RaiseError) as ei:
        raising.raise_jaxpr(fn, (4, 4))
    assert prim in str(ei.value), str(ei.value)


def test_rank1_rhs_dot_rejected():
    with pytest.raises(raising.RaiseError) as ei:
        raising.raise_jaxpr(lambda x, v: jnp.dot(x, v), (4, 4), (4,))
    assert "dot_general" in str(ei.value)


_NEGATIVE_CASES = [
    ("sin", lambda x: jnp.sin(x)),
    ("concatenate", lambda x: jnp.concatenate([x, x], axis=0)),
    ("top_k", lambda x: jax.lax.top_k(x, 2)[0]),
    ("sort", lambda x: jnp.sort(x, axis=1)),
    ("slice", lambda x: x[0:1, :]),
]


@pytest.mark.parametrize("prim,fn", _NEGATIVE_CASES,
                         ids=[c[0] for c in _NEGATIVE_CASES])
def test_negative_names_primitive_and_equation(prim, fn):
    with pytest.raises(raising.RaiseError) as ei:
        raising.raise_jaxpr(fn, (4, 4))
    msg = str(ei.value)
    assert prim in msg, msg
    assert "in equation" in msg, msg
