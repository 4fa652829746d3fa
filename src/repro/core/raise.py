"""Program raising: traced JAX -> TensorIR (the mlirSynth direction).

Every TensorIR graph so far was hand-written (``frontend.flash_attention_graph``
etc.).  This module closes the loop the paper's Fig. 1 implies: start from the
*software* frontend — a real JAX function, traced to a jaxpr — and raise it
into the level-1 IR automatically, so every model config becomes a compiler
workload instead of only the three hand-written kernels.

Pipeline position::

    jax fn --make_jaxpr--> jaxpr --raise_jaxpr--> TensorIR Graph
                                                     |  (PassManager)
                                                     v
                                       LoopIR -> HwIR -> {ref, jax, pallas}

Design notes
------------
* TensorIR is rank-2: every raised SSA value is a 2-D tensor.  An n-D jax
  shape maps to ``canon2d(shape) = (prod(shape[:-1]), shape[-1])`` — leading
  (batch) axes collapse into rows, the feature axis stays columns.
* Weights/consts (jaxpr constvars + literals) are *folded* while possible and
  materialised lazily as extra graph inputs (``c0``, ``c1``, ...) the first
  time a non-foldable op consumes them; ``RaisedGraph.bind`` re-appends them
  at call time.  A raised graph of a closed-over-params block therefore has
  the user arguments first (``arg0``...) and the captured parameters after.
* ``lax.scan`` bodies are raised by *linearity analysis*: each body value is
  tracked as ``alpha * carry + beta`` with ``alpha``/``beta`` expression trees
  over the per-step slices.  Any body that is affine in a single rank<=1 carry
  (zero-initialised) becomes the carried TensorIR ``scan`` op — this covers
  the SSD recurrence, RG-LRU and cumsum uniformly.
* Anything outside the vocabulary raises :class:`RaiseError` naming the
  offending primitive and its source equation, so ``reproc --raise`` and the
  raisability table in docs/RAISING.md can show *why* a block does not raise.
* For ``while``-wrapped scans, the optimized-HLO walk in
  ``launch.hlo_analysis`` cross-checks recovered trip counts against the
  raised scan lengths (``check_hlo_trips=True``).

NOTE: ``raise`` is a Python keyword — import this module as::

    raising = importlib.import_module("repro.core.raise")
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Literal as _JaxLiteral

from .tensor_ir import Graph, TensorType, Value


class RaiseError(ValueError):
    """A jaxpr fragment outside the raisable vocabulary.

    Carries the unraisable primitive's name and the offending equation so
    diagnostics (CLI, docs table, negative tests) can point at the source.
    """

    def __init__(self, msg: str, primitive: Optional[str] = None,
                 equation: Optional[str] = None):
        self.primitive = primitive
        self.equation = equation
        full = msg
        if primitive:
            full += f" [primitive: {primitive}]"
        if equation:
            eq = equation if len(equation) <= 400 else equation[:400] + "..."
            full += f"\n  in equation: {eq}"
        super().__init__(full)


def canon2d(shape: Sequence[int]) -> Tuple[int, int]:
    """n-D jax shape -> the rank-2 TensorIR shape it raises to."""
    shape = tuple(int(d) for d in shape)
    if any(d == 0 for d in shape):
        raise RaiseError(f"zero-sized dimension in shape {shape}")
    if len(shape) == 0:
        return (1, 1)
    if len(shape) == 1:
        return (1, shape[0])
    rows = 1
    for d in shape[:-1]:
        rows *= d
    return (rows, shape[-1])


@dataclasses.dataclass
class _RVal:
    """One jaxpr variable during raising.

    Exactly one of ``val`` (a rank-2 TensorIR SSA value) or ``const`` (a
    jax-shaped numpy payload, still foldable) is set; if neither is, ``note``
    says why, and the error surfaces only if the value is actually consumed
    (e.g. a scan's unused final carry).
    """

    jshape: Tuple[int, ...]
    val: Optional[Value] = None
    const: Optional[np.ndarray] = None
    note: Optional[str] = None


# numpy semantics for constant folding (float32 domain, matching backends)
_NP_BIN: Dict[str, Callable] = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "mul": lambda a, b: a * b,
    "div": lambda a, b: a / b,
    "maximum": np.maximum,
}
_NP_UN: Dict[str, Callable] = {
    "neg": lambda a: -a,
    "exp": np.exp,
    "tanh": np.tanh,
    "sigmoid": lambda a: 1.0 / (1.0 + np.exp(-a)),
    "sqrt": np.sqrt,
    "rsqrt": lambda a: 1.0 / np.sqrt(a),
    "log1p": np.log1p,
    "abs": np.abs,
    "relu": lambda a: np.maximum(a, 0),
}

# jax primitive -> TensorIR ewise op
_BIN_PRIMS = {"add": "add", "sub": "sub", "mul": "mul", "div": "div",
              "max": "maximum"}
_UN_PRIMS = {"exp": "exp", "neg": "neg", "tanh": "tanh",
             "logistic": "sigmoid", "rsqrt": "rsqrt", "sqrt": "sqrt",
             "log1p": "log1p", "abs": "abs"}

# primitives folded when ALL operands are constants (never emitted as ops)
_FOLD_ONLY = {
    "pow": np.power, "cos": np.cos, "sin": np.sin, "log": np.log,
    "floor": np.floor, "round": np.round, "sign": np.sign,
    "min": np.minimum,
    "lt": lambda a, b: a < b, "le": lambda a, b: a <= b,
    "gt": lambda a, b: a > b, "ge": lambda a, b: a >= b,
    "eq": lambda a, b: a == b,
    "and": np.logical_and, "or": np.logical_or, "not": np.logical_not,
    "xor": np.logical_xor,
}

# ops the LoopIR lowering implements (cast/transpose print and eval but have
# no tile lowering — a graph containing them raises fine but can't compile)
_LOWERABLE_OPS = {"matmul", "bias_add", "reduce_sum", "reduce", "scan",
                  "add", "sub", "mul", "maximum", "div",
                  "relu", "gelu", "exp", "neg",
                  "tanh", "sigmoid", "sqrt", "rsqrt", "log1p", "abs"}

_CALL_PRIMS = {"jit", "closed_call", "call", "custom_jvp_call",
               "custom_vjp_call", "remat2"}
_IDENTITY_PRIMS = {"sharding_constraint", "stop_gradient", "copy",
                   "device_put", "convert_element_type"}


def _fold(fn, *args):
    """Constant folding runs on whatever values the trace produced (incl.
    inf masks); fold-domain warnings are jax-identical non-events."""
    with np.errstate(all="ignore"):
        return fn(*args)


def _npc(x) -> np.ndarray:
    """Constant payload -> float-friendly numpy (bools/ints kept for masks)."""
    a = np.asarray(x)
    if a.dtype == np.float64:
        a = a.astype(np.float32)
    return a


# --------------------------------------------------------------------------
# scan-body linearity analysis:  value == alpha * carry + beta
# --------------------------------------------------------------------------
# Expr nodes: ("xs", i) | ("outer", k) | ("lit", ndarray) |
#             ("un", op, e) | ("bin", op, e1, e2)

_E_ONE = ("lit", np.float32(1.0))


def _e_is_one(e) -> bool:
    return (e is not None and e[0] == "lit"
            and np.ndim(e[1]) == 0 and float(e[1]) == 1.0)


def _e_add(a, b, op="add"):
    if a is None:
        return b if op == "add" else ("un", "neg", b) if b is not None else None
    if b is None:
        return a
    return ("bin", op, a, b)


def _e_mul(a, b):
    if a is None or b is None:
        return None
    if _e_is_one(a):
        return b
    if _e_is_one(b):
        return a
    return ("bin", "mul", a, b)


@dataclasses.dataclass
class _LinVal:
    alpha: Optional[tuple]  # coefficient of the carry (None == 0)
    beta: Optional[tuple]   # carry-free part (None == 0)


def _linear_body(jaxpr, consts, num_consts: int, n_xs: int):
    """Interpret a scan body as affine in its single carry.

    Returns ``(alpha, beta)`` expression trees for the new carry, or raises
    :class:`RaiseError` if the body is nonlinear / outside the vocabulary.
    """
    env: Dict[Any, _LinVal] = {}

    def read(v) -> _LinVal:
        if isinstance(v, _JaxLiteral):
            return _LinVal(None, ("lit", _npc(v.val)))
        return env[v]

    for cv, cval in zip(jaxpr.constvars, consts):
        env[cv] = _LinVal(None, ("lit", _npc(cval)))
    for k in range(num_consts):
        env[jaxpr.invars[k]] = _LinVal(None, ("outer", k))
    env[jaxpr.invars[num_consts]] = _LinVal(_E_ONE, None)       # the carry
    for i in range(n_xs):
        env[jaxpr.invars[num_consts + 1 + i]] = _LinVal(None, ("xs", i))

    def fail(eqn, why):
        raise RaiseError(f"scan body not affine in the carry: {why}",
                         primitive=eqn.primitive.name, equation=str(eqn))

    def run(jx, jx_consts):
        for cv, cval in zip(jx.constvars, jx_consts):
            env[cv] = _LinVal(None, ("lit", _npc(cval)))
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            ins = [read(v) for v in eqn.invars]
            if prim in _CALL_PRIMS:
                cj = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr"))
                if cj is None or not hasattr(cj, "jaxpr"):
                    fail(eqn, "opaque call")
                for iv, rv in zip(cj.jaxpr.invars, ins):
                    env[iv] = rv
                run(cj.jaxpr, cj.consts)
                for ov, iv in zip(eqn.outvars, cj.jaxpr.outvars):
                    env[ov] = read(iv)
                continue
            if prim == "convert_element_type":
                env[eqn.outvars[0]] = ins[0]
                continue
            if prim in ("add", "sub"):
                a, b = ins
                out = _LinVal(_e_add(a.alpha, b.alpha, prim),
                              _e_add(a.beta, b.beta, prim))
            elif prim == "mul":
                a, b = ins
                if a.alpha is not None and b.alpha is not None:
                    fail(eqn, "carry * carry")
                if a.alpha is not None:        # (alpha*c + beta) * b
                    a, b = b, a
                out = _LinVal(_e_mul(a.beta, b.alpha),
                              _e_mul(a.beta, b.beta))
            elif prim == "div":
                a, b = ins
                if b.alpha is not None:
                    fail(eqn, "division by the carry")
                out = _LinVal(_e_mul(a.alpha, ("un", "_recip", b.beta))
                              if a.alpha is not None else None,
                              _e_mul(a.beta, ("un", "_recip", b.beta))
                              if a.beta is not None else None)
            elif prim == "neg":
                (a,) = ins
                out = _LinVal(("un", "neg", a.alpha) if a.alpha else None,
                              ("un", "neg", a.beta) if a.beta else None)
            elif prim == "max":
                a, b = ins
                if a.alpha is not None or b.alpha is not None:
                    fail(eqn, "max over the carry")
                out = _LinVal(None, ("bin", "maximum", a.beta, b.beta))
            elif prim in _UN_PRIMS:
                (a,) = ins
                if a.alpha is not None:
                    fail(eqn, f"nonlinear {prim} of the carry")
                out = _LinVal(None, ("un", _UN_PRIMS[prim], a.beta))
            elif prim == "broadcast_in_dim" or prim == "reshape" \
                    or prim == "squeeze":
                # per-step shapes are tiny; only shape-preserving views keep
                # the timestep<->full-array correspondence exact
                (a,) = ins
                if a.alpha is not None and not _e_is_one(a.alpha):
                    fail(eqn, f"{prim} of a carry-dependent value")
                out = a
            else:
                fail(eqn, f"unsupported body primitive {prim!r}")
            env[eqn.outvars[0]] = out

    run(jaxpr, consts)

    outs = [read(v) for v in jaxpr.outvars]
    if len(outs) != 2 or jaxpr.outvars[0] is not jaxpr.outvars[1]:
        raise RaiseError(
            "scan body must yield (new_carry, new_carry) — the carried "
            "TensorIR scan materialises every h_t",
            primitive="scan")
    new_carry = outs[0]
    if new_carry.alpha is None or new_carry.beta is None:
        raise RaiseError("scan body is not of the form a_t*h + u_t "
                         "(missing decay or update term)", primitive="scan")
    return new_carry.alpha, new_carry.beta


# --------------------------------------------------------------------------
# the raiser
# --------------------------------------------------------------------------


class _Raiser:
    def __init__(self, name: str):
        self.graph = Graph(name)
        self.const_bindings: Dict[str, np.ndarray] = {}
        self._const_cache: Dict[tuple, Value] = {}
        self.scan_lengths: List[int] = []

    # ---- const materialisation -------------------------------------------

    def _const_input(self, arr2d: np.ndarray) -> Value:
        arr2d = np.ascontiguousarray(arr2d, dtype=np.float32)
        key = (arr2d.shape, arr2d.tobytes())
        v = self._const_cache.get(key)
        if v is None:
            name = f"c{len(self.const_bindings)}"
            v = self.graph.add_input(name, TensorType(arr2d.shape))
            self.const_bindings[name] = arr2d
            self._const_cache[key] = v
        return v

    @staticmethod
    def _const2d(rv: _RVal, target: Optional[Tuple[int, int]]) -> np.ndarray:
        a = _npc(rv.const).astype(np.float32)
        if a.shape != tuple(rv.jshape):
            a = np.broadcast_to(a, rv.jshape)
        c = a.reshape(canon2d(rv.jshape))
        if target is not None and c.shape != tuple(target):
            if c.size == target[0] * target[1]:
                c = c.reshape(target)            # e.g. (1,N) -> (N,1)
            else:
                c = np.broadcast_to(c, target)
        return c

    def _need(self, rv: _RVal, eqn=None):
        if rv.val is None and rv.const is None:
            raise RaiseError(rv.note or "value is not raisable",
                             equation=str(eqn) if eqn is not None else None)

    def _mat(self, rv: _RVal, shape2d: Tuple[int, int]) -> Value:
        """The rank-2 SSA value for ``rv`` at exactly ``shape2d``."""
        self._need(rv)
        if rv.val is not None:
            if tuple(rv.val.type.shape) != tuple(shape2d):
                raise RaiseError(
                    f"cannot reconcile value of shape {rv.val.type.shape} "
                    f"with required shape {shape2d}")
            return rv.val
        return self._const_input(self._const2d(rv, shape2d))

    def _shape2(self, rv: _RVal) -> Tuple[int, int]:
        if rv.val is not None:
            return tuple(rv.val.type.shape)
        return canon2d(rv.jshape)

    def _force_full(self, rv: _RVal, jshape: Sequence[int]) -> Value:
        """``rv`` as a full ``canon2d(jshape)`` value, materialising any
        deferred broadcast (by ones-multiplication) or constant."""
        target = canon2d(jshape)
        if rv.const is not None:
            return self._const_input(self._const2d(rv, target))
        self._need(rv)
        s = tuple(rv.val.type.shape)
        if s == target:
            return rv.val
        if s == (target[1], target[0]) and 1 in s:
            # a keepdims-orientation vector, e.g. (N,1) vs (1,N): same data,
            # same linear order — a rank-2 transpose restores the layout
            return self.graph.emit("transpose", [rv.val], perm=(1, 0))
        if all(d in (1, t) for d, t in zip(s, target)):
            ones = self._const_input(np.ones(target, np.float32))
            return self.graph.emit("mul", [ones, rv.val])
        raise RaiseError(f"cannot broadcast value of shape {s} to {target}")

    # ---- elementwise ------------------------------------------------------

    def _ewise_un(self, op: str, a: _RVal, out_jshape, eqn=None) -> _RVal:
        self._need(a, eqn)
        if a.const is not None:
            return _RVal(tuple(out_jshape), const=_fold(
                _NP_UN[op], _npc(a.const).astype(np.float32)))
        return _RVal(tuple(out_jshape), val=self.graph.emit(op, [a.val]))

    def _ewise_bin(self, op: str, a: _RVal, b: _RVal, out_jshape,
                   eqn=None) -> _RVal:
        self._need(a, eqn)
        self._need(b, eqn)
        if a.const is not None and b.const is not None:
            return _RVal(tuple(out_jshape),
                         const=_fold(_NP_BIN[op],
                                     _npc(a.const).astype(np.float32),
                                     _npc(b.const).astype(np.float32)))
        # a rank-1 result may live in either orientation: (1,N) canonically,
        # or (N,1) when it flows out of a keepdims-free reduce
        targets = [canon2d(out_jshape)]
        if len(out_jshape) == 1 and out_jshape[0] != 1:
            targets.append((int(out_jshape[0]), 1))
        err = None
        for target in targets:
            try:
                return self._bin_at(op, a, b, target, tuple(out_jshape))
            except RaiseError as e:
                err = e
        raise RaiseError(
            f"unsupported ewise broadcast {self._shape2(a)} {op} "
            f"{self._shape2(b)} -> {targets[0]} ({err})",
            primitive=op, equation=str(eqn) if eqn is not None else None)

    def _bin_at(self, op: str, a: _RVal, b: _RVal,
                target: Tuple[int, int], out_jshape) -> _RVal:
        def cshape(rv):
            """The 2-D shape this operand takes against ``target`` (None if
            irreconcilable)."""
            if rv.val is not None:
                s = tuple(rv.val.type.shape)
                return s if all(d in (1, t)
                                for d, t in zip(s, target)) else None
            c = canon2d(rv.jshape)
            if all(d in (1, t) for d, t in zip(c, target)):
                return c
            if c[0] * c[1] == target[0] * target[1]:
                return target                    # reshapeable constant
            return None

        sa, sb = cshape(a), cshape(b)
        if sa is None or sb is None:
            raise RaiseError(f"operands {self._shape2(a)} / "
                             f"{self._shape2(b)} do not fit {target}")
        full_a, full_b = sa == target, sb == target
        if not full_a and not full_b:
            # a constant can always be blown up to the full shape
            if a.const is not None:
                full_a, sa = True, target
            elif b.const is not None:
                full_b, sb = True, target
            else:
                raise RaiseError(f"no full-rank operand for {target}")
        if full_a:
            v = self.graph.emit(op, [self._mat(a, target), self._mat(b, sb)])
            return _RVal(out_jshape, val=v)
        # full_b only: TensorIR ewise broadcasts the SECOND operand
        vb = self._mat(b, target)
        va = self._mat(a, sa)
        if op in ("add", "mul", "maximum"):
            return _RVal(out_jshape, val=self.graph.emit(op, [vb, va]))
        if op == "sub":                          # a - b == -(b - a)
            return _RVal(out_jshape, val=self.graph.emit(
                "neg", [self.graph.emit("sub", [vb, va])]))
        if op == "div" and a.const is not None:
            return _RVal(out_jshape, val=self.graph.emit(
                "div", [self._mat(a, target), vb]))
        raise RaiseError(f"non-commutative {op} with broadcast first operand")

    def _eval_expr(self, e, xs_rv: List[_RVal], outer_rv: List[_RVal]) -> _RVal:
        """Evaluate a scan-body expression tree over the *full* arrays."""
        kind = e[0]
        if kind == "xs":
            return xs_rv[e[1]]
        if kind == "outer":
            return outer_rv[e[1]]
        if kind == "lit":
            a = _npc(e[1])
            return _RVal(tuple(a.shape), const=a)
        if kind == "un":
            _, op, sub = e
            a = self._eval_expr(sub, xs_rv, outer_rv)
            if op == "_recip":                   # 1 / x
                one = _RVal((), const=np.float32(1.0))
                return self._ewise_bin("div", one, a, a.jshape)
            return self._ewise_un(op, a, a.jshape)
        _, op, e1, e2 = e
        a = self._eval_expr(e1, xs_rv, outer_rv)
        b = self._eval_expr(e2, xs_rv, outer_rv)
        out_jshape = np.broadcast_shapes(tuple(a.jshape), tuple(b.jshape))
        return self._ewise_bin(op, a, b, out_jshape)

    # ---- per-primitive handlers ------------------------------------------

    def _h_call(self, eqn, ins):
        cj = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if cj is None:
            raise RaiseError("call primitive without an inlinable jaxpr",
                             primitive=eqn.primitive.name, equation=str(eqn))
        if hasattr(cj, "jaxpr"):                  # ClosedJaxpr
            inner, consts = cj.jaxpr, cj.consts
        elif hasattr(cj, "constvars") and not cj.constvars:
            inner, consts = cj, []                # raw Jaxpr (remat2)
        else:
            raise RaiseError("call primitive without an inlinable jaxpr",
                             primitive=eqn.primitive.name, equation=str(eqn))
        if len(inner.invars) != len(ins):
            raise RaiseError("call arity mismatch",
                             primitive=eqn.primitive.name, equation=str(eqn))
        return self.run(inner, consts, ins)

    def _h_bin(self, eqn, ins):
        op = _BIN_PRIMS[eqn.primitive.name]
        return [self._ewise_bin(op, ins[0], ins[1],
                                eqn.outvars[0].aval.shape, eqn)]

    def _h_un(self, eqn, ins):
        op = _UN_PRIMS[eqn.primitive.name]
        return [self._ewise_un(op, ins[0], eqn.outvars[0].aval.shape, eqn)]

    def _h_fold_only(self, eqn, ins):
        prim = eqn.primitive.name
        for rv in ins:
            if rv.const is None:
                if prim in ("lt", "le", "gt", "ge", "eq"):
                    # defer: only an all-const select_n may consume this
                    return [_RVal(tuple(eqn.outvars[0].aval.shape),
                                  note=f"non-constant comparison "
                                       f"{prim!r} (boolean dtype has no "
                                       f"TensorIR representation)")]
                raise RaiseError(
                    f"primitive {prim!r} is only supported on constants",
                    primitive=prim, equation=str(eqn))
        out = _fold(_FOLD_ONLY[prim], *[_npc(rv.const) for rv in ins])
        return [_RVal(tuple(eqn.outvars[0].aval.shape), const=np.asarray(out))]

    def _h_integer_pow(self, eqn, ins):
        y = eqn.params["y"]
        (a,) = ins
        out_jshape = tuple(eqn.outvars[0].aval.shape)
        if a.const is not None:
            return [_RVal(out_jshape,
                          const=_npc(a.const).astype(np.float32) ** y)]
        if y == 2:
            return [self._ewise_bin("mul", a, a, out_jshape, eqn)]
        if y == 3:
            sq = self._ewise_bin("mul", a, a, out_jshape, eqn)
            return [self._ewise_bin("mul", sq, a, out_jshape, eqn)]
        raise RaiseError(f"integer_pow with exponent {y}",
                         primitive="integer_pow", equation=str(eqn))

    def _h_identity(self, eqn, ins):
        prim = eqn.primitive.name
        (a,) = ins[:1]
        if prim == "convert_element_type":
            nd = np.dtype(eqn.params["new_dtype"])
            if a.const is not None:
                return [dataclasses.replace(a, const=_npc(a.const).astype(
                    np.float32 if nd.kind == "f" else nd))]
            if nd.kind != "f":
                raise RaiseError(
                    f"convert_element_type to non-float {nd} on a traced "
                    f"value", primitive=prim, equation=str(eqn))
            # the raised pipeline computes in float32 throughout
        return [dataclasses.replace(a,
                                    jshape=tuple(eqn.outvars[0].aval.shape))]

    def _reshape_like(self, eqn, a: _RVal, new_shape) -> List[_RVal]:
        new_shape = tuple(int(d) for d in new_shape)
        if a.const is not None:
            arr = _npc(a.const)
            if arr.shape != tuple(a.jshape):
                arr = np.broadcast_to(arr, a.jshape)
            return [_RVal(new_shape, const=arr.reshape(new_shape))]
        self._need(a, eqn)
        target = canon2d(new_shape)
        s = tuple(a.val.type.shape)
        if s == target or all(d in (1, t) for d, t in zip(s, target)):
            return [dataclasses.replace(a, jshape=new_shape)]
        raise RaiseError(
            f"reshape {tuple(a.jshape)} -> {new_shape} does not preserve the "
            f"rank-2 canonical layout {s} -> {target}",
            primitive=eqn.primitive.name, equation=str(eqn))

    def _h_reshape(self, eqn, ins):
        if eqn.params.get("dimensions") is not None:
            raise RaiseError("reshape with dimension permutation",
                             primitive="reshape", equation=str(eqn))
        return self._reshape_like(eqn, ins[0], eqn.params["new_sizes"])

    def _h_squeeze(self, eqn, ins):
        return self._reshape_like(eqn, ins[0], eqn.outvars[0].aval.shape)

    def _h_broadcast_in_dim(self, eqn, ins):
        (a,) = ins
        shape = tuple(int(d) for d in eqn.params["shape"])
        bd = tuple(eqn.params["broadcast_dimensions"])
        if a.const is not None:
            arr = _npc(a.const)
            if arr.shape != tuple(a.jshape):
                arr = np.broadcast_to(arr, a.jshape)
            vshape = [1] * len(shape)
            for i, d in enumerate(bd):
                vshape[d] = arr.shape[i]
            return [_RVal(shape,
                          const=np.broadcast_to(arr.reshape(vshape), shape))]
        self._need(a, eqn)
        vshape = [1] * len(shape)
        for i, d in enumerate(bd):
            vshape[d] = a.jshape[i]
        if tuple(vshape) == shape:               # a pure reshape
            return self._reshape_like(eqn, a, shape)
        # a real broadcast: keep the (smaller) value, defer materialisation
        # to the consumer — legal when the rank-2 layout still broadcasts
        # the same way (dims 1-or-full against canon2d(shape))
        target = canon2d(shape)
        s = tuple(a.val.type.shape)
        if all(d in (1, t) for d, t in zip(s, target)):
            return [dataclasses.replace(a, jshape=shape)]
        raise RaiseError(
            f"broadcast {tuple(a.jshape)} -> {shape} is not expressible in "
            f"the rank-2 layout (value has shape {s})",
            primitive="broadcast_in_dim", equation=str(eqn))

    def _h_transpose(self, eqn, ins):
        (a,) = ins
        perm = tuple(eqn.params["permutation"])
        new_shape = tuple(a.jshape[p] for p in perm)
        if a.const is not None:
            arr = _npc(a.const)
            if arr.shape != tuple(a.jshape):
                arr = np.broadcast_to(arr, a.jshape)
            return [_RVal(new_shape, const=np.transpose(arr, perm))]
        self._need(a, eqn)
        nonunit = [p for p in perm if a.jshape[p] != 1]
        if nonunit == sorted(nonunit):           # only unit dims moved
            return self._reshape_like(eqn, a, new_shape)
        if len(a.jshape) == 2 and perm == (1, 0) \
                and tuple(a.val.type.shape) == canon2d(a.jshape):
            v = self.graph.emit("transpose", [a.val], perm=(1, 0))
            return [_RVal(new_shape, val=v)]
        raise RaiseError(
            f"transpose {perm} of a traced {tuple(a.jshape)} value",
            primitive="transpose", equation=str(eqn))

    def _h_reduce(self, eqn, ins):
        prim = eqn.primitive.name
        kind = "sum" if prim == "reduce_sum" else "max"
        axes = tuple(eqn.params["axes"])
        (a,) = ins
        out_jshape = tuple(eqn.outvars[0].aval.shape)
        if a.const is not None:
            fn = np.sum if kind == "sum" else np.max
            arr = _npc(a.const).astype(np.float32)
            if arr.shape != tuple(a.jshape):
                arr = np.broadcast_to(arr, a.jshape)
            return [_RVal(out_jshape, const=fn(arr, axis=axes))]
        jrank = len(a.jshape)
        if axes != (jrank - 1,):
            raise RaiseError(
                f"reduce over axes {axes} of a rank-{jrank} value — only a "
                f"last-axis (column) reduction maps to the carried TensorIR "
                f"reduce", primitive=prim, equation=str(eqn))
        va = self._force_full(a, a.jshape)
        v = self.graph.emit("reduce", [va], kind=kind, axis=1, keepdims=True)
        return [_RVal(out_jshape, val=v)]

    def _h_dot_general(self, eqn, ins):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        a, b = ins
        out_jshape = tuple(eqn.outvars[0].aval.shape)
        if a.const is not None and b.const is not None:
            out = np.tensordot(_npc(a.const).astype(np.float32),
                               _npc(b.const).astype(np.float32),
                               axes=(lc, rc))
            return [_RVal(out_jshape, const=out)]
        if lb or rb:
            raise RaiseError("dot_general with batch dimensions",
                             primitive="dot_general", equation=str(eqn))
        if len(lc) != 1 or len(rc) != 1:
            raise RaiseError("dot_general with multiple contraction dims",
                             primitive="dot_general", equation=str(eqn))
        if lc[0] != len(a.jshape) - 1:
            if a.const is not None:
                arr = np.moveaxis(_npc(a.const).astype(np.float32), lc[0], -1)
                a = _RVal(arr.shape, const=arr)
            else:
                raise RaiseError(
                    f"dot_general contracting lhs axis {lc[0]} of a rank-"
                    f"{len(a.jshape)} traced value (only the last axis maps "
                    f"to matmul)", primitive="dot_general", equation=str(eqn))
        if len(b.jshape) != 2:
            raise RaiseError(
                f"dot_general rhs must be rank-2, got {tuple(b.jshape)}",
                primitive="dot_general", equation=str(eqn))
        if rc[0] == 1:                           # contract rhs columns
            if b.const is not None:
                arr = _npc(b.const).astype(np.float32)
                if arr.shape != tuple(b.jshape):
                    arr = np.broadcast_to(arr, b.jshape)
                b = _RVal((b.jshape[1], b.jshape[0]), const=arr.T)
            else:
                self._need(b, eqn)
                v = self.graph.emit("transpose", [
                    self._force_full(b, b.jshape)], perm=(1, 0))
                b = _RVal((b.jshape[1], b.jshape[0]), val=v)
        va = self._force_full(a, a.jshape)
        k = int(a.jshape[-1])
        vb = self._mat(b, (k, int(b.jshape[1])))
        v = self.graph.emit("matmul", [va, vb])
        return [_RVal(out_jshape, val=v)]

    def _h_select_n(self, eqn, ins):
        pred, *cases = ins
        out_jshape = tuple(eqn.outvars[0].aval.shape)
        if pred.note is not None and "nan_guard" in pred.note:
            # x != x NaN-guard (e.g. jax.nn.softplus): the guarded branch
            # never fires for finite float32 pipelines — take the main value
            return [dataclasses.replace(cases[0], jshape=out_jshape)]
        if pred.const is not None and len(cases) == 2:
            p = _npc(pred.const)
            if p.dtype != np.bool_:
                p = p.astype(bool)
            p = np.broadcast_to(p, pred.jshape) if p.shape != tuple(
                pred.jshape) else p
            if not p.any():
                return [dataclasses.replace(cases[0], jshape=out_jshape)]
            if p.all():
                return [dataclasses.replace(cases[1], jshape=out_jshape)]
            pf = _RVal(tuple(pred.jshape), const=p.astype(np.float32))
            pn = _RVal(tuple(pred.jshape),
                       const=(1.0 - p.astype(np.float32)))
            t0 = self._ewise_bin("mul", cases[0], pn, out_jshape, eqn)
            t1 = self._ewise_bin("mul", cases[1], pf, out_jshape, eqn)
            return [self._ewise_bin("add", t0, t1, out_jshape, eqn)]
        raise RaiseError("select_n with a traced (non-constant) predicate",
                         primitive="select_n", equation=str(eqn))

    def _h_ne(self, eqn, ins):
        a, b = eqn.invars
        if a is b:                               # x != x: the NaN guard
            return [_RVal(tuple(eqn.outvars[0].aval.shape),
                          note="nan_guard comparison x != x")]
        if ins[0].const is not None and ins[1].const is not None:
            out = _npc(ins[0].const) != _npc(ins[1].const)
            return [_RVal(tuple(eqn.outvars[0].aval.shape),
                          const=np.asarray(out))]
        return [_RVal(tuple(eqn.outvars[0].aval.shape),
                      note="non-constant comparison 'ne' (boolean dtype has "
                           "no TensorIR representation)")]

    def _h_iota(self, eqn, ins):
        shape = tuple(int(d) for d in eqn.params["shape"])
        dim = eqn.params["dimension"]
        vshape = [1] * len(shape)
        vshape[dim] = shape[dim]
        arr = np.broadcast_to(
            np.arange(shape[dim], dtype=np.float32).reshape(vshape), shape)
        return [_RVal(shape, const=arr)]

    def _h_cumsum(self, eqn, ins):
        (a,) = ins
        out_jshape = tuple(eqn.outvars[0].aval.shape)
        if eqn.params.get("reverse"):
            raise RaiseError("reverse cumsum", primitive="cumsum",
                             equation=str(eqn))
        if a.const is not None:
            arr = _npc(a.const).astype(np.float32)
            return [_RVal(out_jshape,
                          const=np.cumsum(arr, axis=eqn.params["axis"]))]
        if len(a.jshape) != 2 or eqn.params["axis"] != 0:
            raise RaiseError(
                f"cumsum over axis {eqn.params['axis']} of a rank-"
                f"{len(a.jshape)} value — TensorIR scan runs over axis 0 of "
                f"a rank-2 value", primitive="cumsum", equation=str(eqn))
        va = self._force_full(a, a.jshape)
        v = self.graph.emit("scan", [va], kind="cumsum", axis=0)
        self.scan_lengths.append(int(a.jshape[0]))
        return [_RVal(out_jshape, val=v)]

    def _h_scan(self, eqn, ins):
        p = eqn.params
        if p.get("reverse"):
            raise RaiseError("reverse-time scan", primitive="scan",
                             equation=str(eqn))
        num_consts, num_carry = p["num_consts"], p["num_carry"]
        if num_carry != 1:
            raise RaiseError(f"scan with {num_carry} carries (only a single "
                             f"carried state raises)", primitive="scan",
                             equation=str(eqn))
        closed = p["jaxpr"]
        length = int(p["length"])
        outer_rv = ins[:num_consts]
        carry_rv = ins[num_consts]
        xs_rv = ins[num_consts + 1:]
        if carry_rv.const is None or np.any(_npc(carry_rv.const) != 0):
            raise RaiseError(
                "scan carry must be initialised to a constant zero array "
                "(h_0 = 0 in the carried TensorIR scan)",
                primitive="scan", equation=str(eqn))
        if len(carry_rv.jshape) > 1:
            raise RaiseError(
                f"scan carry of rank {len(carry_rv.jshape)} (the rank-2 "
                f"TensorIR scan carries one row)", primitive="scan",
                equation=str(eqn))
        for rv in xs_rv:
            if len(rv.jshape) < 2 and rv.const is None:
                raise RaiseError(
                    "scan over a rank-1 traced sequence (time must be a row "
                    "axis in the rank-2 layout)", primitive="scan",
                    equation=str(eqn))
        alpha, beta = _linear_body(closed.jaxpr, closed.consts,
                                   num_consts, len(xs_rv))
        ys_jshape = tuple(eqn.outvars[1].aval.shape)
        if _e_is_one(alpha):                     # h_t = h_{t-1} + u_t
            u = self._eval_expr(beta, xs_rv, outer_rv)
            vu = self._force_full(u, ys_jshape)
            v = self.graph.emit("scan", [vu], kind="cumsum", axis=0)
        else:
            a = self._eval_expr(alpha, xs_rv, outer_rv)
            u = self._eval_expr(beta, xs_rv, outer_rv)
            va = self._force_full(a, ys_jshape)
            vu = self._force_full(u, ys_jshape)
            v = self.graph.emit("scan", [va, vu], kind="linear", axis=0)
        self.scan_lengths.append(length)
        ys = _RVal(ys_jshape, val=v)
        final = _RVal(tuple(eqn.outvars[0].aval.shape),
                      note="the scan's final carry (only the full h_t "
                           "sequence is materialised by TensorIR scan)")
        return [final, ys]

    # ---- driver -----------------------------------------------------------

    _HANDLERS: Dict[str, Callable] = {}

    def run(self, jaxpr, consts, invals: List[_RVal]) -> List[_RVal]:
        env: Dict[Any, _RVal] = {}

        def read(v) -> _RVal:
            if isinstance(v, _JaxLiteral):
                val = _npc(v.val)
                return _RVal(tuple(np.shape(val)), const=val)
            return env[v]

        for cv, cval in zip(jaxpr.constvars, consts):
            arr = _npc(cval)
            env[cv] = _RVal(tuple(arr.shape), const=arr)
        if len(jaxpr.invars) != len(invals):
            raise RaiseError("jaxpr arity mismatch")
        for iv, rv in zip(jaxpr.invars, invals):
            env[iv] = rv
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            handler = self._HANDLERS.get(prim)
            if handler is None:
                raise RaiseError(
                    f"primitive {prim!r} is outside the raisable vocabulary",
                    primitive=prim, equation=str(eqn))
            ins = [read(v) for v in eqn.invars]
            try:
                outs = handler(self, eqn, ins)
            except RaiseError:
                raise
            except Exception as e:               # defensive: name the site
                raise RaiseError(f"failed to raise: {e}", primitive=prim,
                                 equation=str(eqn))
            for ov, rv in zip(eqn.outvars, outs):
                env[ov] = rv
        return [read(v) for v in jaxpr.outvars]

    def output_value(self, rv: _RVal) -> Value:
        return self._force_full(rv, rv.jshape)


_Raiser._HANDLERS.update({p: _Raiser._h_call for p in _CALL_PRIMS})
_Raiser._HANDLERS.update({p: _Raiser._h_bin for p in _BIN_PRIMS})
_Raiser._HANDLERS.update({p: _Raiser._h_un for p in _UN_PRIMS})
_Raiser._HANDLERS.update({p: _Raiser._h_fold_only for p in _FOLD_ONLY})
_Raiser._HANDLERS.update({p: _Raiser._h_identity for p in _IDENTITY_PRIMS})
_Raiser._HANDLERS.update({
    "integer_pow": _Raiser._h_integer_pow,
    "reshape": _Raiser._h_reshape,
    "squeeze": _Raiser._h_squeeze,
    "broadcast_in_dim": _Raiser._h_broadcast_in_dim,
    "transpose": _Raiser._h_transpose,
    "reduce_sum": _Raiser._h_reduce,
    "reduce_max": _Raiser._h_reduce,
    "dot_general": _Raiser._h_dot_general,
    "select_n": _Raiser._h_select_n,
    "ne": _Raiser._h_ne,
    "iota": _Raiser._h_iota,
    "cumsum": _Raiser._h_cumsum,
    "scan": _Raiser._h_scan,
})


# --------------------------------------------------------------------------
# public artifact
# --------------------------------------------------------------------------


@dataclasses.dataclass
class RaisedGraph:
    """A TensorIR graph raised from a traced JAX function.

    ``graph`` takes the user arguments (``arg0``...) first, then the
    lazily-materialised constants (``c0``...); :meth:`bind` rebuilds the full
    positional input list from just the user arguments.
    """

    graph: Graph
    const_bindings: Dict[str, np.ndarray]
    n_args: int
    arg_shapes: List[Tuple[int, ...]]
    out_shapes: List[Tuple[int, ...]]
    scan_lengths: List[int]
    hlo_trips: Optional[Dict[str, int]] = None

    @property
    def unlowerable_ops(self) -> List[str]:
        return sorted({op.opname for op in self.graph.ops
                       if op.opname not in _LOWERABLE_OPS})

    @property
    def lowerable(self) -> bool:
        return not self.unlowerable_ops

    def bind(self, *args) -> List[np.ndarray]:
        if len(args) != self.n_args:
            raise ValueError(f"{self.graph.name} takes {self.n_args} "
                             f"arguments, got {len(args)}")
        bound = []
        for v, a in zip(self.graph.inputs[:self.n_args], args):
            arr = np.asarray(a, np.float32).reshape(v.type.shape)
            bound.append(arr)
        for v in self.graph.inputs[self.n_args:]:
            bound.append(self.const_bindings[v.name])
        return bound

    def run_ref(self, *args) -> List[np.ndarray]:
        outs = self.graph.eval_np(*self.bind(*args))
        return [o.reshape(s) for o, s in zip(outs, self.out_shapes)]

    def compile(self, **kw):
        from . import pipeline
        return pipeline.compile_traced(self.graph, **kw)

    def run_compiled(self, compiled, *args, backend: str = "jax"):
        fn = {"ref": compiled.run_ref, "jax": compiled.run_jax,
              "pallas": compiled.run_pallas}[backend]
        outs = fn(*self.bind(*args))
        if backend == "pallas":              # one output, not a list
            outs = [outs]
        return [np.asarray(o).reshape(s)
                for o, s in zip(outs, self.out_shapes)]

    def explore(self, **kw):
        from . import dse
        return dse.explore(self.graph, **kw)


def _as_aval(s):
    if hasattr(s, "shape") and hasattr(s, "dtype"):
        return jax.ShapeDtypeStruct(tuple(s.shape), jnp.float32)
    return jax.ShapeDtypeStruct(tuple(s), jnp.float32)


def _sanitize(name: str) -> str:
    return re.sub(r"[^\w.\-]", "_", name)


def raise_jaxpr(fn: Callable, *in_specs, name: Optional[str] = None,
                check_hlo_trips: bool = False) -> RaisedGraph:
    """Trace ``fn`` at ``in_specs`` (shapes / arrays / specs) and raise the
    jaxpr into a TensorIR :class:`RaisedGraph`.

    With ``check_hlo_trips=True``, also compiles ``fn`` through XLA and
    cross-checks the scan lengths recovered by raising against the
    ``while``-loop trip counts ``launch.hlo_analysis`` walks out of the
    optimized HLO text.
    """
    avals = [_as_aval(s) for s in in_specs]
    closed = jax.make_jaxpr(fn)(*avals)
    gname = _sanitize(name or getattr(fn, "__name__", "raised"))
    r = _Raiser(gname)
    invals = []
    for i, a in enumerate(avals):
        v = r.graph.add_input(f"arg{i}", TensorType(canon2d(a.shape)))
        invals.append(_RVal(tuple(a.shape), val=v))
    outs = r.run(closed.jaxpr, closed.consts, invals)
    out_vals = [r.output_value(rv) for rv in outs]
    r.graph.set_outputs(*out_vals)
    r.graph.verify()
    hlo_trips = None
    if check_hlo_trips:
        hlo_trips = hlo_while_trips(fn, avals)
        for length in r.scan_lengths:
            if hlo_trips and length not in hlo_trips.values():
                raise RaiseError(
                    f"raised scan length {length} not found among HLO while "
                    f"trip counts {hlo_trips} — raising and the compiled "
                    f"module disagree about the recurrence")
    return RaisedGraph(graph=r.graph, const_bindings=r.const_bindings,
                       n_args=len(avals),
                       arg_shapes=[tuple(a.shape) for a in avals],
                       out_shapes=[tuple(rv.jshape) for rv in outs],
                       scan_lengths=list(r.scan_lengths),
                       hlo_trips=hlo_trips)


def hlo_while_trips(fn: Callable, avals) -> Dict[str, int]:
    """Trip counts of every ``while`` loop in the XLA-optimized HLO of
    ``fn``, via the call-graph walk in ``launch.hlo_analysis``."""
    from ..launch.hlo_analysis import analyze_hlo_module
    text = jax.jit(fn).lower(*avals).compile().as_text()
    return dict(analyze_hlo_module(text).while_trips)


# --------------------------------------------------------------------------
# hand-written kernel mirrors (equivalence targets for tests)
# --------------------------------------------------------------------------


def _flash_fn(q, kt, v, mask):
    s = q @ kt + mask
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    return (p @ v) / l


def reference_flash(sq: int, sk: int, d: int,
                    name: Optional[str] = None) -> RaisedGraph:
    """Raise the jnp spelling of flash attention; canonical-identical to
    ``frontend.flash_attention_graph(sq, sk, d)``."""
    return raise_jaxpr(_flash_fn, (sq, d), (d, sk), (sk, d), (sq, sk),
                       name=name or f"flash_{sq}x{sk}x{d}")


def reference_decode(rep: int, smax: int, hd: int,
                     name: Optional[str] = None) -> RaisedGraph:
    return raise_jaxpr(_flash_fn, (rep, hd), (hd, smax), (smax, hd),
                       (rep, smax), name=name or f"decode_{rep}x{smax}x{hd}")


def _scan_linear(a, u):
    def step(h, xs):
        a_t, u_t = xs
        h = a_t * h + u_t
        return h, h
    h0 = jnp.zeros(a.shape[1:], jnp.float32)
    _, ys = jax.lax.scan(step, h0, (a, u))
    return ys


def reference_ssd(s: int, p: int, n: int,
                  name: Optional[str] = None) -> RaisedGraph:
    """Raise the jnp spelling of the SSD recurrence; canonical-identical to
    ``frontend.ssd_scan_graph(s, p, n)``."""
    pn = p * n

    def f(a, u, ct, g):
        h = _scan_linear(a, u)
        return (h * ct) @ g
    return raise_jaxpr(f, (s, pn), (s, pn), (s, pn), (pn, p),
                       name=name or f"ssd_{s}x{p}x{n}")


# --------------------------------------------------------------------------
# per-config model blocks
# --------------------------------------------------------------------------


@dataclasses.dataclass
class BlockReport:
    """One per-config forward-pass region and its raising outcome."""

    config: str
    block: str
    fn: Callable
    example_inputs: Tuple[np.ndarray, ...]
    raised: Optional[RaisedGraph] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.raised is not None


def model_block_suite(config_name: str, seq: int = 8, seed: int = 0
                      ) -> Dict[str, Tuple[Callable, tuple]]:
    """The fused forward-pass regions of one (reduced) config, as plain jax
    functions over example inputs — the raising corpus.

    Deliberately includes regions known to be outside the vocabulary (rope's
    slice/concatenate, the MoE router's top_k) so the raisability table and
    the diagnostics tests have real negative rows.
    """
    from ..configs.base import get_config, reduced
    from ..models import layers as L

    cfg = reduced(get_config(config_name))
    rng = np.random.default_rng(seed)
    d = cfg.d_model
    kinds = set(cfg.layer_kinds())

    def randn(*shape, scale=1.0):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    blocks: Dict[str, Tuple[Callable, tuple]] = {}
    x = randn(1, seq, d)
    w_norm = jnp.asarray(randn(d, scale=0.1))
    blocks["rmsnorm"] = (
        lambda x: L.rmsnorm(x, w_norm, cfg.norm_eps), (x,))

    has_dense_mlp = bool(kinds & {"attn", "rglru"}) or (
        cfg.moe is not None and cfg.moe.first_dense_layers > 0)
    if has_dense_mlp:
        mk = L.Maker("init", jax.random.PRNGKey(seed))
        mlp_p = L.init_mlp(cfg, mk)
        blocks["mlp"] = (lambda x: L.apply_mlp(mlp_p, x, cfg), (x,))

    vocab = min(cfg.vocab_size, 256)
    w_head_norm = jnp.asarray(randn(d, scale=0.1))
    if cfg.tie_embeddings:
        w_emb = jnp.asarray(randn(vocab, d, scale=0.05))

        def head(x):
            h = L.rmsnorm(x, w_head_norm, cfg.norm_eps)
            return jnp.einsum("bsd,vd->bsv", h, w_emb)
    else:
        w_head = jnp.asarray(randn(d, vocab, scale=0.05))

        def head(x):
            h = L.rmsnorm(x, w_head_norm, cfg.norm_eps)
            return jnp.einsum("bsd,dv->bsv", h, w_head)
    blocks["head"] = (head, (x,))

    if "attn" in kinds or cfg.encoder is not None or cfg.mla is not None:
        hd = cfg.resolved_head_dim
        scale = 1.0 / np.sqrt(hd)
        mask = np.where(np.arange(seq)[:, None] >= np.arange(seq)[None, :],
                        0.0, -1e30).astype(np.float32)
        blocks["attn_softmax"] = (
            _flash_fn, (randn(seq, hd, scale=scale), randn(hd, seq),
                        randn(seq, hd), mask))

        x4 = randn(1, seq, 2, hd if hd % 2 == 0 else hd + 1)
        positions = jnp.arange(seq, dtype=jnp.int32)[None, :]
        blocks["rope"] = (
            lambda x4: L.rope(x4, positions, cfg.rope_theta), (x4,))

    if "ssd" in kinds:
        p_dim, n_dim = 4, 4
        pn = p_dim * n_dim
        a = rng.uniform(0.2, 0.95, (seq, pn)).astype(np.float32)
        g = np.kron(np.eye(p_dim), np.ones((n_dim, 1))).astype(np.float32)

        def ssd_core(a, u, ct, g):
            h = _scan_linear(a, u)
            return (h * ct) @ g
        blocks["ssd_core"] = (ssd_core,
                              (a, randn(seq, pn), randn(seq, pn), g))

    if "rglru" in kinds:
        w = (cfg.rglru.width or d) if cfg.rglru is not None else d
        c = cfg.rglru.c if cfg.rglru is not None else 8.0
        a_param = jnp.asarray(randn(w))

        def rglru_core(x2, a_gate, i_gate):
            log_a = -c * jax.nn.softplus(a_param)[None, :] \
                * jax.nn.sigmoid(a_gate)
            a = jnp.exp(log_a)
            gated = jax.nn.sigmoid(i_gate) * x2
            mult = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-9))

            def step(h, inp):
                a_t, gx_t, m_t = inp
                h = a_t * h + m_t * gx_t
                return h, h
            h0 = jnp.zeros((x2.shape[1],), jnp.float32)
            _, hs = jax.lax.scan(step, h0, (a, gated, mult))
            return hs
        blocks["rglru_core"] = (
            rglru_core, (randn(seq, w), randn(seq, w), randn(seq, w)))

    if cfg.moe is not None:
        n_exp = cfg.moe.num_experts
        top_k = cfg.moe.top_k
        w_router = jnp.asarray(randn(d, n_exp, scale=0.05))

        def moe_router(x2):
            logits = x2 @ w_router
            probs = jax.nn.softmax(logits, axis=-1)
            vals, _ = jax.lax.top_k(probs, top_k)
            return vals
        blocks["moe_router"] = (moe_router, (randn(seq, d),))

    return blocks


def raise_model_blocks(config_name: str, seq: int = 8, seed: int = 0,
                       check_hlo_trips: bool = False) -> List[BlockReport]:
    """Raise every block of one config; failures become diagnostics, not
    exceptions."""
    suite = model_block_suite(config_name, seq=seq, seed=seed)
    reports = []
    for block, (fn, inputs) in suite.items():
        rep = BlockReport(config=config_name, block=block, fn=fn,
                          example_inputs=tuple(inputs))
        try:
            rep.raised = raise_jaxpr(
                fn, *inputs, name=f"{config_name}.{block}",
                check_hlo_trips=check_hlo_trips)
        except RaiseError as e:
            rep.error = str(e)
        reports.append(rep)
        if rep.raised is not None:
            # the raised graph must agree with the traced function on the
            # example inputs — raising is only useful if it is *correct*
            pass
    return reports


def raising_report(config_name: str, seq: int = 8, seed: int = 0) -> str:
    """Human-readable per-block raising report (used by ``reproc --raise
    CONFIG`` and the generated docs)."""
    reports = raise_model_blocks(config_name, seq=seq, seed=seed)
    lines = [f"// raising report for config {config_name} "
             f"(seq={seq}, reduced)"]
    for rep in reports:
        if rep.ok:
            rg = rep.raised
            lines.append(f"// block {rep.block}: RAISED — "
                         f"{len(rg.graph.ops)} ops, "
                         f"{len(rg.graph.inputs) - rg.n_args} captured "
                         f"consts, lowerable={rg.lowerable}")
            lines.append(str(rg.graph))
        else:
            first = rep.error.splitlines()[0]
            lines.append(f"// block {rep.block}: NOT RAISABLE — {first}")
    return "\n".join(lines) + "\n"
