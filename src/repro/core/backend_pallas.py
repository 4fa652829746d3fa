"""Pallas backend: emit a ``pl.pallas_call`` TPU kernel from scheduled LoopIR.

This is the RTL-emission stage of the paper's pipeline (Calyx -> System
Verilog): the scheduled LoopIR's GRID loops become the pallas grid, tile
shapes become BlockSpecs (explicit VMEM tiling), and the statement body
becomes the kernel body executed per grid step by the Mosaic "synthesis"
layer.

Like Calyx, the emitter accepts a *structured subset* of the IR — the
shapes produced by ``lowering.py`` + ``schedule.py`` for contraction
kernels:

    Loop(g0 @grid) { Loop(g1 @grid) { [Loop(g2 @grid)]
        [ZeroTile(acc)]
        ( Loop(k @seq|@unrolled) { MatmulTile(acc, A, B) } | MatmulTile )
        [EwiseTile epilogue ...]*
        [EwiseTile copy -> HBM out]
    }}}

Two canonical layouts fall out of the schedules, mirroring the paper:

  * ``(i, j)`` grid, K inside the block  — the *inner-flattened* analogue:
    each grid step holds a full ``(tm, K)``/(``K, tn``) stripe in VMEM, so
    VMEM consumption grows with K (Fig. 3(b): resources ∝ size);
  * ``(i, j, k)`` grid                   — the *nested* analogue: one
    ``(tm, tk)`` tile per step, one output tile time-multiplexed across
    the k grid dimension (Fig. 3(a): constant resources, datapath reuse).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .loop_ir import (EwiseTile, FillTile, Kernel, Loop, LoopKind, MatmulTile,
                      MemSpace, ReduceTile, ScanTile, Stmt, TileRef, ZeroTile,
                      _stmt_refs, _stmt_written_refs)
from .backend_jax import _EWISE_JNP, _JNP_DTYPE
from ..device import pallas_interpret


class EmitError(NotImplementedError):
    """Raised when a kernel is outside the emitter's structured subset."""


@dataclasses.dataclass
class _Plan:
    grid_vars: List[str]                 # outer -> inner
    grid: Tuple[int, ...]
    inner_body: List[Stmt]
    k_loop: Optional[Loop]               # reduction loop inside block, if any
    k_grid_var: Optional[str]            # reduction on the grid, if any
    in_buffers: List[str]
    out_buffer: str
    block_specs: Dict[str, Tuple[Tuple[int, ...], Tuple[object, ...]]]
    acc_name: Optional[str]
    matmul: Optional[MatmulTile] = None


def _analyze(kernel: Kernel) -> _Plan:
    kernel.verify()
    # 1. peel GRID loops
    grid_vars: List[str] = []
    grid: List[int] = []
    stmts = kernel.body
    if len(stmts) != 1 or not isinstance(stmts[0], Loop):
        raise EmitError(f"{kernel.name}: body must be a single loop nest")
    cur: Stmt = stmts[0]
    while isinstance(cur, Loop) and cur.kind == LoopKind.GRID:
        grid_vars.append(cur.var.name)
        grid.append(cur.var.extent)
        if len(cur.body) == 1 and isinstance(cur.body[0], Loop) \
                and cur.body[0].kind == LoopKind.GRID:
            cur = cur.body[0]
        else:
            inner = cur.body
            break
    else:
        raise EmitError(f"{kernel.name}: no GRID loops — run a schedule first")

    if not grid_vars:
        raise EmitError(f"{kernel.name}: no GRID loops")

    # 2. classify the inner statements
    acc_name = None
    k_loop = None
    k_grid_var = None
    matmul: Optional[MatmulTile] = None
    epilogue: List[EwiseTile] = []
    for s in inner:
        if isinstance(s, ZeroTile):
            if s.dst.buffer.space == MemSpace.VREG:
                acc_name = s.dst.buffer.name
            # Zero of the HBM out with a k grid var is implicit (pl.when)
        elif isinstance(s, Loop):
            if len(s.body) != 1 or not isinstance(s.body[0], MatmulTile):
                raise EmitError(f"{kernel.name}: reduction loop body must be "
                                f"a single MatmulTile")
            if s.kind == LoopKind.GRID:
                # reduction mapped onto the grid (time-multiplexed schedule):
                # hoist it as the innermost grid dimension; accumulation
                # becomes pl.when-guarded updates of the revisited out block.
                grid_vars.append(s.var.name)
                grid.append(s.var.extent)
                k_grid_var = s.var.name
                matmul = s.body[0]
                continue
            if k_loop is not None or s.kind not in (LoopKind.SEQUENTIAL,
                                                    LoopKind.UNROLLED):
                raise EmitError(f"{kernel.name}: unsupported inner loop {s.var}")
            k_loop = s
            matmul = s.body[0]
        elif isinstance(s, MatmulTile):
            matmul = s
            kvars = [v for e in (*s.lhs.index, *s.rhs.index)
                     for v, _ in e.coeffs if v in grid_vars[2:]]
            if kvars:
                k_grid_var = kvars[0]
        elif isinstance(s, EwiseTile):
            epilogue.append(s)
        else:
            raise EmitError(f"{kernel.name}: unsupported stmt {s}")
    if matmul is None:
        raise EmitError(f"{kernel.name}: no MatmulTile found")
    # a 3-long grid means k lives on the grid
    if len(grid_vars) == 3:
        k_grid_var = grid_vars[2]

    # HBM buffers *written* inside the block that are not the kernel
    # output are SSA temporaries left by fusion; the emitter forwards
    # their values through registers instead of materialising them
    # (the codegen equivalent of Calyx wiring cells directly).
    out_names_ = {b.name for b in kernel.outputs}
    written = set()
    for s in inner:
        if isinstance(s, (ZeroTile, MatmulTile, EwiseTile)) \
                and s.dst.buffer.space == MemSpace.HBM \
                and s.dst.buffer.name not in out_names_:
            written.add(s.dst.buffer.name)

    # 3. build block specs for every HBM buffer touched
    inner_vars = {} if k_loop is None else {k_loop.var.name: k_loop.var.extent}
    specs: Dict[str, Tuple[Tuple[int, ...], Tuple[object, ...]]] = {}

    def visit(ref: TileRef):
        if ref.buffer.space != MemSpace.HBM or ref.buffer.name in written:
            return
        block: List[int] = []
        imap: List[object] = []   # either a grid-var name or 0
        for d, e in enumerate(ref.index):
            t = ref.tile[d]
            if not e.coeffs:
                # constant index: block covers [const*t, const*t + t)
                if e.const != 0:
                    raise EmitError(f"{kernel.name}: non-zero const index")
                block.append(t)
                imap.append(0)
            elif len(e.coeffs) == 1:
                v, stride = e.coeffs[0]
                if stride != 1:
                    raise EmitError(f"{kernel.name}: strided index on {v}")
                if v in grid_vars:
                    block.append(t)
                    imap.append(v)
                elif v in inner_vars:
                    block.append(t * inner_vars[v])
                    imap.append(0)
                else:
                    raise EmitError(f"{kernel.name}: unbound index var {v}")
            else:
                raise EmitError(f"{kernel.name}: multi-var affine index "
                                f"(apply split+grid only)")
        prev = specs.get(ref.buffer.name)
        spec = (tuple(block), tuple(imap))
        if prev is not None and prev != spec:
            raise EmitError(f"{kernel.name}: inconsistent refs to "
                            f"{ref.buffer.name}: {prev} vs {spec}")
        specs[ref.buffer.name] = spec

    for s in inner:
        if isinstance(s, Loop):
            for b in s.body:
                if isinstance(b, MatmulTile):
                    visit(b.dst), visit(b.lhs), visit(b.rhs)
        elif isinstance(s, ZeroTile):
            visit(s.dst)
        elif isinstance(s, MatmulTile):
            visit(s.dst), visit(s.lhs), visit(s.rhs)
        elif isinstance(s, EwiseTile):
            visit(s.dst)
            for r in s.srcs:
                visit(r)

    out_names = [b.name for b in kernel.outputs]
    if len(out_names) != 1:
        raise EmitError(f"{kernel.name}: exactly one output supported")
    out = out_names[0]
    ins = [b.name for b in kernel.params
           if b.name in specs and b.name != out]
    return _Plan(grid_vars=grid_vars, grid=tuple(grid), inner_body=inner,
                 k_loop=k_loop, k_grid_var=k_grid_var, in_buffers=ins,
                 out_buffer=out, block_specs=specs, acc_name=acc_name,
                 matmul=matmul)


def emit(kernel: Kernel,
         interpret: Optional[bool] = None) -> Callable[..., jax.Array]:
    """Emit ``f(*hbm_inputs) -> out`` for a scheduled kernel.

    Dispatch: the single-nest GEMM classifier (``_analyze``) first — it
    produces the tight BlockSpec'd pallas_call the contraction schedules
    want — and the general multi-nest emitter (``emit_general``) for
    everything else (the serving-kernel graphs: several chained nests
    with carried reductions and scans).

    ``interpret`` defaults to the platform's choice
    (:func:`repro.device.pallas_interpret`): the interpreter on the CPU
    backend, Mosaic elsewhere.
    """
    interpret = pallas_interpret(interpret)
    try:
        return _emit_gemm(kernel, interpret=interpret)
    except EmitError:
        return emit_general(kernel, interpret=interpret)


def _emit_gemm(kernel: Kernel,
               interpret: bool) -> Callable[..., jax.Array]:
    """The original single-nest contraction emitter (see module doc)."""
    plan = _analyze(kernel)
    buffers = {b.name: b for b in kernel.params + kernel.scratch}
    out_buf = buffers[plan.out_buffer]
    out_dtype = _JNP_DTYPE[out_buf.type.dtype]
    gpos = {v: i for i, v in enumerate(plan.grid_vars)}

    def mk_index_map(imap):
        def index_map(*gids):
            return tuple(gids[gpos[v]] if isinstance(v, str) else 0
                         for v in imap)
        return index_map

    in_specs = []
    for name in plan.in_buffers:
        block, imap = plan.block_specs[name]
        in_specs.append(pl.BlockSpec(block, mk_index_map(imap)))
    out_block, out_imap = plan.block_specs[plan.out_buffer]
    out_spec = pl.BlockSpec(out_block, mk_index_map(out_imap))

    mm = plan.matmul
    tm, tk = mm.lhs.tile[-2:]
    tn = mm.rhs.tile[-1]
    lhs_name, rhs_name = mm.lhs.buffer.name, mm.rhs.buffer.name
    k_on_grid = plan.k_grid_var is not None
    k_extent = plan.k_loop.var.extent if plan.k_loop is not None else 1
    k_unrolled = (plan.k_loop is not None
                  and plan.k_loop.kind == LoopKind.UNROLLED)
    # which dim of each operand block the k sub-tiling walks
    epilogue = [s for s in plan.inner_body if isinstance(s, EwiseTile)]

    def body(*refs):
        ref_of = dict(zip(plan.in_buffers + [plan.out_buffer], refs))
        a_ref, b_ref = ref_of[lhs_name], ref_of[rhs_name]
        o_ref = ref_of[plan.out_buffer]

        def dot_k(kk):
            a = a_ref[..., :, pl.dslice(kk * tk, tk)] if k_extent > 1 else a_ref[...]
            b = b_ref[pl.dslice(kk * tk, tk), :] if k_extent > 1 else b_ref[...]
            return jnp.dot(a, b, preferred_element_type=jnp.float32)

        if k_on_grid:
            k_id = pl.program_id(gpos[plan.k_grid_var])

            @pl.when(k_id == 0)
            def _init():
                o_ref[...] = jnp.zeros_like(o_ref)

            o_ref[...] = o_ref[...] + dot_k(0).astype(out_dtype)
            last = pl.num_programs(gpos[plan.k_grid_var]) - 1
            if epilogue:
                @pl.when(k_id == last)
                def _epi():
                    o_ref[...] = _apply_epilogue(
                        epilogue, o_ref[...], ref_of, plan).astype(out_dtype)
        else:
            acc = jnp.zeros((tm, tn), jnp.float32)
            if k_unrolled or k_extent <= 4:
                for kk in range(k_extent):
                    acc = acc + dot_k(kk)
            else:
                acc = jax.lax.fori_loop(
                    0, k_extent, lambda kk, c: c + dot_k(kk), acc)
            acc = _apply_epilogue(epilogue, acc, ref_of, plan)
            o_ref[...] = acc.astype(out_dtype)

    fname = f"stagecc_pallas_{kernel.name}"
    call = pl.pallas_call(
        body,
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_buf.shape, out_dtype),
        interpret=interpret,
        name=kernel.name,
    )

    def fn(*inputs):
        by_name = dict(zip(plan.in_buffers, inputs))
        args = [jnp.asarray(by_name[n], _JNP_DTYPE[buffers[n].type.dtype])
                for n in plan.in_buffers]
        return call(*args)

    fn.__name__ = fname
    fn.plan = plan  # exposed for tests / resource introspection
    return fn


def _apply_epilogue(epilogue: Sequence[EwiseTile], acc, ref_of, plan: _Plan):
    """Apply fused elementwise tail ops to the accumulator value.

    HBM temporaries introduced by fusion are forwarded through a local
    SSA environment (``local``) and never materialised.
    """
    local: Dict[str, object] = {}
    if plan.acc_name is not None:
        local[plan.acc_name] = acc
    val = acc
    for s in epilogue:
        srcs = []
        for r in s.srcs:
            if r.buffer.name in local:
                srcs.append(local[r.buffer.name])
            elif r.buffer.name == plan.out_buffer:
                srcs.append(val)
            elif r.buffer.name in ref_of:
                srcs.append(ref_of[r.buffer.name][...])
            else:
                raise EmitError(f"epilogue src {r.buffer.name} not mapped")
        if len(srcs) == 2 and getattr(srcs[1], "ndim", 0) < srcs[0].ndim:
            srcs[1] = srcs[1][(None,) * (srcs[0].ndim - srcs[1].ndim)]
        v = srcs[0] if s.op == "copy" else _EWISE_JNP[s.op](*srcs)
        local[s.dst.buffer.name] = v
        val = v
    if plan.out_buffer in local:
        return local[plan.out_buffer]
    return val


# --------------------------------------------------------------------------
# general multi-nest emitter
# --------------------------------------------------------------------------
#
# The serving-kernel graphs lower to *several* top-level nests chained
# through HBM temporaries (matmul -> mask add -> carried max -> exp ->
# carried sum -> matmul -> div), which the single-nest classifier above
# cannot express.  The general emitter maps each top-level statement to
# its own ``pl.pallas_call``:
#
#   * the nest's leading @grid chain becomes the pallas grid (a nest with
#     none grids its outermost sequential loop, see ``_stage_grid``);
#   * each HBM buffer gets a block that follows the grid on every dim
#     that all of the stage's refs index by one grid counter, and spans
#     the dim otherwise (``_block_spec``); tile addressing inside the
#     block uses ``pl.ds`` — inner @seq/@unrolled/@vector counters
#     resolve to python ints at trace time;
#   * VREG/VMEM scratch (accumulators, scan carries) become VMEM scratch
#     refs read and written by window — carried state threads through
#     the grid steps exactly as the sequential schedule orders it;
#   * stages communicate through a host-level environment: each stage's
#     written HBM buffers feed the next stage's inputs.
#
# Interior @grid loops (the k-on-grid revisit trick) stay exclusive to
# the GEMM path — in a multi-nest kernel they would need cross-stage
# revisit reasoning, so the general emitter refuses them.


def _stage_io(stmts: Sequence[Stmt]) -> Tuple[List[str], List[str]]:
    """(read, written) HBM buffer names under ``stmts``, in first-use
    order.  The carry of a ScanTile counts as read *and* written."""
    read: List[str] = []
    written: List[str] = []

    def go(ss):
        for s in ss:
            if isinstance(s, Loop):
                go(s.body)
                continue
            w = {r.buffer.name for r in _stmt_written_refs(s)}
            for r in _stmt_refs(s):
                if r.buffer.space != MemSpace.HBM:
                    continue
                tgt = written if r.buffer.name in w else read
                if r.buffer.name not in tgt:
                    tgt.append(r.buffer.name)
            if isinstance(s, (MatmulTile, ReduceTile)) and s.accumulate \
                    and s.dst.buffer.space == MemSpace.HBM:
                raise EmitError(
                    f"stage accumulates into HBM buffer "
                    f"{s.dst.buffer.name} (schedule an accumulator)")
    go(stmts)
    return read, written


def _stage_grid(top: Stmt) -> Tuple[List[Loop], List[Stmt]]:
    """(grid loops, per-step body) of one top-level statement.

    The leading @grid chain becomes the pallas grid.  A stage with no
    @grid loop maps its outermost @seq/@unrolled loop onto a grid of one
    dimension instead: the TPU walks the grid in order and VMEM scratch
    persists across steps, so the carried state threads exactly as the
    sequential schedule orders it, and each step holds one slice of the
    stage's operands in VMEM rather than whole arrays."""
    loops: List[Loop] = []
    cur = top
    while isinstance(cur, Loop) and cur.kind == LoopKind.GRID:
        loops.append(cur)
        if len(cur.body) == 1 and isinstance(cur.body[0], Loop) \
                and cur.body[0].kind == LoopKind.GRID:
            cur = cur.body[0]
        else:
            break
    if loops:
        return loops, list(loops[-1].body)
    if isinstance(top, Loop) and top.kind in (LoopKind.SEQUENTIAL,
                                              LoopKind.UNROLLED):
        return [top], list(top.body)
    return [], [top]


def _block_spec(refs: Sequence[TileRef], shape: Tuple[int, ...],
                grid_vars: Sequence[str]):
    """Block shape and per-dim grid position (None = whole dim) of one
    HBM buffer.  A dim is blocked when every ref of the stage indexes it
    by the same grid counter at the same tile, and the tile keeps the
    TPU's block tiling rule (last two dims a multiple of (8, 128) or the
    whole dim); otherwise the block spans the dim."""
    rank = len(shape)
    block: List[int] = []
    pos: List[Optional[int]] = []
    for d in range(rank):
        keys = {(r.index[d].coeffs, r.index[d].const, r.tile[d])
                for r in refs}
        blocked = None
        if len(keys) == 1:
            coeffs, const, t = next(iter(keys))
            if const == 0 and len(coeffs) == 1 and coeffs[0][1] == 1 \
                    and coeffs[0][0] in grid_vars and shape[d] % t == 0:
                align = {rank - 1: 128, rank - 2: 8}.get(d, 1)
                if t % align == 0 or t == shape[d]:
                    blocked = (t, list(grid_vars).index(coeffs[0][0]))
        if blocked is None:
            block.append(shape[d])
            pos.append(None)
        else:
            block.append(blocked[0])
            pos.append(blocked[1])
    return tuple(block), tuple(pos)


def _emit_stage(kernel: Kernel, top: Stmt, buffers: Dict[str, "Buffer"],
                interpret: bool, name: str):
    """Build ``stage(env) -> None`` executing one top-level statement as
    a pallas_call named ``name`` over the host-level buffer
    environment."""
    loops, inner = _stage_grid(top)
    grid_vars = [lp.var.name for lp in loops]
    grid = [lp.var.extent for lp in loops]
    for s in inner:
        for n in _walk_stmts([s]):
            if isinstance(n, Loop) and n.kind == LoopKind.GRID:
                raise EmitError(
                    f"{kernel.name}: interior @grid loop %{n.var.name} "
                    f"(k-on-grid is a single-nest schedule)")

    reads, writes = _stage_io([top])
    if not writes:
        raise EmitError(f"{kernel.name}: stage writes no HBM buffer")
    # the non-grid loops unroll at trace time: refuse schedules so
    # scalar the trace would blow up (tile-1 nested GEMM belongs to the
    # XLA backend, not pallas)
    traced = _traced_stmts(inner)
    if traced > 4096:
        raise EmitError(
            f"{kernel.name}: stage would trace {traced} statements "
            f"(grid-map or tile the schedule first)")
    leaves = [s for s in _walk_stmts([top]) if not isinstance(s, Loop)]
    refs_of: Dict[str, List[TileRef]] = {}
    for s in leaves:
        for r in _stmt_refs(s):
            refs_of.setdefault(r.buffer.name, []).append(r)
    scratch = [b for b in kernel.scratch if b.name in refs_of]
    blocks = {n: _block_spec(refs_of[n], buffers[n].shape, grid_vars)
              for n in reads + writes}

    def body(*refs):
        n_io = len(reads) + len(writes)
        ref_of = dict(zip(reads + writes, refs[:n_io]))
        ref_of.update(zip((b.name for b in scratch), refs[n_io:]))

        def window(r: TileRef, env):
            pos = blocks[r.buffer.name][1] if r.buffer.name in blocks \
                else (None,) * len(r.tile)
            return tuple(
                pl.ds(0 if p is not None else e.evaluate(env) * t, t)
                for e, t, p in zip(r.index, r.tile, pos))

        def read(r: TileRef, env):
            return ref_of[r.buffer.name][window(r, env)]

        def write(r: TileRef, env, val):
            ref = ref_of[r.buffer.name]
            ref[window(r, env)] = val.astype(ref.dtype)

        def exec_stmt(s: Stmt, env):
            if isinstance(s, ZeroTile):
                write(s.dst, env, jnp.zeros(s.dst.tile, jnp.float32))
            elif isinstance(s, FillTile):
                write(s.dst, env,
                      jnp.full(s.dst.tile, s.value, jnp.float32))
            elif isinstance(s, MatmulTile):
                c = jnp.dot(read(s.lhs, env), read(s.rhs, env),
                            preferred_element_type=jnp.float32)
                if s.accumulate:
                    c = read(s.dst, env).astype(jnp.float32) + c
                write(s.dst, env, c)
            elif isinstance(s, ReduceTile):
                r = (jnp.max if s.kind == "max" else jnp.sum)(
                    read(s.src, env), axis=-1, keepdims=True)
                if s.accumulate:
                    d = read(s.dst, env)
                    r = jnp.maximum(d, r) if s.kind == "max" else d + r
                write(s.dst, env, r)
            elif isinstance(s, ScanTile):
                srcs = [read(r, env) for r in s.srcs]

                def step(c, row):
                    if s.kind == "linear":
                        c = row[0] * c + row[1]
                    else:
                        c = c + row[0]
                    return c, c

                carry0 = read(s.carry, env)[0]
                last, out = jax.lax.scan(step, carry0, tuple(srcs))
                write(s.dst, env, out)
                write(s.carry, env, last[None])
            elif isinstance(s, EwiseTile):
                if s.op == "ones":
                    write(s.dst, env, jnp.ones(s.dst.tile, jnp.float32))
                    return
                srcs = [read(r, env) for r in s.srcs]
                if s.op == "copy1":
                    write(s.dst, env, srcs[0].reshape(s.dst.tile))
                    return
                if s.op == "cast":
                    write(s.dst, env, srcs[0])
                    return
                if len(srcs) == 2 and srcs[1].ndim < srcs[0].ndim:
                    srcs[1] = srcs[1][(None,) * (srcs[0].ndim
                                                 - srcs[1].ndim)]
                write(s.dst, env, _EWISE_JNP[s.op](*srcs))
            else:
                raise EmitError(
                    f"{kernel.name}: no pallas emission for "
                    f"{type(s).__name__}")

        def go(stmts, env):
            for s in stmts:
                if isinstance(s, Loop):
                    for t in range(s.var.extent):
                        go(s.body, {**env, s.var.name: t})
                else:
                    exec_stmt(s, env)

        env0 = {v: pl.program_id(i) for i, v in enumerate(grid_vars)}
        go(inner, env0)

    def spec(n):
        block, pos = blocks[n]
        return pl.BlockSpec(block, lambda *g: tuple(
            0 if p is None else g[p] for p in pos))

    call = pl.pallas_call(
        body,
        grid=tuple(grid) or (1,),
        in_specs=[spec(n) for n in reads],
        out_specs=[spec(n) for n in writes],
        out_shape=[jax.ShapeDtypeStruct(buffers[n].shape,
                                        _JNP_DTYPE[buffers[n].type.dtype])
                   for n in writes],
        scratch_shapes=[pltpu.VMEM(b.shape, _JNP_DTYPE[b.type.dtype])
                        for b in scratch],
        interpret=interpret,
        name=name,
    )

    def stage(env: Dict[str, jax.Array]) -> None:
        outs = call(*[env[n] for n in reads])
        for n, a in zip(writes, outs):
            env[n] = a

    stage.reads, stage.writes = reads, writes
    return stage


def _walk_stmts(stmts):
    for s in stmts:
        yield s
        if isinstance(s, Loop):
            yield from _walk_stmts(s.body)


def _traced_stmts(stmts) -> int:
    """Leaf statements the stage body will trace (loop trips multiply)."""
    n = 0
    for s in stmts:
        if isinstance(s, Loop):
            n += s.var.extent * _traced_stmts(s.body)
        else:
            n += 1
    return n


def emit_general(kernel: Kernel,
                 interpret: Optional[bool] = None) -> Callable[..., jax.Array]:
    """Emit a multi-nest kernel as a chain of per-nest pallas_calls."""
    interpret = pallas_interpret(interpret)
    kernel.verify()
    if len(kernel.outputs) != 1:
        raise EmitError(f"{kernel.name}: exactly one output supported")
    buffers = {b.name: b for b in kernel.params + kernel.scratch}
    # each nest's kernel name leads with its index: a profile keeps only
    # the start of a long name, and still tells the nests apart
    stages = [_emit_stage(kernel, top, buffers, interpret,
                          f"nest{i}_{kernel.name}")
              for i, top in enumerate(kernel.body)]
    out_name = kernel.outputs[0].name
    out_names = {b.name for b in kernel.outputs}
    in_params = [b for b in kernel.params if b.name not in out_names]

    def fn(*inputs):
        if len(inputs) > len(in_params):
            raise ValueError(
                f"{kernel.name}: expected <= {len(in_params)} inputs")
        env: Dict[str, jax.Array] = {}
        it = iter(inputs)
        for b in kernel.params:
            if b.name in out_names:
                env[b.name] = jnp.zeros(b.shape, _JNP_DTYPE[b.type.dtype])
                continue
            try:
                env[b.name] = jnp.asarray(next(it),
                                          _JNP_DTYPE[b.type.dtype])
            except StopIteration:
                env[b.name] = jnp.zeros(b.shape, _JNP_DTYPE[b.type.dtype])
        for stage in stages:
            stage(env)
        return env[out_name]

    fn.__name__ = f"stagecc_pallas_{kernel.name}"
    fn.plan = None                       # general path has no _Plan
    fn.stages = stages                   # introspection for tests
    return fn
