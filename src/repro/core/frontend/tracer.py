"""JAX -> HIR frontend tracer (the mirror image of ``lower/to_jax.py``).

``trace(fn, in_shapes, name=...)`` abstractly evaluates a restricted
jax/jax.numpy program (via ``jax.make_jaxpr``) and rebuilds it as an
``hir.func``: every jaxpr equation becomes a bounded ``hir.for`` nest over
the HIR memref holding its result —

  * elementwise primitives -> one loop nest per equation (read operands,
    one arith chain, write the destination buffer);
  * ``reduce_sum`` / ``reduce_max`` / ``reduce_min`` -> an init nest plus a
    read-modify-write reduction nest (the histogram idiom);
  * ``cumsum`` -> a sequential recurrence loop through a register
    accumulator (the fifo/mac idiom);
  * ``dot_general`` (2-D matmul) -> a tiled i/jo/k/ji nest calling a shared
    combinational ``mac`` function, with a ``(tile,)`` register accumulator
    bank — the PE-array idiom of the gallery GEMM, with the column tile as
    the frontend's loop-level design knob;
  * ``broadcast_in_dim`` -> a zero-cost index-remapping view.

The tracer emits a *naive* sequential schedule whose only job is to pin the
program order (every op gets a monotone time offset), then hands the design
to the HLS pipeline: ``erase_schedule`` + ``hls_schedule`` produce the real
schedule, so traced designs share the exact verification/codegen path as
the hand-written gallery.

Dtype policy: integer-only (int32 data, bools as 0/1 i32).  Anything float
raises ``FrontendError`` — fixed-point integer kernels are the supported
hardware target (see README "Frontend" for the rationale and the supported
primitive table).
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np
from jax.extend import core as jax_core

from .. import ir
from ..builder import Builder


class FrontendError(NotImplementedError):
    """The traced program falls outside the supported JAX subset."""


class UnsupportedPrimitiveError(FrontendError):
    """A jaxpr equation uses a primitive the frontend cannot lower."""


# --------------------------------------------------------------------------
# traced values


class _Const:
    """Rank-0 integer literal."""

    __slots__ = ("v",)
    shape: tuple = ()

    def __init__(self, v):
        self.v = int(v)


class _Buf:
    """A (possibly index-remapped view of a) memref read port."""

    __slots__ = ("rd", "shape", "index")

    def __init__(self, rd, shape, index=None):
        self.rd = rd
        self.shape = tuple(shape)
        self.index = index if index is not None else (lambda ids: list(ids))


class _Alloc:
    """A local buffer: read + write ports plus the memref index mapping
    (rank-0 values live in a shape-(1,) register)."""

    __slots__ = ("rd", "wr", "shape")

    def __init__(self, rd, wr, shape):
        self.rd = rd
        self.wr = wr
        self.shape = tuple(shape)

    def midx(self, ids):
        return list(ids) if self.shape else [0]

    def view(self) -> _Buf:
        return _Buf(self.rd, self.shape, index=self.midx)


# --------------------------------------------------------------------------
# elementwise primitive table: jax primitive name -> emitter(tr, *scalars)

def _ew(opname: str):
    return lambda tr, *xs: tr.arith(opname, *xs)


def _cmp(kind: str):
    def f(tr, a, b):
        return tr.b.zext(tr.b.cmp(kind, a, b, at=tr.tick()), ir.i32,
                         at=tr.tick())
    return f


def _minmax(kind: str):
    def f(tr, a, b):
        c = tr.b.cmp(kind, a, b, at=tr.tick())
        # explicit i32 result: the default type inference picks the first
        # primitive operand, which here is the 1-bit compare
        return tr.b._arith("select", c, a, b, at=tr.tick(),
                           result_type=ir.i32)
    return f


_ELEMENTWISE: dict[str, Callable] = {
    "add": _ew("add"),
    "sub": _ew("sub"),
    "mul": _ew("mult"),
    "div": _ew("div"),
    "and": _ew("and"),
    "or": _ew("or"),
    "xor": _ew("xor"),
    "shift_left": _ew("shl"),
    "shift_right_arithmetic": _ew("shr"),
    "neg": lambda tr, a: tr.b.sub(0, a, at=tr.tick()),
    "max": _minmax("ge"),
    "min": _minmax("le"),
    "lt": _cmp("lt"),
    "le": _cmp("le"),
    "eq": _cmp("eq"),
    "ne": _cmp("ne"),
    "gt": _cmp("gt"),
    "ge": _cmp("ge"),
    # select_n picks cases[pred]; hir.select picks a when cond != 0
    "select_n": lambda tr, p, c0, c1: tr.b._arith(
        "select", p, c1, c0, at=tr.tick(), result_type=ir.i32),
}

#: primitives that are identity at the integer-only level
_IDENTITY = ("convert_element_type", "stop_gradient", "copy")

_REDUCE_INIT = {"reduce_sum": 0, "reduce_max": -(1 << 30),
                "reduce_min": (1 << 30)}

SUPPORTED_PRIMITIVES = tuple(sorted(
    set(_ELEMENTWISE) | set(_IDENTITY) | set(_REDUCE_INIT)
    | {"broadcast_in_dim", "cumsum", "dot_general", "jit"}))


def _check_int(aval, what: str) -> None:
    if not (np.issubdtype(aval.dtype, np.integer)
            or np.issubdtype(aval.dtype, np.bool_)):
        raise FrontendError(
            f"frontend is integer-only (int32 / bool): {what} has dtype "
            f"{aval.dtype}; express the kernel in fixed point")


class _Tracer:
    def __init__(self, b: Builder, root_time: ir.Time, tile: int):
        self.b = b
        self.tile = tile
        self.clocks: list[list] = [[root_time, 0]]
        self.n = 0          # unique-name counter (ivs, time vars, buffers)
        self.n_buf = 0

    # -- naive-schedule clock ------------------------------------------------
    def now(self) -> ir.Time:
        base, off = self.clocks[-1]
        return base + off

    def tick(self) -> ir.Time:
        t = self.now()
        self.clocks[-1][1] += 1
        return t

    def arith(self, opname: str, *xs) -> ir.Value:
        return self.b._arith(opname, *xs, at=self.tick())

    # -- loops ---------------------------------------------------------------
    @contextmanager
    def loop(self, n: int, unroll: bool = False):
        at = self.tick()
        k = self.n
        self.n += 1
        with self.b.for_(0, n, 1, at=at, unroll=unroll, iv_name=f"i{k}",
                         tv_name=f"t{k}") as lp:
            self.clocks.append([lp.time, 0])
            try:
                yield lp.iv
            finally:
                _, off = self.clocks.pop()
                self.b.yield_(at=lp.time + max(off, 1))

    def nest(self, shape: Sequence[int], body: Callable[[list], None]) -> None:
        """Run ``body(ids)`` inside a loop nest over ``shape`` (no loops for
        rank-0: the body runs in the current region)."""
        def rec(ids):
            if len(ids) == len(shape):
                body(ids)
                return
            with self.loop(shape[len(ids)]) as iv:
                rec(ids + [iv])
        rec([])

    # -- buffers --------------------------------------------------------------
    def new_buf(self, shape: Sequence[int], tag: str = "b",
                reg: bool = False) -> _Alloc:
        """Local buffer: BRAM for arrays, a fully-distributed register bank
        for rank-0 values and ``reg=True`` (parallel-access accumulators)."""
        shape = tuple(shape)
        k = self.n_buf
        self.n_buf += 1
        if shape and not reg:
            mt = ir.MemrefType(shape, ir.i32)
        else:
            mt = ir.MemrefType(shape or (1,), ir.i32, packed=[],
                               kind=ir.KIND_REG)
        rd, wr = self.b.alloc(mt, names=[f"{tag}{k}r", f"{tag}{k}w"])
        return _Alloc(rd, wr, shape)

    def elem(self, val, ids):
        """One scalar element of a traced value at loop indices ``ids``."""
        if isinstance(val, _Const):
            return val.v
        return self.b.read(val.rd, val.index(ids), at=self.tick())

    # -- jaxpr environment -----------------------------------------------------
    def lift_const(self, c) -> _Const:
        v = np.asarray(c)
        _check_int(v, "constant")
        if v.ndim == 0:
            return _Const(v)
        raise FrontendError(
            "array-valued constants are not supported; pass the array "
            "as a traced input instead")

    def val(self, env: dict, atom):
        if isinstance(atom, jax_core.Literal):
            return self.lift_const(atom.val)
        return env[atom]

    # -- equation handlers ------------------------------------------------------
    def eval_jaxpr(self, jaxpr, env: dict) -> None:
        for eqn in jaxpr.eqns:
            self.eval_eqn(eqn, env)

    def eval_eqn(self, eqn, env: dict) -> None:
        p = eqn.primitive.name
        if p == "jit" or p == "closed_call":
            inner = eqn.params["jaxpr"]
            sub = {v: self.val(env, a)
                   for v, a in zip(inner.jaxpr.invars, eqn.invars)}
            for cv, c in zip(inner.jaxpr.constvars, inner.consts):
                sub[cv] = self.lift_const(c)
            self.eval_jaxpr(inner.jaxpr, sub)
            for ov, res in zip(eqn.outvars, inner.jaxpr.outvars):
                env[ov] = self.val(sub, res)
            return
        if p in _IDENTITY:
            _check_int(eqn.outvars[0].aval, f"'{p}' result")
            env[eqn.outvars[0]] = self.val(env, eqn.invars[0])
            return
        if p == "broadcast_in_dim":
            self.eval_broadcast(eqn, env)
            return
        if p in _ELEMENTWISE:
            self.eval_elementwise(eqn, env)
            return
        if p in _REDUCE_INIT:
            self.eval_reduce(eqn, env)
            return
        if p == "cumsum":
            self.eval_cumsum(eqn, env)
            return
        if p == "dot_general":
            self.eval_dot_general(eqn, env)
            return
        raise UnsupportedPrimitiveError(
            f"frontend: unsupported JAX primitive '{p}'; supported "
            f"primitives are: {', '.join(SUPPORTED_PRIMITIVES)}")

    def eval_broadcast(self, eqn, env: dict) -> None:
        src = self.val(env, eqn.invars[0])
        oshape = tuple(eqn.params["shape"])
        bdims = tuple(eqn.params["broadcast_dimensions"])
        if isinstance(src, _Const):
            env[eqn.outvars[0]] = src
            return
        sshape = src.shape
        inner = src.index

        def index(ids, _ss=sshape, _bd=bdims, _osh=oshape):
            return inner([ids[d] if _ss[k] == _osh[d] else 0
                          for k, d in enumerate(_bd)])

        env[eqn.outvars[0]] = _Buf(src.rd, oshape, index=index)

    def eval_elementwise(self, eqn, env: dict) -> None:
        out = eqn.outvars[0]
        _check_int(out.aval, f"'{eqn.primitive.name}' result")
        oshape = tuple(out.aval.shape)
        vals = [self.val(env, a) for a in eqn.invars]
        for v in vals:
            if isinstance(v, _Buf) and v.shape not in (oshape, ()):
                raise FrontendError(
                    f"'{eqn.primitive.name}' operand shape {v.shape} does "
                    f"not match result shape {oshape} (missing broadcast?)")
        if eqn.primitive.name == "select_n" and len(vals) != 3:
            raise UnsupportedPrimitiveError(
                "select_n with more than two cases is not supported")
        impl = _ELEMENTWISE[eqn.primitive.name]
        dst = self.new_buf(oshape)

        def body(ids):
            xs = [self.elem(v, ids) for v in vals]
            self.b.write(impl(self, *xs), dst.wr, dst.midx(ids),
                         at=self.tick())

        self.nest(oshape, body)
        env[out] = dst.view()

    def eval_reduce(self, eqn, env: dict) -> None:
        out = eqn.outvars[0]
        _check_int(out.aval, f"'{eqn.primitive.name}' result")
        src = self.val(env, eqn.invars[0])
        axes = set(eqn.params["axes"])
        ishape = tuple(eqn.invars[0].aval.shape)
        oshape = tuple(out.aval.shape)
        dst = self.new_buf(oshape, tag="red")
        init = _REDUCE_INIT[eqn.primitive.name]
        self.nest(oshape, lambda ids: self.b.write(
            init, dst.wr, dst.midx(ids), at=self.tick()))

        def body(ids):
            oids = [iv for d, iv in enumerate(ids) if d not in axes]
            acc = self.b.read(dst.rd, dst.midx(oids), at=self.tick())
            x = self.elem(src, ids)
            if eqn.primitive.name == "reduce_sum":
                r = self.b.add(acc, x, at=self.tick())
            else:
                kind = "ge" if eqn.primitive.name == "reduce_max" else "le"
                c = self.b.cmp(kind, acc, x, at=self.tick())
                r = self.b._arith("select", c, acc, x, at=self.tick(),
                                  result_type=ir.i32)
            self.b.write(r, dst.wr, dst.midx(oids), at=self.tick())

        self.nest(ishape, body)
        env[out] = dst.view()

    def eval_cumsum(self, eqn, env: dict) -> None:
        out = eqn.outvars[0]
        _check_int(out.aval, "'cumsum' result")
        src = self.val(env, eqn.invars[0])
        shape = tuple(eqn.invars[0].aval.shape)
        if len(shape) != 1 or eqn.params.get("reverse"):
            raise UnsupportedPrimitiveError(
                "cumsum is supported on rank-1 arrays, forward only "
                f"(got shape {shape}, reverse={eqn.params.get('reverse')})")
        dst = self.new_buf(shape, tag="scan")
        acc = self.new_buf((), tag="acc")
        self.b.write(0, acc.wr, [0], at=self.tick())

        def body(ids):
            x = self.elem(src, ids)
            a = self.b.read(acc.rd, [0], at=self.tick())
            s = self.b.add(a, x, at=self.tick())
            self.b.write(s, acc.wr, [0], at=self.tick())
            self.b.write(s, dst.wr, dst.midx(ids), at=self.tick())

        self.nest(shape, body)
        env[out] = dst.view()

    def eval_dot_general(self, eqn, env: dict) -> None:
        out = eqn.outvars[0]
        _check_int(out.aval, "'dot_general' result")
        dn = eqn.params["dimension_numbers"]
        a_val = self.val(env, eqn.invars[0])
        b_val = self.val(env, eqn.invars[1])
        ashape = tuple(eqn.invars[0].aval.shape)
        bshape = tuple(eqn.invars[1].aval.shape)
        if (len(ashape), len(bshape)) != (2, 2) or \
                tuple(map(tuple, dn[0])) != ((1,), (0,)) or any(dn[1]):
            raise UnsupportedPrimitiveError(
                "dot_general is supported as plain 2-D matmul "
                f"(contract a.dim1 with b.dim0, no batch dims; got {dn})")
        m, kk = ashape
        n = bshape[1]
        t = self.tile if self.tile and n % self.tile == 0 else 1
        dst = self.new_buf((m, n), tag="mm")
        # per-tile accumulators: a small local RAM cycled read-modify-write
        # (the histogram idiom); A elements are read once per (i, jo, k) and
        # reused across the ji tile — the tile width is the reuse knob
        acc = self.new_buf((t,), tag="acc")

        b = self.b
        with self.loop(m) as i:
            with self.loop(n // t) as jo:
                with self.loop(t) as ji:
                    b.write(0, acc.wr, [ji], at=self.tick())
                with self.loop(kk) as k:
                    a_el = self.elem(a_val, [i, k])
                    with self.loop(t) as ji:
                        col = self.arith(
                            "add", self.arith("mult", jo, t), ji)
                        b_el = self.elem(b_val, [k, col])
                        old = b.read(acc.rd, [ji], at=self.tick())
                        s = b.call("mac", [a_el, b_el, old], at=self.tick())
                        b.write(s, acc.wr, [ji], at=self.tick())
                with self.loop(t) as ji:
                    col = self.arith("add", self.arith("mult", jo, t), ji)
                    v = b.read(acc.rd, [ji], at=self.tick())
                    b.write(v, dst.wr, [i, col], at=self.tick())
        env[out] = dst.view()


def _walk_jaxprs(jaxpr):
    """Yield jaxpr and every sub-jaxpr reachable through eqn params."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            if isinstance(v, jax_core.ClosedJaxpr):
                yield from _walk_jaxprs(v.jaxpr)
            elif isinstance(v, jax_core.Jaxpr):
                yield from _walk_jaxprs(v)


def _uses_prim(jaxpr, name: str) -> bool:
    return any(eqn.primitive.name == name
               for j in _walk_jaxprs(jaxpr) for eqn in j.eqns)


def trace(fn: Callable, in_shapes: Sequence[Sequence[int]], *,
          name: str, tile: int = 2,
          arg_names: Optional[Sequence[str]] = None,
          schedule: bool = True, cache: bool = True,
          scheduler_options: Any = None):
    """Trace ``fn`` over int32 inputs of ``in_shapes`` into a scheduled HIR
    module.  Returns ``(Module, entry_name)`` — the gallery ``build()``
    contract, so traced kernels drop into every downstream harness
    (``run_differential``, ``hls_compile``, ``explore_design``).

    ``tile`` is the loop-level design knob for ``dot_general`` (column-tile
    width / accumulator-bank size; must divide N, else falls back to 1).
    ``schedule=False`` returns the *unscheduled* (erased) design for callers
    that schedule themselves; ``cache`` forwards to the process-wide
    ``ScheduleCache`` keyed by structural fingerprint."""
    from ..hls import erase_schedule, hls_schedule

    examples = [np.zeros(tuple(s) or (), np.int32) for s in in_shapes]
    closed = jax.make_jaxpr(fn)(*examples)
    jaxpr = closed.jaxpr
    for v in jaxpr.invars:
        _check_int(v.aval, "input")
    for v in jaxpr.outvars:
        _check_int(v.aval, "output")

    b = Builder(ir.Module(name))
    if _uses_prim(jaxpr, "dot_general"):
        # the shared PE compute op (create it *before* the main func: the
        # builder hoists constants into region_stack[0], which must be the
        # function under construction)
        with b.func("mac", [ir.i32, ir.i32, ir.i32], ["a", "bb", "c"],
                    result_types=[ir.i32], result_delays=[0]) as g:
            ga, gb, gc = g.args
            b.ret([b.add(b.mult(ga, gb, at=g.t), gc)])

    names = list(arg_names or [f"in{i}" for i in range(len(jaxpr.invars))])
    assert len(names) == len(jaxpr.invars), (names, len(jaxpr.invars))
    outs = jaxpr.outvars
    out_names = ["out"] if len(outs) == 1 else [f"out{i}"
                                               for i in range(len(outs))]
    arg_types = [ir.MemrefType(tuple(v.aval.shape) or (1,), ir.i32,
                               ir.PORT_R) for v in jaxpr.invars]
    arg_types += [ir.MemrefType(tuple(v.aval.shape) or (1,), ir.i32,
                                ir.PORT_W) for v in outs]

    with b.func(name, arg_types, names + out_names) as f:
        tr = _Tracer(b, f.t + 1, tile)
        env: dict = {}
        for var, arg in zip(jaxpr.invars, f.args):
            shape = tuple(var.aval.shape)
            env[var] = _Buf(arg, shape,
                            index=None if shape else (lambda ids: [0]))
        for cv, c in zip(jaxpr.constvars, closed.consts):
            env[cv] = tr.lift_const(c)
        tr.eval_jaxpr(jaxpr, env)
        for ov, out_arg in zip(outs, f.args[len(names):]):
            val = tr.val(env, ov)
            oshape = tuple(ov.aval.shape)

            def copy(ids, _v=val, _a=out_arg, _sh=oshape):
                x = tr.elem(_v, ids)
                tr.b.write(x, _a, list(ids) if _sh else [0], at=tr.tick())

            tr.nest(oshape, copy)
        b.ret()

    um = erase_schedule(b.module)
    if schedule:
        hls_schedule(um, options=scheduler_options,
                     cache=True if cache else None)
    return um, name
