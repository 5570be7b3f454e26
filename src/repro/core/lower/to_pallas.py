"""HIR -> Pallas TPU lowering (the hardware-adaptation component).

The paper's three design components map onto a ``pl.pallas_call`` as:

  algorithm  -> the kernel body: HIR ops interpreted into scalar jnp ops on
                Refs;
  schedule   -> the *main* pipelined loop becomes the (sequential) Pallas
                grid; cross-iteration state (HIR register windows /
                accumulators) becomes scratch persisting across grid
                steps; prologue/epilogue phases run under
                ``pl.when(first/last step)``;
  binding    -> memref arguments become whole-array inputs/outputs and
                ``hir.alloc`` buffers become scratch, all in SMEM: the body
                reads and writes one element at a time, and the TPU stores
                scalars only to SMEM.  A v5e has 1 MiB of SMEM: compiling
                for one, array_add with 3 x 64 Ki int32 elements was
                accepted and 3 x 128 Ki refused, so bound designs must keep
                their memrefs and scratch under that.

Supported subset (covers the paper's benchmark gallery except GEMM): a
function whose top level is a sequence of phases — constant-bound loops and
straight-line memory ops — with one *main* ``for`` loop (the largest trip
count).  The GEMM systolic array is intentionally NOT emulated PE-by-PE: on
TPU the MXU *is* the systolic array, and its binding is the hand-scheduled
``repro.kernels.matmul``.

``hir.delay`` lowers to identity: the *functional* semantics of a verified
schedule-correct design do not depend on the delays (that is the point of
the schedule verifier); the pipeline realisation is Pallas's.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import ir
from ..ir import ForOp, MemrefType, Module, Operation, Region, Value
from .common import (EFFECTFUL_OPS as _EFFECTFUL, jnp_arith_table,
                     pallas_dtype as _dtype, schedule_key as _schedule_key)

_ARITH = jnp_arith_table()
_PURE = set(_ARITH) | {"delay", "constant"}


class _KernelInterp:
    """Executes HIR effects in schedule order over Pallas Refs; pure values
    resolve lazily (recursively through arith/delay/constant defs)."""

    def __init__(self, module: Module, refs: dict[Value, Any],
                 env: dict[Value, Any] | None = None):
        self.module = module
        self.refs = refs                      # memref Value -> Ref
        self.env: dict[Value, Any] = dict(env or {})

    def value(self, v: Value):
        if v in self.env:
            return self.env[v]
        d = v.defining_op
        if d is None or d.opname not in _PURE:
            raise KeyError(f"%{v.name} unbound in pallas interp ({d})")
        if d.opname == "constant":
            out = d.attrs["value"]
        elif d.opname == "delay":
            out = self.value(d.operands[0])
        else:
            out = _ARITH[d.opname](*[self.value(x) for x in d.operands])
        self.env[v] = out
        return out

    def run_effects(self, ops: list[Operation]) -> None:
        for op in sorted((x for x in ops if x.opname in _EFFECTFUL),
                         key=_schedule_key):
            self.run_effect(op)

    def run_region(self, region: Region) -> None:
        self.run_effects(list(region.ops))

    def run_effect(self, op: Operation) -> None:
        o = op.opname
        if o == "mem_read":
            mem, idx = ir.mem_read_parts(op)
            ixs = tuple(self.value(i) for i in idx)
            self.env[op.result] = self.refs[mem][ixs]
            return
        if o == "mem_write":
            val, mem, idx, pred = ir.mem_write_parts(op)
            ref = self.refs[mem]
            ixs = tuple(self.value(i) for i in idx)
            x = jnp.asarray(self.value(val)).astype(ref.dtype)
            if pred is not None:
                old = ref[ixs]
                x = jnp.where(jnp.asarray(self.value(pred)) != 0, x, old)
            ref[ixs] = x
            return
        if o == "call":
            callee = self.module.funcs[op.attrs["callee"]]
            sub = _KernelInterp(self.module, self.refs)
            for formal, actual in zip(callee.args, op.operands):
                if isinstance(formal.type, MemrefType):
                    sub.refs[formal] = self.refs[actual]
                else:
                    sub.env[formal] = self.value(actual)
            sub.run_region(callee.body)
            for bop in callee.body.ops:
                if bop.opname == "return" and bop.operands:
                    for r, v in zip(op.results, bop.operands):
                        self.env[r] = sub.value(v)
            return
        if isinstance(op, ForOp):
            trip = op.trip_count()
            assert trip is not None, "to_pallas: nested loops need constant bounds"
            lb = ir.const_value(op.lb)
            step = ir.const_value(op.step)
            for t in range(trip):                 # fully unrolled in-kernel
                body = _KernelInterp(self.module, self.refs, self.env)
                body.env[op.iv] = lb + t * step
                body.run_region(op.region(0))
            return
        raise NotImplementedError(f"to_pallas: hir.{o}")


def lower_to_pallas(module: Module, func_name: str, *,
                    interpret: bool = False,
                    pipeline: Optional[str] = None,
                    allow_downcast: bool = False) -> Callable:
    """Lower ``@func_name`` to a callable mapping input arrays (one per
    read-port memref arg) to a dict of output arrays (write-port args).

    ``pipeline`` optionally names a ``PassManager`` spec run on ``module``
    (in place) before lowering, mirroring ``lower_to_jax``.  ``interpret``
    runs the kernel body in Pallas's interpreter (the CPU path of the
    tests); by default the kernel is compiled for the TPU.

    Dtype policy (see ``lower.common.pallas_dtype``): ``f64`` memrefs raise
    ``TypeError`` unless ``allow_downcast=True`` (TPU compute is f32);
    ``f16`` maps to TPU-native ``bfloat16`` with a ``PrecisionWarning``."""
    if pipeline:
        from ..passmgr import PassManager

        PassManager.from_spec(pipeline).run(module)
    func = module.get(func_name)
    in_args = [a for a in func.args if isinstance(a.type, MemrefType)
               and a.type.port == ir.PORT_R]
    out_args = [a for a in func.args if isinstance(a.type, MemrefType)
                and a.type.port in (ir.PORT_W, ir.PORT_RW)]
    allocs = [op for op in func.body.ops if op.opname == "alloc"]

    # phase split: the main loop is the largest-trip top-level for
    top = [op for op in func.body.ops if op.opname in _EFFECTFUL]
    loops = [op for op in top if isinstance(op, ForOp)]
    assert loops, "to_pallas needs at least one top-level loop"
    main = max(loops, key=lambda l: l.trip_count() or 0)
    mi = top.index(main)
    prologue, epilogue = top[:mi], top[mi + 1:]

    trip = main.trip_count()
    assert trip is not None, "main loop needs constant bounds"
    lb = ir.const_value(main.lb)
    step = ir.const_value(main.step)

    def kernel(*refs):
        n_in, n_out = len(in_args), len(out_args)
        ref_of: dict[Value, Any] = {}
        for a, r in zip(in_args, refs[:n_in]):
            ref_of[a] = r
        for a, r in zip(out_args, refs[n_in:n_in + n_out]):
            ref_of[a] = r
        for al, r in zip(allocs, refs[n_in + n_out:]):
            for res in al.results:          # every port aliases one buffer
                ref_of[res] = r

        pid = pl.program_id(0)

        @pl.when(pid == 0)
        def _prologue():
            _KernelInterp(module, ref_of).run_effects(prologue)

        body = _KernelInterp(module, ref_of)
        body.env[main.iv] = lb + pid * step
        body.run_region(main.region(0))

        @pl.when(pid == trip - 1)
        def _epilogue():
            _KernelInterp(module, ref_of).run_effects(epilogue)

    out_shapes = [jax.ShapeDtypeStruct(a.type.shape,
                                       _dtype(a.type.elem, allow_downcast))
                  for a in out_args]
    scratch = [pltpu.SMEM(al.attrs["base"].shape,
                          _dtype(al.attrs["base"].elem, allow_downcast))
               for al in allocs]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    def fn(*arrays):
        assert len(arrays) == len(in_args), (len(arrays), len(in_args))
        ins = [jnp.asarray(x).astype(_dtype(a.type.elem, allow_downcast))
               for x, a in zip(arrays, in_args)]
        outs = pl.pallas_call(
            kernel,
            grid=(trip,),
            in_specs=[smem] * len(in_args),
            out_specs=[smem] * len(out_args),
            out_shape=out_shapes,
            scratch_shapes=scratch,
            interpret=interpret,
        )(*ins)
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        return {a.name: o for a, o in zip(out_args, outs)}

    return fn
