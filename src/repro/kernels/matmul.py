"""Blocked MXU matmul — the TPU binding of the paper's systolic GEMM (§7.3).

The HIR GEMM describes a 16x16 systolic array via nested unroll_for with
distributed-memref banking; on TPU the MXU *is* the systolic array, so the
binding component becomes BlockSpec tiling: (bm x bk) x (bk x bn) VMEM tiles
streamed over a (M/bm, N/bn, K/bk) grid with the K dim innermost
(sequential), accumulating in an f32 VMEM scratch.  The schedule component
(HIR's II=1 pipelined loop) is the implicitly double-buffered Pallas grid.

Alignment contract (checked by ``core.verifier``-style ``check_schedule``):
block dims multiples of the 128x128 MXU / (8,128) VREG tiling; working set
within ``VMEM_BUDGET``.  ``f32`` operands contract at
full fp32 precision, so integer operands (the gemm binding) stay exact while
every partial sum is below 2**24.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Budget for the working set of one grid step (double-buffered input and
#: output tiles plus the f32 accumulator) under Mosaic's default scoped-VMEM
#: limit on v5e, which this kernel does not raise.  Compiling for a v5e, a
#: bf16 working set of 14 MiB was accepted and one of 16 MiB refused.  f32
#: operands, contracted at fp32 precision, were refused at smaller sets
#: (512^3 tiles accepted, 640^3 refused), so they count at twice their
#: width.
VMEM_BUDGET = 14 * 1024 * 1024


def check_schedule(M: int, N: int, K: int, bm: int, bn: int, bk: int,
                   elem_bytes: int = 2) -> list[str]:
    """HIR-style static schedule verification for the kernel binding:
    returns a list of diagnostics (empty = clean)."""
    errs = []
    for name, b, d in (("bm", bm, M), ("bn", bn, N), ("bk", bk, K)):
        if d % b:
            errs.append(f"{name}={b} does not tile dim {d}")
    if bm % 8 or bn % 128:
        errs.append(f"output tile ({bm},{bn}) not (8,128)-aligned for the VPU/MXU")
    if bk % 128:
        errs.append(f"contraction tile bk={bk} not 128-aligned for the MXU")
    in_bytes = 2 * elem_bytes if elem_bytes == 4 else elem_bytes
    ws = 2 * ((bm * bk + bk * bn) * in_bytes + bm * bn * elem_bytes) \
        + bm * bn * 4
    if ws > VMEM_BUDGET:
        errs.append(f"working set {ws} exceeds VMEM budget {VMEM_BUDGET}")
    return errs


def _mm_kernel(x_ref, y_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    precision = (jax.lax.Precision.HIGHEST if x_ref.dtype == jnp.float32
                 else None)
    acc_ref[...] += jnp.dot(x_ref[...], y_ref[...], precision=precision,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def matmul(x, y, *, bm: int = 256, bn: int = 256, bk: int = 256,
           out_dtype=None, interpret: bool = False):
    """(M,K) @ (K,N); dims must tile by (bm,bn,bk) — ``ops.matmul`` pads."""
    M, K = x.shape
    K2, N = y.shape
    assert K == K2, (x.shape, y.shape)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    errs = check_schedule(M, N, K, bm, bn, bk, x.dtype.itemsize)
    if errs and not interpret:
        raise ValueError("; ".join(errs))
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        partial(_mm_kernel, k_steps=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, y)
