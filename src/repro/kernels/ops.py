"""Public jit'd wrappers around the Pallas kernels.

Handles (a) padding arbitrary shapes to kernel tile multiples and (b) layout
adaptation from model conventions ((B,S,H,D)) to kernel conventions
((B,H,S,D)).  Every wrapper compiles its kernel for the TPU unless the
caller passes ``interpret=True``, which runs the kernel body in Pallas's
interpreter (how the tests run on the CPU).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import matmul as _mm
from . import rglru_scan as _rg
from . import ssd_scan as _ssd


def _pad_to(x, axis: int, mult: int, value=0.0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# -- matmul -------------------------------------------------------------------


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul(x, y, *, bm: int = 256, bn: int = 256, bk: int = 256,
           interpret: bool = False):
    M, K = x.shape
    _, N = y.shape
    xp = _pad_to(_pad_to(x, 0, bm), 1, bk)
    yp = _pad_to(_pad_to(y, 0, bk), 1, bn)
    out = _mm.matmul(xp, yp, bm=bm, bn=bn, bk=bk, interpret=interpret)
    return out[:M, :N]


# -- attention ----------------------------------------------------------------


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                   "interpret"))
def mha(q, k, v, *, causal: bool = True, window: Optional[int] = None,
        bq: int = 256, bk: int = 256, interpret: bool = False):
    """Model layout: q (B,S,H,D); k,v (B,S,KvH,D).  Returns (B,S,H,D)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    bq_, bk_ = min(bq, Sq), min(bk, Sk)
    qp = _pad_to(qt, 2, bq_)
    kp = _pad_to(kt, 2, bk_)
    vp = _pad_to(vt, 2, bk_)
    # keys appended by padding must never attend; causal masking alone does
    # not exclude them (any q_pos >= Sk admits key positions in [Sk, padded)),
    # so tell the kernel the true key length and let it mask by position
    kv_len = Sk if kp.shape[2] != Sk else None
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              kv_len=kv_len, bq=bq_, bk=bk_,
                              interpret=interpret)
    return jnp.swapaxes(out[:, :, :Sq], 1, 2)


@partial(jax.jit, static_argnames=("bk", "interpret"))
def decode(q, k, v, length, *, bk: int = 512, interpret: bool = False):
    """q (B,H,D) single position; k,v (B,L,KvH,D); length: valid entries."""
    L = k.shape[1]
    bk_ = min(bk, L)
    kp = _pad_to(k, 1, bk_)
    vp = _pad_to(v, 1, bk_)
    return _dec.decode_attention(q, kp, vp, length, bk=bk_,
                                 interpret=interpret)


decode_partial = _dec.partial_decode_attention
merge_partials = _dec.merge_partials


# -- ssd ----------------------------------------------------------------------


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xdt, dA, Bc, Cc, *, chunk: int = 128, interpret: bool = False):
    """Model layout: xdt (B,S,H,P); dA (B,S,H); Bc,Cc (B,S,N).
    Returns y (B,S,H,P) = SSD recurrence outputs."""
    B, S, H, P = xdt.shape
    N = Bc.shape[-1]
    Q = min(chunk, S)
    xp = _pad_to(xdt, 1, Q)
    dp = _pad_to(dA, 1, Q)
    bp = _pad_to(Bc, 1, Q)
    cp = _pad_to(Cc, 1, Q)
    nc = xp.shape[1] // Q
    xk = jnp.moveaxis(xp, 2, 1).reshape(B, H, nc, Q, P)
    dk = jnp.moveaxis(dp, 2, 1).reshape(B, H, nc, Q)
    bk = bp.reshape(B, nc, Q, N)
    ck = cp.reshape(B, nc, Q, N)
    y = _ssd.ssd_scan(xk, dk, bk, ck, interpret=interpret)
    y = jnp.moveaxis(y.reshape(B, H, nc * Q, P), 1, 2)
    return y[:, :S]


# -- rglru --------------------------------------------------------------------


@partial(jax.jit, static_argnames=("bs", "bd", "interpret"))
def rglru_scan(a, b, *, bs: int = 256, bd: int = 512,
               interpret: bool = False):
    """a, b (B,S,D): h_t = a_t h_{t-1} + b_t; returns h (B,S,D)."""
    B, S, D = a.shape
    bs_, bd_ = min(bs, S), min(bd, D)
    ap = _pad_to(_pad_to(a, 1, bs_), 2, bd_)
    bp = _pad_to(_pad_to(b, 1, bs_), 2, bd_)
    h = _rg.rglru_scan(ap, bp, bs=bs_, bd=bd_, interpret=interpret)
    return h[:, :S, :D]
