"""Int8-compressed cross-pod gradient all-reduce.

Cross-pod ICI/DCN links are the scarcest bandwidth in a multi-pod job; the
within-pod reduction happens at full precision (GSPMD, over the auto axes)
while the pod-to-pod exchange ships int8 + one fp32 scale per tensor — a 4x
(vs fp32) / 2x (vs bf16) wire-byte reduction.

Implementation: ``jax.shard_map`` manual over the ``pod`` axis only
(``axis_names={"pod"}``); ``data``/``model`` stay automatic so the inner
fwd+bwd keeps its GSPMD sharding.  The all-reduce is an all-gather of the
int8 payload + per-pod scales followed by a local fused dequant-sum
(sum_i scale_i * q_i), which is how compressed collectives are actually
realised (you cannot sum int8 payloads with different scales on the wire).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize_int8(x):
    """Per-tensor symmetric int8 quantization; returns (q, scale)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def compressed_psum(x, axis_name: str):
    """All-reduce ``x`` over ``axis_name`` shipping int8 on the wire."""
    q, scale = quantize_int8(x)
    qs = jax.lax.all_gather(q, axis_name)          # int8 on the wire
    ss = jax.lax.all_gather(scale, axis_name)      # (npods,) fp32 scales
    return jnp.einsum("p,p...->...", ss, qs.astype(jnp.float32))


def pod_grads_compressed(grad_fn, params, batch, mesh):
    """Run ``grad_fn(params, batch) -> (loss, metrics, grads)`` per pod and
    combine gradients with the compressed cross-pod all-reduce."""
    npods = mesh.shape["pod"]

    def body(params, batch):
        loss, metrics, grads = grad_fn(params, batch)
        grads = jax.tree.map(lambda g: compressed_psum(g, "pod") / npods, grads)
        loss = jax.lax.pmean(loss, "pod")
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, "pod"), metrics)
        return loss, metrics, grads

    fm = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P("pod")),
        out_specs=(P(), P(), P()),
        axis_names={"pod"},
        check_vma=False,
    )
    return fm(params, batch)
