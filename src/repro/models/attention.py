"""Attention: GQA with RoPE (full / sliding-window / cross), flash-style
chunked computation in pure jnp (doubles as the oracle for the Pallas flash
kernel), and single-token decode over KV caches (full-cache and
sequence-sharded variants live in ``repro.kernels``)."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import AttentionCfg, ModelCfg
from ..parallel.api import shard
from .common import _named_scope, apply_rope, ninit, softcap as _softcap

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_attn(key, cfg: ModelCfg, cross: bool = False):
    a = cfg.attn
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    qd, kvd = a.n_heads * a.d_head, a.n_kv_heads * a.d_head
    p = {
        "wq": ninit(ks[0], (d, a.n_heads, a.d_head)),
        "wk": ninit(ks[1], (d, a.n_kv_heads, a.d_head)),
        "wv": ninit(ks[2], (d, a.n_kv_heads, a.d_head)),
        "wo": ninit(ks[3], (a.n_heads, a.d_head, d), scale=0.02 / max(1, cfg.n_layers) ** 0.5),
    }
    if a.qkv_bias:
        p["bq"] = jnp.zeros((a.n_heads, a.d_head), jnp.bfloat16)
        p["bk"] = jnp.zeros((a.n_kv_heads, a.d_head), jnp.bfloat16)
        p["bv"] = jnp.zeros((a.n_kv_heads, a.d_head), jnp.bfloat16)
    return p


def specs_attn(cfg: ModelCfg, cross: bool = False):
    a = cfg.attn
    p = {
        "wq": ("embed_tp", "heads", None),
        "wk": ("embed_tp", "kv_heads", None),
        "wv": ("embed_tp", "kv_heads", None),
        "wo": ("heads", None, "embed_tp"),
    }
    if a.qkv_bias:
        p["bq"] = ("heads", None)
        p["bk"] = ("kv_heads", None)
        p["bv"] = ("kv_heads", None)
    return p


# ---------------------------------------------------------------------------
# flash-style chunked attention (pure jnp oracle)
# ---------------------------------------------------------------------------


@_named_scope("pallas_kernel.flash_attention")
def flash_attention(
    q, k, v, *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    q_offset: int = 0,
    kv_chunk: int = 1024,
):
    """q: (B, Sq, H, D); k/v: (B, Sk, KvH, D).  Online-softmax over KV chunks:
    O(Sq * kv_chunk) live memory instead of O(Sq * Sk).  ``q_offset`` is the
    absolute position of q[0] relative to k[0] (for decode/prefill-continue).
    """
    B, Sq, H, D = q.shape
    Sk, KvH = k.shape[1], k.shape[2]
    G = H // KvH
    scale = D ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KvH, G, D)

    nchunks = -(-Sk // kv_chunk)
    pad = nchunks * kv_chunk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(B, nchunks, kv_chunk, KvH, D)
    vc = v.reshape(B, nchunks, kv_chunk, KvH, D)

    q_pos = q_offset + jnp.arange(Sq)

    def step(carry, ci):
        acc, m, l = carry
        kb = kc[:, ci].astype(jnp.float32)           # (B, C, KvH, D)
        vb = vc[:, ci].astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kb)  # (B,Sq,KvH,G,C)
        s = _softcap(s, logit_cap)
        kv_pos = ci * kv_chunk + jnp.arange(kv_chunk)
        valid = (kv_pos < Sk)[None, None, None, None, :]
        if causal:
            valid = valid & (kv_pos[None, :] <= q_pos[:, None])[None, :, None, None, :]
        if window is not None:
            valid = valid & (kv_pos[None, :] > q_pos[:, None] - window)[None, :, None, None, :]
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bqhgk,bkhd->bqhgd", p, vb)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((B, Sq, KvH, G, D), jnp.float32)
    m0 = jnp.full((B, Sq, KvH, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, KvH, G), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), jnp.arange(nchunks))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, H, D).astype(q.dtype)


@_named_scope("pallas_kernel.flash_attention")
def dense_attention(q, k, v, *, causal=True, window=None, logit_cap=None, q_offset=0):
    """Reference O(Sq*Sk) attention (small shapes / tests)."""
    B, Sq, H, D = q.shape
    KvH = k.shape[2]
    G = H // KvH
    qf = (q.astype(jnp.float32) * D ** -0.5).reshape(B, Sq, KvH, G, D)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, k.astype(jnp.float32))
    s = _softcap(s, logit_cap)
    q_pos = q_offset + jnp.arange(Sq)
    kv_pos = jnp.arange(k.shape[1])
    valid = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        valid = valid & (kv_pos[None, :] <= q_pos[:, None])
    if window is not None:
        valid = valid & (kv_pos[None, :] > q_pos[:, None] - window)
    s = jnp.where(valid[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# block forward
# ---------------------------------------------------------------------------


def _project_qkv(p, x, cfg: ModelCfg):
    a = cfg.attn
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if a.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return q, k, v


def attn_forward(p, x, cfg: ModelCfg, *, positions=None, window=None, kv=None,
                 causal: bool = True):
    """Self-attention over x (B,S,D); cross-attention if ``kv`` (memory
    hidden states (B,Sm,D)) is given.  ``causal=False`` gives bidirectional
    self-attention (encoder stacks)."""
    a = cfg.attn
    B, S, D = x.shape
    if kv is None:
        q, k, v = _project_qkv(p, x, cfg)
        pos = positions if positions is not None else jnp.arange(S)[None, :].repeat(B, 0)
        q = apply_rope(q, pos, a.rope_theta, a.rope_dim)
        k = apply_rope(k, pos, a.rope_theta, a.rope_dim)
    else:
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
        k = jnp.einsum("bsd,dhe->bshe", kv, p["wk"])
        v = jnp.einsum("bsd,dhe->bshe", kv, p["wv"])
        causal = False  # cross-attention attends to the full memory
    q = shard(q, "batch", "seq", "heads", None)
    k = shard(k, "batch", "seq", "kv_heads", None)
    v = shard(v, "batch", "seq", "kv_heads", None)
    Sk = k.shape[1]
    if Sk * S <= 2048 * 2048 or Sk <= 1024:
        o = dense_attention(q, k, v, causal=causal, window=window, logit_cap=a.logit_softcap)
    else:
        o = flash_attention(q, k, v, causal=causal, window=window, logit_cap=a.logit_softcap)
    o = shard(o, "batch", "seq", "heads", None)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"])


def cross_attn_decode(p, x1, k, v, cfg: ModelCfg):
    """Cross-attention decode against *precomputed* memory K/V (filled once
    at prefill — recomputing the 1600-token memory projections every decode
    step was ~half the VLM decode FLOPs, found via the roofline's useful-
    FLOPs column).  x1: (B,1,D); k,v: (B,Tm,KvH,Dh)."""
    q = jnp.einsum("bsd,dhe->bshe", x1, p["wq"])
    o = dense_attention(q, k, v, causal=False, logit_cap=cfg.attn.logit_softcap)
    return jnp.einsum("bshe,hed->bsd", o, p["wo"])


def cross_attn_kv(p, memory, cfg: ModelCfg):
    """Memory K/V for one cross-attention layer; memory: (B,Tm,D)."""
    k = jnp.einsum("btd,dhe->bthe", memory, p["wk"])
    v = jnp.einsum("btd,dhe->bthe", memory, p["wv"])
    return k, v


# ---------------------------------------------------------------------------
# decode (single new token against a cache)
# ---------------------------------------------------------------------------


def init_attn_cache(batch: int, seq_len: int, cfg: ModelCfg, window: Optional[int] = None):
    from .common import dtype_of

    a = cfg.attn
    L = min(window, seq_len) if window else seq_len
    dt = dtype_of(cfg.dtype)
    shape = (batch, L, a.n_kv_heads, a.d_head)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def specs_attn_cache(window: Optional[int] = None):
    # full caches shard the sequence dim over the model axis (flash-decode
    # with partial-softmax reduction); windowed caches are small — replicate
    # the window dim and keep batch sharded.
    seq_ax = None if window else "kv_seq"
    return {"k": ("batch", seq_ax, "kv_heads_decode", None),
            "v": ("batch", seq_ax, "kv_heads_decode", None)}


def _sharded_flash_decode(q, k, v, idx, cfg: ModelCfg, mesh):
    """Sequence-sharded flash-decode (the distributed realisation of
    ``kernels.decode_attention``): the cache stays sharded over ``model`` on
    its length dim; each shard computes a partial online-softmax and the
    shards merge with one tiny all-gather of (acc, m, l) — O(B·H·D) on the
    wire instead of O(B·L·KvH·D) for gathering the cache.

    q: (B, 1, H, Dh) post-RoPE; k/v: (B, L, KvH, Dh); idx: (B,)."""
    from jax.sharding import PartitionSpec as P

    a = cfg.attn
    KvH, Dh = a.n_kv_heads, a.d_head
    G = a.n_heads // KvH
    scale = Dh ** -0.5

    def body(q, k, v, idx):
        i = jax.lax.axis_index("model")
        Ll = k.shape[1]
        lo = i * Ll
        Bq = q.shape[0]
        with jax.named_scope("pallas_kernel.decode_attention"):
            # == kernels.decode_attention.partial_decode_attention: the
            # scores/softmax state lives in VMEM on TPU
            qf = (q[:, 0].astype(jnp.float32) * scale).reshape(Bq, KvH, G, Dh)
            s = jnp.einsum("bhgd,blhd->bhgl", qf, k.astype(jnp.float32))
            s = _softcap(s, a.logit_softcap)
            pos = lo + jnp.arange(Ll)
            valid = pos[None, :] <= idx[:, None]                  # (B, Ll)
            s = jnp.where(valid[:, None, None, :], s, NEG_INF)
            m = s.max(-1)
            p = jnp.exp(s - m[..., None])
            l = p.sum(-1)
            acc = jnp.einsum("bhgl,blhd->bhgd", p, v.astype(jnp.float32))
        accs = jax.lax.all_gather(acc, "model")                   # (S,B,KvH,G,Dh)
        ms = jax.lax.all_gather(m, "model")
        ls = jax.lax.all_gather(l, "model")
        mm = ms.max(0)
        corr = jnp.exp(ms - mm[None])
        den = jnp.maximum((ls * corr).sum(0), 1e-30)
        o = (accs * corr[..., None]).sum(0) / den[..., None]
        return o.reshape(Bq, 1, a.n_heads, Dh)

    fm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "model"), P(None, "model"), P()),
        out_specs=P(), axis_names={"model"}, check_vma=False)
    return fm(q, k, v, idx)


def attn_decode_step(p, x1, cache, index, cfg: ModelCfg, *, window=None):
    """x1: (B, 1, D); cache k/v: (B, L, KvH, Dh); index: scalar or per-lane
    (B,) current positions (continuous batching).  Returns
    (out (B,1,D), new_cache)."""
    from ..parallel.api import current_mesh, current_rules

    a = cfg.attn
    B = x1.shape[0]
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (B,))
    q, k1, v1 = _project_qkv(p, x1, cfg)
    pos = idx[:, None]
    q = apply_rope(q, pos, a.rope_theta, a.rope_dim)
    k1 = apply_rope(k1, pos, a.rope_theta, a.rope_dim)
    L = cache["k"].shape[1]
    slot = jnp.mod(idx, L) if window else idx                       # (B,)
    lane = jnp.arange(B)
    k = cache["k"].at[lane, slot].set(k1[:, 0].astype(cache["k"].dtype))
    v = cache["v"].at[lane, slot].set(v1[:, 0].astype(cache["v"].dtype))

    rules = current_rules()
    mesh = current_mesh()
    if (rules is not None and mesh is not None and rules.rules.get("_flash_decode")
            and not window and "model" in mesh.axis_names
            and L % mesh.shape["model"] == 0 and L >= mesh.shape["model"]):
        o = _sharded_flash_decode(q, k, v, idx, cfg, mesh).astype(x1.dtype)
        out = jnp.einsum("bshe,hed->bsd", o, p["wo"])
        return out, {"k": k, "v": v}

    KvH, Dh = a.n_kv_heads, a.d_head
    G = a.n_heads // KvH
    qf = (q.astype(jnp.float32) * Dh ** -0.5).reshape(B, 1, KvH, G, Dh)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, k.astype(jnp.float32))
    s = _softcap(s, a.logit_softcap)
    kv_pos = jnp.arange(L)
    if window:
        # ring buffer: valid entries are the last ``window`` positions
        age = jnp.mod(slot[:, None] - kv_pos[None, :], L)           # (B,L)
        valid = age < jnp.minimum(idx + 1, L)[:, None]
    else:
        valid = kv_pos[None, :] <= idx[:, None]                     # (B,L)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqhgk,bkhd->bqhgd", prob, v.astype(jnp.float32))
    o = o.reshape(B, 1, a.n_heads, Dh).astype(x1.dtype)
    out = jnp.einsum("bshe,hed->bsd", o, p["wo"])
    return out, {"k": k, "v": v}
