"""Per-kernel interpret-mode validation against the pure-jnp oracles in
``repro.kernels.ref`` — shape/dtype sweeps per the assignment contract."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


# -- matmul -------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(128, 128, 128), (256, 384, 128),
                                   (100, 130, 70), (8, 8, 8)])
def test_matmul(shape, dtype):
    M, K, N = shape
    k1, k2 = jax.random.split(jax.random.key(0))
    x = jax.random.normal(k1, (M, K), jnp.float32).astype(dtype)
    y = jax.random.normal(k2, (K, N), jnp.float32).astype(dtype)
    out = ops.matmul(x, y, bm=128, bn=128, bk=128, interpret=True)
    want = ref.matmul_ref(x, y)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_matmul_schedule_checker():
    from repro.kernels.matmul import check_schedule
    assert check_schedule(256, 256, 256, 128, 128, 128) == []
    errs = check_schedule(256, 256, 256, 100, 128, 130)
    assert errs and any("aligned" in e or "tile" in e for e in errs)


# -- flash attention ----------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("Sq,Sk,H,KvH,D,window", [
    (128, 128, 4, 4, 64, None),
    (128, 128, 4, 2, 64, None),     # GQA
    (256, 256, 2, 1, 32, 64),       # sliding window + MQA
    (64, 64, 2, 2, 128, None),
])
def test_flash_attention(Sq, Sk, H, KvH, D, window, dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    B = 2
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Sk, KvH, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Sk, KvH, D), jnp.float32).astype(dtype)
    out = ops.mha(q, k, v, causal=True, window=window, bq=64, bk=64,
                  interpret=True)
    # oracle expects (B,H,S,D) with KV repeated to H
    rep = H // KvH
    kk = jnp.repeat(jnp.swapaxes(k, 1, 2), rep, axis=1)
    vv = jnp.repeat(jnp.swapaxes(v, 1, 2), rep, axis=1)
    want = ref.flash_attention_ref(jnp.swapaxes(q, 1, 2), kk, vv,
                                   causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(jnp.swapaxes(want, 1, 2), np.float32),
                               **_tol(dtype))


def test_flash_vs_model_oracle():
    """Kernel agrees with the model-layer chunked flash oracle."""
    from repro.models.attention import flash_attention as model_flash
    ks = jax.random.split(jax.random.key(2), 3)
    B, S, H, D = 1, 256, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    out = ops.mha(q, k, v, bq=64, bk=64, interpret=True)
    want = model_flash(q, k, v, causal=True, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# -- decode attention ---------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,H,KvH,D,length", [
    (512, 4, 4, 64, 200),
    (1024, 4, 2, 64, 1024),
    (256, 2, 1, 128, 1),
])
def test_decode_attention(L, H, KvH, D, length, dtype):
    ks = jax.random.split(jax.random.key(3), 3)
    B = 2
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, L, KvH, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, L, KvH, D), jnp.float32).astype(dtype)
    out = ops.decode(q, k, v, length, bk=128, interpret=True)
    rep = H // KvH
    kk = jnp.repeat(k, rep, axis=2)
    vv = jnp.repeat(v, rep, axis=2)
    want = ref.decode_attention_ref(q, kk, vv, length)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_decode_partial_merge_equals_full():
    """Sequence-sharded flash-decode: merging per-shard partials reproduces
    the unsharded result (the production decode collective schedule)."""
    ks = jax.random.split(jax.random.key(4), 3)
    B, L, H, D, S = 2, 512, 4, 64, 4
    q = jax.random.normal(ks[0], (B, H, D))
    k = jax.random.normal(ks[1], (B, L, H, D))
    v = jax.random.normal(ks[2], (B, L, H, D))
    full = ops.decode(q, k, v, L, bk=128, interpret=True)
    shard = L // S
    outs, ms, ls = [], [], []
    for s in range(S):
        o, m, l = ops.decode_partial(q, k[:, s * shard:(s + 1) * shard],
                                     v[:, s * shard:(s + 1) * shard],
                                     shard, bk=128, interpret=True)
        outs.append(o)
        ms.append(m)
        ls.append(l)
    merged = ops.merge_partials(jnp.stack(outs), jnp.stack(ms), jnp.stack(ls))
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


# -- ssd ----------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("S,H,P,N,chunk", [
    (64, 2, 16, 16, 16),
    (100, 1, 32, 16, 32),     # ragged tail
    (128, 3, 8, 8, 128),      # single chunk
])
def test_ssd_scan(S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.key(5), 4)
    B = 2
    xdt = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dA = -jax.random.uniform(ks[1], (B, S, H), dtype, 0.01, 0.5)
    Bc = jax.random.normal(ks[2], (B, S, N), dtype)
    Cc = jax.random.normal(ks[3], (B, S, N), dtype)
    y = ops.ssd_scan(xdt, dA, Bc, Cc, chunk=chunk, interpret=True)
    want, _ = ref.ssd_scan_ref(xdt, dA, Bc, Cc)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_ssd_kernel_matches_model_core():
    """Kernel output equals the model-layer chunked SSD (same recurrence)."""
    from repro.models.ssd import ssd_core_chunked
    ks = jax.random.split(jax.random.key(6), 4)
    B, S, H, P, N = 1, 64, 2, 16, 16
    xh = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    Bc = jax.random.normal(ks[3], (B, S, N))
    Cc = jax.random.normal(jax.random.key(7), (B, S, N))
    D = jnp.zeros((H,))
    want, _ = ssd_core_chunked(xh, dt, A, Bc, Cc, D, chunk=16)
    # kernel takes dt-weighted inputs and per-step dA
    y = ops.ssd_scan(xh * dt[..., None], dt * A[None, None, :], Bc, Cc,
                     chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# -- rglru --------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("S,D,bs,bd", [
    (64, 32, 16, 32),
    (100, 48, 32, 16),    # ragged both dims
    (128, 8, 128, 8),     # single block
])
def test_rglru_scan(S, D, bs, bd, dtype):
    ks = jax.random.split(jax.random.key(8), 2)
    B = 2
    a = jax.random.uniform(ks[0], (B, S, D), dtype, 0.5, 0.99)
    b = jax.random.normal(ks[1], (B, S, D), dtype)
    h = ops.rglru_scan(a, b, bs=bs, bd=bd, interpret=True)
    want, _ = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_rglru_kernel_matches_model_scan():
    from repro.models.rglru import rglru_forward  # noqa: F401 (import check)
    ks = jax.random.split(jax.random.key(9), 2)
    B, S, D = 1, 64, 16
    a = jax.random.uniform(ks[0], (B, S, D), jnp.float32, 0.8, 0.999)
    b = jax.random.normal(ks[1], (B, S, D))

    def comb(x, y):
        a1, b1 = x
        a2, b2 = y
        return a1 * a2, a2 * b1 + b2

    _, want = jax.lax.associative_scan(comb, (a, b), axis=1)
    h = ops.rglru_scan(a, b, bs=16, bd=16, interpret=True)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- mha padded-KV masking (regression: padded keys used to attend) -----------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("Sq,Sk", [
    (80, 48),    # Sq != Sk, both ragged: causal admits k_pos in [48, 64)
    (48, 48),    # ragged keys only
])
def test_mha_padded_kv_is_masked(causal, Sq, Sk):
    """Keys appended by block padding must never attend.  The causal test
    alone admits padded key positions whenever q_pos >= Sk (and non-causal
    rows always would), so ``ops.mha`` must pass the true key length through
    to the kernel's position mask."""
    ks = jax.random.split(jax.random.key(11), 3)
    B, H, D = 1, 2, 32
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Sk, H, D))
    v = jax.random.normal(ks[2], (B, Sk, H, D))
    out = ops.mha(q, k, v, causal=causal, bq=32, bk=32, interpret=True)
    want = ref.flash_attention_ref(jnp.swapaxes(q, 1, 2),
                                   jnp.swapaxes(k, 1, 2),
                                   jnp.swapaxes(v, 1, 2), causal=causal)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.swapaxes(want, 1, 2)),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_contract_errors_survive_optimization():
    """The shape contracts are ValueErrors, not asserts: they must hold even
    under ``python -O`` (which strips assert statements)."""
    from repro.kernels import flash_attention as fa
    q = jnp.zeros((1, 3, 64, 16))   # H=3
    kv = jnp.zeros((1, 2, 64, 16))  # KvH=2 does not divide H
    with pytest.raises(ValueError, match="multiple of KvH"):
        fa.flash_attention(q, kv, kv, interpret=True)
    q = jnp.zeros((1, 2, 48, 16))   # Sq=48 does not tile by bq=32
    kv = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match="must tile"):
        fa.flash_attention(q, kv, kv, bq=32, bk=32, interpret=True)
    q = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match="kv_len"):
        fa.flash_attention(q, kv, kv, kv_len=65, interpret=True)
