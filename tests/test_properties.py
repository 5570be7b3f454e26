"""Hypothesis property tests on the framework's system invariants
(deliverable c): pass-pipeline semantic preservation, optimizer math,
compression error bounds, pipeline determinism, checkpoint round-trips,
kernel/oracle agreement over drawn shapes, and the HLO analyzer on
synthetic modules with known ground truth."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import ir, verifier
from repro.core.builder import Builder
from repro.core.lower import lower_to_jax, simulate
from repro.core.passes import run_pipeline


# ---------------------------------------------------------------------------
# 1. optimization passes never change semantics (random affine pipelines)
# ---------------------------------------------------------------------------


@st.composite
def affine_pipeline(draw):
    n = draw(st.integers(min_value=4, max_value=24))
    muls = draw(st.lists(st.integers(min_value=0, max_value=9), min_size=1, max_size=3))
    adds = draw(st.lists(st.integers(min_value=-5, max_value=5), min_size=1, max_size=3))
    return n, muls, adds


def _build(n, muls, adds):
    b = Builder(ir.Module("p"))
    r = ir.MemrefType((n,), ir.i32, ir.PORT_R)
    w = ir.MemrefType((n,), ir.i32, ir.PORT_W)
    with b.func("f", [r, w], ["A", "O"]) as f:
        A, O = f.args
        with b.for_(0, n, 1, at=f.t + 1) as l:
            b.yield_(at=l.time + 1)
            v = b.read(A, [l.iv], at=l.time)
            for m in muls:
                v = b.mult(v, m)
            for a in adds:
                v = b.add(v, a)
            i1 = b.delay(l.iv, 1, at=l.time)
            b.write(v, O, [i1], at=l.time + 1)
        b.ret()
    return b.module


@given(affine_pipeline())
@settings(max_examples=25, deadline=None)
def test_pass_pipeline_preserves_semantics(design):
    n, muls, adds = design
    m1 = _build(n, muls, adds)
    m2 = _build(n, muls, adds)
    run_pipeline(m2)   # constprop/cse/strength-reduce/precision/delay-elim
    assert not [d for d in verifier.verify(m2, raise_on_error=False)
                if d.severity == "error"]
    rng = np.random.default_rng(1)
    a = rng.integers(0, 2**10, size=(n,), dtype=np.int64)
    o1, o2 = np.zeros_like(a), np.zeros_like(a)
    simulate(m1, "f", [a.copy(), o1])
    simulate(m2, "f", [a.copy(), o2])
    np.testing.assert_array_equal(o1, o2)
    # the functional JAX lowering agrees with the optimized design too
    j = lower_to_jax(m2, "f")(a, np.zeros_like(a))["O"]
    np.testing.assert_array_equal(np.asarray(j, np.int64), o1)


# ---------------------------------------------------------------------------
# 2. optimizer invariants
# ---------------------------------------------------------------------------


@given(st.integers(min_value=1, max_value=64), st.floats(0.1, 10.0),
       st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_clip_by_global_norm_bounds(dim, max_norm, seed):
    from repro.optim.adamw import clip_by_global_norm, global_norm

    g = {"w": jax.random.normal(jax.random.key(seed), (dim,)) * 10}
    clipped, norm = clip_by_global_norm(g, max_norm)
    assert float(global_norm(clipped)) <= max_norm * 1.001
    # direction preserved
    ratio = np.asarray(clipped["w"]) / np.maximum(np.abs(np.asarray(g["w"])), 1e-9)
    assert (np.sign(np.asarray(clipped["w"])) == np.sign(np.asarray(g["w"]))).all()


@given(st.integers(min_value=1, max_value=32))
@settings(max_examples=10, deadline=None)
def test_adamw_zero_grad_no_decay_is_identity(dim):
    from repro.optim.adamw import OptCfg, adamw_update, init_opt_state

    p = {"w": jnp.ones((dim,)), "b": jnp.zeros((dim,))}  # ndim<2: never decayed
    opt = init_opt_state(p)
    g = jax.tree.map(jnp.zeros_like, p)
    newp, newopt, _ = adamw_update(g, opt, p, OptCfg(weight_decay=0.0))
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(newp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    assert int(newopt["step"]) == 1


# ---------------------------------------------------------------------------
# 3. int8 compression error bound
# ---------------------------------------------------------------------------


@given(st.integers(min_value=1, max_value=256), st.floats(1e-3, 1e3),
       st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_quantize_roundtrip_error_bound(n, scale, seed):
    from repro.parallel.compression import dequantize, quantize_int8

    x = jax.random.normal(jax.random.key(seed), (n,)) * scale
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize(q, s)) - np.asarray(x)).max()
    amax = np.abs(np.asarray(x)).max()
    assert err <= amax / 127.0 + 1e-12


# ---------------------------------------------------------------------------
# 4. data pipeline: determinism, seekability, host disjointness
# ---------------------------------------------------------------------------


@given(st.integers(min_value=0, max_value=10_000), st.integers(min_value=0, max_value=99))
@settings(max_examples=20, deadline=None)
def test_pipeline_batch_is_pure_function_of_step(step, seed):
    from repro.configs.base import ShapeCfg
    from repro.configs.registry import get_smoke_config
    from repro.data.pipeline import make_batch

    cfg = get_smoke_config("tinyllama-1.1b")
    shape = ShapeCfg("t", seq_len=8, global_batch=2, kind="train")
    b1 = make_batch(cfg, shape, step=step, seed=seed)
    b2 = make_batch(cfg, shape, step=step, seed=seed)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # adjacent steps differ (with overwhelming probability)
    b3 = make_batch(cfg, shape, step=step + 1, seed=seed)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are the next-token shift of the same stream
    assert b1["labels"].shape == b1["tokens"].shape


@given(st.integers(min_value=0, max_value=500))
@settings(max_examples=10, deadline=None)
def test_pipeline_host_shards_are_distinct(step):
    from repro.configs.base import ShapeCfg
    from repro.configs.registry import get_smoke_config
    from repro.data.pipeline import make_batch

    cfg = get_smoke_config("tinyllama-1.1b")
    shape = ShapeCfg("t", seq_len=16, global_batch=4, kind="train")
    h0 = make_batch(cfg, shape, step=step, host_id=0, n_hosts=2)
    h1 = make_batch(cfg, shape, step=step, host_id=1, n_hosts=2)
    assert h0["tokens"].shape[0] == 2
    assert not np.array_equal(h0["tokens"], h1["tokens"])


# ---------------------------------------------------------------------------
# 5. checkpoint round-trip on random pytrees
# ---------------------------------------------------------------------------


@given(st.lists(st.integers(min_value=1, max_value=16), min_size=1, max_size=4),
       st.sampled_from(["float32", "bfloat16", "int32"]))
@settings(max_examples=15, deadline=None)
def test_checkpoint_roundtrip_random_tree(dims, dtype):
    import tempfile

    from repro.checkpoint.store import restore, save

    tree = {f"leaf{i}": (jnp.arange(d * 2, dtype=dtype).reshape(d, 2) + i)
            for i, d in enumerate(dims)}
    with tempfile.TemporaryDirectory() as td:
        save(td, 3, tree)
        back, step = restore(td, tree)
        assert step == 3
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# 6. HLO analyzer ground truth on synthetic modules
# ---------------------------------------------------------------------------


@given(st.integers(min_value=1, max_value=64), st.integers(min_value=1, max_value=64),
       st.integers(min_value=1, max_value=64), st.integers(min_value=1, max_value=40))
@settings(max_examples=25, deadline=None)
def test_hlo_analyzer_dot_flops_and_trip_counts(m, n, k, trip):
    from repro.launch.hlo_analysis import HloModule

    hlo = f"""HloModule synth

%body (p: (s32[], f32[{m},{k}], f32[{k},{n}])) -> (s32[], f32[{m},{k}], f32[{k},{n}]) {{
  %p = (s32[], f32[{m},{k}], f32[{k},{n}]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %a = f32[{m},{k}]{{1,0}} get-tuple-element(%p), index=1
  %b = f32[{k},{n}]{{1,0}} get-tuple-element(%p), index=2
  %d = f32[{m},{n}]{{1,0}} dot(%a, %b), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
  %ar = f32[{m},{n}]{{1,0}} all-reduce(%d), replica_groups={{}}, to_apply=%add
  ROOT %t = (s32[], f32[{m},{k}], f32[{k},{n}]) tuple(%i, %a, %b)
}}

%cond (p: (s32[], f32[{m},{k}], f32[{k},{n}])) -> pred[] {{
  %p = (s32[], f32[{m},{k}], f32[{k},{n}]) parameter(0)
  ROOT %lt = pred[] constant(true)
}}

ENTRY %main (x: f32[{m},{k}], y: f32[{k},{n}]) -> f32[] {{
  %x = f32[{m},{k}]{{1,0}} parameter(0)
  %y = f32[{k},{n}]{{1,0}} parameter(1)
  %init = (s32[], f32[{m},{k}], f32[{k},{n}]) tuple(%x, %x, %y)
  %w = (s32[], f32[{m},{k}], f32[{k},{n}]) while(%init), condition=%cond, body=%body, backend_config={{"known_trip_count":{{"n":"{trip}"}}}}
  ROOT %r = f32[] constant(0)
}}
"""
    st_ = HloModule(hlo).stats()
    assert st_.flops == 2.0 * m * n * k * trip
    assert st_.coll_bytes == 4.0 * m * n * trip
    assert st_.coll_by_kind == {"all-reduce": 4.0 * m * n * trip}


# ---------------------------------------------------------------------------
# 7. kernels vs oracles over drawn shapes (interpret mode, kept small)
# ---------------------------------------------------------------------------


@given(st.integers(min_value=1, max_value=40), st.integers(min_value=1, max_value=40),
       st.integers(min_value=1, max_value=40))
@settings(max_examples=10, deadline=None)
def test_matmul_kernel_any_shape(m, k, n):
    from repro.kernels import ops, ref

    k1, k2 = jax.random.split(jax.random.key(m * 1000 + k * 100 + n))
    x = jax.random.normal(k1, (m, k), jnp.float32)
    y = jax.random.normal(k2, (k, n), jnp.float32)
    out = ops.matmul(x, y, bm=16, bn=16, bk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(x, y)),
                               rtol=3e-5, atol=3e-5)


@given(st.integers(min_value=1, max_value=48), st.integers(min_value=1, max_value=24))
@settings(max_examples=10, deadline=None)
def test_rglru_kernel_any_shape(S, D):
    from repro.kernels import ops, ref

    k1, k2 = jax.random.split(jax.random.key(S * 100 + D))
    a = jax.random.uniform(k1, (2, S, D), jnp.float32, 0.5, 0.999)
    b = jax.random.normal(k2, (2, S, D), jnp.float32)
    h = ops.rglru_scan(a, b, bs=16, bd=16, interpret=True)
    want, _ = ref.rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want), rtol=2e-4, atol=2e-4)
