"""HIR -> Pallas lowering: every loop-nest gallery kernel must match its
NumPy oracle and the functional JAX lowering, proving the schedule -> grid /
state -> scratch mapping preserves the algorithm.  These run on the CPU, so
every kernel is called with ``interpret=True``; ``tests/test_tpu_compile.py``
compiles the same kernels for the TPU."""

import numpy as np
import pytest

from repro.core.gallery import array_add, conv2d, histogram, stencil1d, transpose
from repro.core.lower import lower_to_jax
from repro.core.lower.to_pallas import lower_to_pallas


def _run(build, make_inputs, oracle_args=None):
    module, name = build()
    fn = lower_to_pallas(module, name, interpret=True)
    inputs = make_inputs()
    n_in = sum(1 for a in module.get(name).args
               if a.type.port == "r")
    outs = fn(*inputs[:n_in])
    return module, name, inputs, outs


def test_array_add():
    module, name, inputs, outs = _run(array_add.build, array_add.make_inputs)
    want = array_add.oracle(inputs[0], inputs[1])
    np.testing.assert_array_equal(np.asarray(outs["C"], np.int64), want)


def test_transpose():
    module, name, inputs, outs = _run(transpose.build, transpose.make_inputs)
    want = transpose.oracle(inputs[0])
    np.testing.assert_array_equal(np.asarray(outs["Co"], np.int64), want)


def test_stencil1d():
    module, name, inputs, outs = _run(stencil1d.build, stencil1d.make_inputs)
    want = stencil1d.oracle(inputs[0])
    np.testing.assert_array_equal(np.asarray(outs["Bw"], np.int64), want)


def test_histogram():
    module, name, inputs, outs = _run(histogram.build, histogram.make_inputs)
    want = histogram.oracle(inputs[0])
    np.testing.assert_array_equal(np.asarray(outs["Out"], np.int64), want)


def test_conv2d():
    module, name, inputs, outs = _run(conv2d.build, conv2d.make_inputs)
    want = conv2d.oracle(inputs[0])
    np.testing.assert_array_equal(np.asarray(outs["Out"], np.int64), want)


@pytest.mark.parametrize("gal", [array_add, transpose, stencil1d, histogram, conv2d])
def test_pallas_agrees_with_functional_lowering(gal):
    """Same HIR, two lowerings (functional JAX vs Pallas grid): identical."""
    module, name = gal.build()
    inputs = gal.make_inputs()
    n_in = sum(1 for a in module.get(name).args if a.type.port == "r")
    jfn = lower_to_jax(module, name)
    pfn = lower_to_pallas(module, name, interpret=True)
    jout = jfn(*inputs)
    pout = pfn(*inputs[:n_in])
    for k, v in pout.items():
        np.testing.assert_array_equal(np.asarray(v, np.int64),
                                      np.asarray(jout[k], np.int64))


def test_gemm_binds_to_mxu_kernel():
    """The systolic GEMM's TPU binding is the MXU matmul kernel (DESIGN §3):
    same math, hardware systolic array instead of PE emulation."""
    import jax.numpy as jnp

    from repro.core.gallery import gemm
    from repro.kernels import ops

    module, name = gemm.build()
    a, b, _ = gemm.make_inputs()
    want = gemm.oracle(a, b)
    got = ops.matmul(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32),
                     bm=16, bn=16, bk=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.int64), want)


# -- dtype coercion policy ----------------------------------------------------


def _build_float_add(elem):
    """8-wide elementwise add over float memrefs (array_add shape)."""
    from repro.core import ir
    from repro.core.builder import Builder

    b = Builder(ir.Module("fadd"))
    r = ir.MemrefType((8,), elem, ir.PORT_R)
    w = ir.MemrefType((8,), elem, ir.PORT_W)
    with b.func("fadd", [r, r, w], ["A", "B", "C"]) as f:
        A, B, C = f.args
        with b.for_(0, 8, 1, at=f.t + 1, iv_name="i", tv_name="ti") as li:
            b.yield_(at=li.time + 1)
            a = b.read(A, [li.iv], at=li.time)
            v = b.read(B, [li.iv], at=li.time)
            c = b.add(a, v)
            i1 = b.delay(li.iv, 1, at=li.time)
            b.write(c, C, [i1], at=li.time + 1)
        b.ret()
    return b.module, "fadd"


def test_pallas_f64_raises_by_default():
    """The old behavior silently truncated f64 -> f32; now it is an error
    unless the caller opts into the downcast explicitly."""
    from repro.core import ir

    module, name = _build_float_add(ir.FloatType(64))
    with pytest.raises(TypeError, match="allow_downcast"):
        lower_to_pallas(module, name)


def test_pallas_f64_downcast_is_explicit_and_warned():
    import warnings

    from repro.core import ir
    from repro.core.lower.common import PrecisionWarning

    module, name = _build_float_add(ir.FloatType(64))
    with pytest.warns(PrecisionWarning, match="f64 -> f32"):
        fn = lower_to_pallas(module, name, allow_downcast=True,
                             interpret=True)
    a = np.arange(8.0)
    b = 2.0 * np.arange(8.0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", PrecisionWarning)
        out = fn(a, b)
    np.testing.assert_allclose(np.asarray(out["C"], np.float64), a + b)


def test_pallas_f16_maps_to_bf16_with_warning():
    from repro.core import ir
    from repro.core.lower.common import PrecisionWarning

    module, name = _build_float_add(ir.FloatType(16))
    with pytest.warns(PrecisionWarning, match="bfloat16"):
        fn = lower_to_pallas(module, name)
