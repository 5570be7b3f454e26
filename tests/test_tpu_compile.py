"""The main path's device programs, compiled for one described TPU v5e chip.

Nothing runs: each case lowers a program for a chip that is described, not
attached, and compiles it with the TPU compiler, which refuses what the chip
would refuse (scalar stores to VMEM, unaligned tiles, too much VMEM) and
which no interpret-mode test can see.  Covered: the batched netlist
simulator's scan, the ``lower_to_pallas`` bindings, and the MXU matmul at
the tiles of the gemm binding.  The topology is described inside a fixture,
so collecting this file loads no TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.codegen import sim as rsim
from repro.core.gallery import GALLERY
from repro.core.lower.to_pallas import lower_to_pallas
from repro.kernels import matmul as mm
from repro.kernels import ops

#: the tiles ``chip_smoke.pallas_bindings`` passes to ``ops.matmul`` for the
#: gemm binding: one MXU-aligned block
GEMM_TILE = 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    saved = jax.config.jax_enable_compilation_cache
    # a TPU executable written to the persistent cache cannot be read back
    # without the chip; keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved)


def _spec(a, sharding, dtype=None):
    a = np.asarray(a)
    return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=sharding)


@pytest.mark.parametrize("kernel,kw", [("stencil1d", {}), ("gemm", {"n": 4})])
def test_simulator_scan_compiles(one_chip, kernel, kw):
    gal = GALLERY[kernel]
    module, entry = gal.build(**kw)
    sim, prepared = rsim.simulator_for(module, entry)
    lanes = 256
    batch = rsim.stack_stimulus(gal.make_inputs, lanes, **kw)
    cycles = rsim.probe_cycles(prepared, entry, [a[0] for a in batch])
    with jax.enable_x64(True):
        state = sim._init_state(batch, lanes)
        scanner, _names = sim.scan_program()
        compiled = jax.jit(scanner).lower(
            {k: _spec(v, one_chip) for k, v in state.items()},
            _spec(np.zeros(cycles, np.int64), one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= sum(v.nbytes
                                             for v in state.values())


@pytest.mark.parametrize("kernel", ["array_add", "stencil1d", "conv2d"])
def test_pallas_binding_compiles(one_chip, kernel):
    gal = GALLERY[kernel]
    module, entry = gal.build()
    fn = lower_to_pallas(module, entry, interpret=False)
    n_in = sum(1 for a in module.get(entry).args if a.type.port == "r")
    ins = [_spec(x, one_chip, jnp.int32) for x in gal.make_inputs()[:n_in]]
    text = jax.jit(fn).lower(*ins).compile().as_text()
    assert "tpu_custom_call" in text


def test_gemm_mxu_binding_compiles(one_chip):
    x = _spec(np.zeros((16, 16), np.float32), one_chip)
    text = jax.jit(lambda a, b: ops.matmul(
        a, b, bm=GEMM_TILE, bn=GEMM_TILE, bk=GEMM_TILE)).lower(x, x) \
        .compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype,bm,bn,bk", [
    (jnp.bfloat16, 1024, 1024, 768),
    (jnp.bfloat16, 1024, 1024, 1024),
    (jnp.float32, 512, 512, 512),
    (jnp.float32, 640, 640, 640),
])
def test_matmul_vmem_budget_matches_compiler(one_chip, monkeypatch, dtype,
                                             bm, bn, bk):
    """``check_schedule`` admits exactly the tiles the compiler accepts."""
    admitted = not mm.check_schedule(2 * bm, 2 * bn, 2 * bk, bm, bn, bk,
                                     jnp.dtype(dtype).itemsize)
    monkeypatch.setattr(mm, "VMEM_BUDGET", 1 << 40)
    x = jax.ShapeDtypeStruct((2 * bm, 2 * bk), dtype, sharding=one_chip)
    y = jax.ShapeDtypeStruct((2 * bk, 2 * bn), dtype, sharding=one_chip)
    step = jax.jit(lambda a, b: mm.matmul(a, b, bm=bm, bn=bn, bk=bk))
    try:
        step.lower(x, y).compile()
        compiles = True
    except jax.errors.JaxRuntimeError as e:
        assert "vmem" in str(e).lower(), e
        compiles = False
    assert admitted == compiles
