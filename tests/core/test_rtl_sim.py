"""Differential acceptance tests for the vectorized RTL simulator.

Every gallery kernel is run on >= 256 random stimulus vectors through the
batched cycle-accurate simulator and checked three ways (``run_differential``):
against the event-driven HIR simulator on sample lanes, against the kernel's
functional numpy oracle on *every* lane, and per-RTL-pass (pass input vs pass
output, per-cycle result-port traces) — in both inline and hierarchical
emission modes.  Plus unit tests for the simulator's value semantics and the
batch/stimulus API."""

import functools

import numpy as np
import pytest

from repro.core.codegen import sim as rsim
from repro.core.codegen.rtl import Binop, Const, Ref, Signed
from repro.core.gallery import (array_add, conv2d, fifo, gemm, gemm_shared,
                                histogram, mac, stencil1d, transpose)
from repro.core.lower import simulate_batch

N_VECTORS = 256

# kernel -> (module, build kwargs, make_inputs kwargs, oracle, oracle_nargs)
KERNELS = {
    "array_add": (array_add, {"n": 8}, {"n": 8}, array_add.oracle, 2),
    "transpose": (transpose, {"n": 4}, {"n": 4}, transpose.oracle, 1),
    "gemm": (gemm, {"n": 4}, {"n": 4}, gemm.oracle, 2),
    # column-staggered II=n schedule: its hierarchical emission exercises
    # rtl-share-instances' time-division muxes under the full matrix
    "gemm_shared": (gemm_shared, {"n": 4}, {"n": 4}, gemm_shared.oracle, 2),
    "stencil1d": (stencil1d, {"n": 8}, {"n": 8}, stencil1d.oracle, 1),
    "conv2d": (conv2d, {"h": 6, "w": 6}, {"h": 6, "w": 6}, conv2d.oracle, 1),
    "histogram": (histogram, {"n": 8, "bins": 4}, {"n": 8, "bins": 4},
                  functools.partial(histogram.oracle, bins=4), 1),
    "fifo": (fifo, {"depth": 16, "n": 8}, {"n": 8}, fifo.oracle, 1),
}

HIERARCHIES = ["inline", "modules"]


@pytest.mark.slow
@pytest.mark.parametrize("hierarchy", HIERARCHIES)
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_gallery_differential(kernel, hierarchy):
    gal, bkw, ikw, oracle, nargs = KERNELS[kernel]
    mod, entry = gal.build(**bkw)
    batch = rsim.stack_stimulus(gal.make_inputs, N_VECTORS, base_seed=7,
                                **ikw)
    rep = rsim.run_differential(mod, entry, batch, kernel=kernel,
                                hierarchy=hierarchy, oracle=oracle,
                                oracle_nargs=nargs)
    assert rep.ok, (kernel, hierarchy, rep.mismatches[:5])
    assert rep.n_vectors == N_VECTORS
    assert rep.event_lanes_checked >= 2
    assert rep.oracle_ok is True
    assert rep.passes_ok and all(rep.passes_ok.values()), rep.passes_ok


@pytest.mark.parametrize("hierarchy", HIERARCHIES)
def test_mac_differential(hierarchy):
    # mac takes three scalar args and returns a scalar — the oracle leg
    # checks the captured result value on every lane instead of a memref
    mod, entry = mac.build()
    rng = np.random.default_rng(11)
    batch = [rng.integers(0, 1 << 15, size=N_VECTORS).astype(np.int64)
             for _ in range(3)]
    rep = rsim.run_differential(mod, entry, batch, kernel="mac",
                                hierarchy=hierarchy)
    assert rep.ok, rep.mismatches[:5]
    sim, prepared = rsim.simulator_for(mod, entry, hierarchy=hierarchy)
    cycles = rsim.probe_cycles(prepared, entry, [int(c[0]) for c in batch])
    res = sim.run(batch, cycles, batched=True)
    want = np.array([mac.oracle(int(a), int(b), int(c))
                     for a, b, c in zip(*batch)], dtype=np.int64)
    assert np.all(np.asarray(res.returns_valid[0]) == 1)
    assert np.array_equal(np.asarray(res.returns[0]), want)


def test_numpy_and_jax_backends_agree():
    mod, entry = gemm.build(n=4)
    batch = rsim.stack_stimulus(gemm.make_inputs, 32, base_seed=3, n=4)
    results = {}
    for backend in ("numpy", "jax"):
        sim, prepared = rsim.simulator_for(mod, entry, backend=backend)
        cycles = rsim.probe_cycles(prepared, entry,
                                   [c[0] for c in batch])
        results[backend] = sim.run(batch, cycles, batched=True, trace=True)
    a, b = results["numpy"], results["jax"]
    for i in a.arrays:
        assert np.array_equal(a.arrays[i], b.arrays[i]), f"arg {i}"
    for p in a.trace:
        assert np.array_equal(a.trace[p], b.trace[p]), f"trace {p}"
    assert np.array_equal(a.conflicts, b.conflicts)
    assert not a.conflicts.any()


def test_vectorized_matches_event_batch():
    # simulate_batch (per-lane event-driven) and the batched simulator agree
    # on final memref state for every lane
    mod, entry = stencil1d.build(n=8)
    batch = rsim.stack_stimulus(stencil1d.make_inputs, 16, base_seed=5, n=8)
    sim, prepared = rsim.simulator_for(mod, entry, backend="numpy")
    cycles = rsim.probe_cycles(prepared, entry, [c[0] for c in batch])
    res = sim.run(batch, cycles, batched=True)
    _, finals = simulate_batch(prepared, entry, batch)
    for i, fin in enumerate(finals):
        if fin is not None:
            assert np.array_equal(res.arrays[i], fin), f"arg {i}"


def test_division_is_floor_and_by_zero_is_zero():
    # matches the event-driven oracle: signed floor division, x/0 == 0
    widths = {"a": 8, "b": 8}
    expr = Binop("/", Signed(Ref("a")), Signed(Ref("b")), width=8)
    fn, _ = rsim._compile_expr(expr, widths)
    ops = rsim._NumpyOps(4)
    env = {"a": np.array([0xF9, 0xF9, 7, 7], dtype=np.int64),   # -7,-7,7,7
           "b": np.array([2, 0, 2, 0xFE], dtype=np.int64)}      # 2,0,2,-2
    got = np.asarray(fn(env, ops)) & 0xFF
    assert got.tolist() == [(-4) & 0xFF, 0, 3, (-4) & 0xFF]


def test_shift_clamp_semantics():
    widths = {"a": 8, "s": 8}
    fn, _ = rsim._compile_expr(Binop("<<", Ref("a"), Ref("s"), width=8),
                               widths)
    ops = rsim._NumpyOps(3)
    env = {"a": np.array([1, 1, 0xFF], dtype=np.int64),
           "s": np.array([3, 200, 1], dtype=np.int64)}
    got = np.asarray(fn(env, ops)) & 0xFF
    assert got.tolist() == [8, 0, 0xFE]


def test_wide_nets_rejected():
    with pytest.raises(rsim.RTLSimError):
        rsim._mask_of(64)


def test_stack_stimulus_shapes_and_determinism():
    batch = rsim.stack_stimulus(array_add.make_inputs, 5, base_seed=1, n=8)
    assert [b.shape for b in batch] == [(5, 8)] * 3
    again = rsim.stack_stimulus(array_add.make_inputs, 5, base_seed=1, n=8)
    assert all(np.array_equal(a, b) for a, b in zip(batch, again))
    # lanes differ (distinct seeds)
    assert not np.array_equal(batch[0][0], batch[0][1])


def _fold_in_per_lane(widths, n_lanes, seed):
    """Reference: one fold_in stream and one host round trip per lane."""
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed)
    out = []
    for i, w in enumerate(widths):
        ki = jax.random.fold_in(key, i)
        lanes = []
        for lane in range(n_lanes):
            hi, lo = (int(b) for b in jax.random.bits(
                jax.random.fold_in(ki, lane), (2,), dtype=jnp.uint32))
            lanes.append(((hi << 32) | lo) & ((1 << min(w, 63)) - 1))
        out.append(np.asarray(lanes, dtype=np.int64))
    return out


@pytest.mark.parametrize("widths", [[1, 8, 16], [32, 63, 64]])
def test_fold_in_stimulus_matches_per_lane_streams(widths):
    got = rsim.fold_in_stimulus(widths, 9, seed=1234)
    want = _fold_in_per_lane(widths, 9, seed=1234)
    for g, w in zip(got, want):
        assert g.dtype == np.int64
        assert np.array_equal(g, w)
    # adding lanes never perturbs the existing ones
    more = rsim.fold_in_stimulus(widths, 12, seed=1234)
    assert all(np.array_equal(m[:9], g) for m, g in zip(more, got))


def test_unbatched_run_lifts_to_single_lane():
    mod, entry = array_add.build(n=8)
    sim, prepared = rsim.simulator_for(mod, entry, backend="numpy")
    args = array_add.make_inputs(n=8, seed=9)
    cycles = rsim.probe_cycles(prepared, entry, args)
    res = sim.run(args, cycles)
    assert res.batch == 1
    want = array_add.oracle(args[0], args[1])
    assert np.array_equal(res.arrays[2][0], want)


def test_all_backend_printers_simulate_identically():
    # (c) leg of the differential harness: every backend printer emits from
    # the same RTL structure, so the cycle-accurate behavior bound to each
    # backend's source modules must be identical (the text-level conformance
    # is covered by the PR 4 golden/lint suites)
    from repro.core.codegen import BACKENDS, generate_verilog
    from repro.core.codegen.sim import RTLSimulator, design_of

    batch = rsim.stack_stimulus(array_add.make_inputs, 16, base_seed=4, n=8)
    ref = None
    for backend in sorted(BACKENDS):
        mod, entry = array_add.build(n=8)
        prepared = mod
        mods = generate_verilog(prepared, entry, backend=backend)
        assert all(vm.text.strip() for vm in mods.values()), backend
        sim = RTLSimulator(design_of(mods, entry),
                           prepared.funcs[entry], entry, backend="numpy")
        cycles = rsim.probe_cycles(prepared, entry, [c[0] for c in batch])
        res = sim.run(batch, cycles, batched=True)
        if ref is None:
            ref = res
        else:
            for i in ref.arrays:
                assert np.array_equal(ref.arrays[i], res.arrays[i]), \
                    (backend, i)


RESCHEDULE_CONFIGS = [
    # (kernel, pipeline, clock_ns) — the exact configs where a rescheduled
    # callee body used to violate its declared result-delay contract (the
    # call site latched data one cycle early) or ControllerMerge dropped a
    # merged FSM's iicnt net with readers still attached; caught by this
    # batched differential, invisible to the event-driven HIR simulator
    ("stencil1d", True, 10.0),
    ("stencil1d", True, 2.5),
    ("gemm", True, 5.0),
    ("gemm", True, 2.5),
    ("gemm", False, 10.0),  # merged ii=2 controllers: dangling iicnt refs
]


@pytest.mark.parametrize("kernel,pipe,clock_ns", RESCHEDULE_CONFIGS)
def test_rescheduled_design_matches_oracle(kernel, pipe, clock_ns):
    from repro.core.hls import SchedulerOptions, erase_schedule, hls_schedule
    from repro.core.passmgr import DEFAULT_PIPELINE_SPEC, PassManager

    gal, bkw, ikw, oracle, nargs = KERNELS[kernel]
    mod, entry = gal.build(**bkw)
    um = erase_schedule(mod)
    hls_schedule(um, options=SchedulerOptions(pipeline_loops=pipe,
                                              clock_ns=clock_ns))
    PassManager.from_spec(DEFAULT_PIPELINE_SPEC).run(um)
    n_vec = 16
    batch = rsim.stack_stimulus(gal.make_inputs, n_vec, base_seed=9, **ikw)
    sim, prepared = rsim.simulator_for(um, entry, backend="numpy")
    cycles = rsim.probe_cycles(prepared, entry, [c[0] for c in batch])
    res = sim.run(batch, cycles, batched=True)
    want = np.stack([np.asarray(oracle(*[col[k] for col in batch[:nargs]]))
                     for k in range(n_vec)])
    got = np.asarray(res.arrays[len(batch) - 1]).reshape(want.shape)
    assert np.array_equal(got, want), (kernel, pipe, clock_ns)


def test_const_fold_matches_event_sim_on_passes():
    # verify_rtl_passes standalone: every RTL pass preserves per-cycle
    # result-port traces and final state on a real kernel
    mod, entry = transpose.build(n=4)
    batch = rsim.stack_stimulus(transpose.make_inputs, 8, base_seed=2, n=4)
    sim, prepared = rsim.simulator_for(mod, entry, backend="numpy")
    cycles = rsim.probe_cycles(prepared, entry, [c[0] for c in batch])
    ok, mism = rsim.verify_rtl_passes(prepared, entry, batch, cycles,
                                      hierarchy="inline")
    assert ok and all(ok.values()), mism[:5]
