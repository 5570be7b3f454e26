"""Lane-uniformity in the batched RTL simulator.

``RTLSimulator`` proves which nets hold the same value in every lane
(``varying`` is the complement), evaluates those once per cycle, and reads
and writes a memory through a port whose address is lane-uniform as one row
of lanes (``mem_ports``, ``row_ports``/``gather_ports``).  These tests pin
the analysis on the gallery, check the split step against the NumPy twin and
the event-driven simulator on mixed-stimulus batches, and keep the compiled
scan free of per-lane gathers and scatters where every port is a row port.
"""

import re

import jax
import numpy as np
import pytest

from repro.core import ir
from repro.core.builder import Builder
from repro.core.codegen import sim as rsim
from repro.core.codegen.rtl import MemRead, MemWrite
from repro.core.gallery import GALLERY, conv2d, fifo, gemm, histogram
from repro.core.lower import simulate_batch

# kernel -> (module, build kwargs, make_inputs kwargs)
KERNELS = {
    "histogram": (histogram, {"n": 8, "bins": 4}, {"n": 8, "bins": 4}),
    "conv2d": (conv2d, {"h": 6, "w": 6}, {"h": 6, "w": 6}),
    "gemm": (gemm, {"n": 4}, {"n": 4}),
    "fifo": (fifo, {"depth": 16, "n": 8}, {"n": 8}),
}


def _fill(n: int = 8):
    """``C[i] = i`` for ``i < k``: a loop whose bound is the scalar input
    ``k``, so its controller, and the write port it addresses, differ
    between lanes."""
    b = Builder(ir.Module("fill"))
    w = ir.MemrefType((n,), ir.i32, ir.PORT_W)
    with b.func("fill", [ir.i32, w], ["k", "C"]) as f:
        k, C = f.args
        with b.for_(0, k, 1, at=f.t, iv_type=ir.i32) as li:
            b.yield_(at=li.time + 1)
            i1 = b.delay(li.iv, 1, at=li.time)
            b.write(i1, C, [i1], at=li.time + 1)
        b.ret()
    return b.module, "fill"


def _mixed_batch(kernel: str, lanes: int = 12) -> list[np.ndarray]:
    """Distinct random stimulus per lane, with one all-zero lane and one
    lane repeated, so that no lane's value stands in for another's."""
    gal, _bkw, ikw = KERNELS[kernel]
    batch = rsim.stack_stimulus(gal.make_inputs, lanes, base_seed=21, **ikw)
    for a in batch:
        a[1] = 0
        a[-1] = a[2]
    return batch


def _mem_items(sim):
    return [it for it in sim.flat.items if isinstance(it, (MemRead, MemWrite))]


@pytest.mark.parametrize("kernel,kw", [
    ("conv2d", {"h": 6, "w": 6}), ("conv2d", {"h": 16, "w": 64}),
    ("gemm", {"n": 4}), ("gemm", {"n": 16}), ("transpose", {"n": 4}),
    ("stencil1d", {"n": 8}), ("fifo", {"depth": 16, "n": 8}),
    ("gemm_shared", {"n": 4}), ("array_add", {"n": 8})])
def test_every_port_takes_the_row_path(kernel, kw):
    mod, entry = GALLERY[kernel].build(**kw)
    sim, _ = rsim.simulator_for(mod, entry)
    ports = _mem_items(sim)
    assert ports
    assert (sim.row_ports, sim.gather_ports) == (len(ports), 0)
    assert all(row for _t, _k, row in sim.mem_ports)
    assert 0 < sim.uniform_nets < len(sim.widths)


@pytest.mark.parametrize("n,bins", [(8, 4), (8192, 256)])
def test_histogram_bin_ports_take_the_gather_path(n, bins):
    mod, entry = histogram.build(n=n, bins=bins)
    sim, _ = rsim.simulator_for(mod, entry)
    assert (sim.row_ports, sim.gather_ports) == (4, 2)
    gathered = {(t, k) for t, k, row in sim.mem_ports if not row}
    # the bin memory is read and written at an address read from the data
    bins = {k for _t, k in gathered}
    assert len(bins) == 1 and {t for t, _k in gathered} == {"memrd", "memwr"}
    assert all(row for t, k, row in sim.mem_ports if k not in bins)
    # a port is a row port exactly when its address reads no varying net
    for it, (_t, _k, row) in zip(_mem_items(sim), sim.mem_ports):
        assert row == sim.varying.isdisjoint(it.addr.refs())


def test_histogram_at_published_widths_counts_past_eight_bits():
    """One 8-bit CLAHE tile of 8,192 pixels into 256 bins, through the JAX
    scan's per-lane gather and scatter.  Uniform tiles keep every bin near
    32; a constant tile fills one bin to 8,192 and a dark tile puts most
    pixels in a few bins, so state or a bin memory narrowed below 14 bits
    would wrap.  The II=2 main loop fixes the cycle count."""
    mod, entry = histogram.build(n=8192, bins=256)
    sim, prepared = rsim.simulator_for(mod, entry)
    rng = np.random.default_rng(16)
    img = rng.integers(0, 256, size=(4, 8192))
    img[0] = 200
    img[1] = np.minimum(rng.geometric(1 / 8, size=8192) - 1, 255)
    img[2, :6000] = 255
    out = np.zeros((4, 256), dtype=np.int64)
    cycles = rsim.probe_cycles(prepared, entry, [img[0], out[0]])
    assert cycles == 16916
    got = sim.run([img, out], cycles, batched=True).arrays[1]
    want = np.stack([np.bincount(tile, minlength=256) for tile in img])
    assert want[0, 200] == 8192 and want[1].max() > 2 ** 9
    np.testing.assert_array_equal(got, want)


def test_scalar_loop_bound_makes_its_controller_lane_varying():
    mod, entry = _fill()
    sim, _ = rsim.simulator_for(mod, entry)
    (ctrl,) = [it for it in sim.flat.items
               if type(it).__name__ == "LoopController"]
    for net in (ctrl.iv, ctrl.active, ctrl.iter_net):
        assert net in sim.varying, net
    assert (sim.row_ports, sim.gather_ports) == (0, 1)
    # t_start and the nets that only delay it stay uniform
    assert "t_start" not in sim.varying and sim.uniform_nets >= 1


def test_scalar_loop_bound_lanes_differ_and_agree():
    mod, entry = _fill()
    ks = np.array([0, 3, 8, 5, 1, 8, 2], dtype=np.int64)
    batch = [ks, np.zeros((len(ks), 8), dtype=np.int64)]
    sim, prepared = rsim.simulator_for(mod, entry)
    twin, _ = rsim.simulator_for(mod, entry, backend="numpy")
    cycles = rsim.probe_cycles(prepared, entry, [8, batch[1][0]])
    got = sim.run(batch, cycles, batched=True, trace=True)
    want = twin.run(batch, cycles, batched=True, trace=True)
    expect = np.where(np.arange(8)[None, :] < ks[:, None],
                      np.arange(8)[None, :], 0)
    assert np.array_equal(got.arrays[1], expect)
    assert np.array_equal(want.arrays[1], expect)
    for p in want.trace:
        assert np.array_equal(got.trace[p], want.trace[p]), p
    _, finals = simulate_batch(prepared, entry, batch)
    assert np.array_equal(finals[1], expect)


@pytest.mark.parametrize("trace", [True, False])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_split_step_matches_numpy_and_event_sim(kernel, trace):
    gal, bkw, _ikw = KERNELS[kernel]
    mod, entry = gal.build(**bkw)
    batch = _mixed_batch(kernel)
    sim, prepared = rsim.simulator_for(mod, entry)
    twin, _ = rsim.simulator_for(mod, entry, backend="numpy")
    cycles = rsim.probe_cycles(prepared, entry, [a[0] for a in batch])
    got = sim.run(batch, cycles, batched=True, trace=trace)
    want = twin.run(batch, cycles, batched=True, trace=trace)
    assert got.platform == "cpu"
    for i in want.arrays:
        assert np.array_equal(got.arrays[i], want.arrays[i]), f"arg {i}"
    for a, b in zip(got.returns + got.returns_valid,
                    want.returns + want.returns_valid):
        assert np.array_equal(a, b)
    assert np.array_equal(got.conflicts, want.conflicts)
    if trace:
        assert got.trace.keys() == want.trace.keys()
        for p, tr in want.trace.items():
            assert got.trace[p].shape == (cycles, len(batch[0]))
            assert np.array_equal(got.trace[p], tr), p
    else:
        assert got.trace is None
    _, finals = simulate_batch(prepared, entry, batch)
    for i, fin in enumerate(finals):
        if fin is not None:
            assert np.array_equal(got.arrays[i], fin), f"arg {i}"


def test_scan_program_keeps_the_host_layout():
    mod, entry = conv2d.build(h=6, w=6)
    sim, prepared = rsim.simulator_for(mod, entry)
    batch = _mixed_batch("conv2d", lanes=5)
    cycles = rsim.probe_cycles(prepared, entry, [a[0] for a in batch])
    with jax.enable_x64(True):
        state = sim._init_state(batch, 5)
        scanner, names = sim.scan_program(trace=True)
        xs = np.zeros(cycles, np.int64)
        xs[0] = 1
        final, ys = jax.jit(scanner)(state, xs)
    assert final.keys() == state.keys()
    for k, v in state.items():
        assert final[k].shape == v.shape, k
    assert [y.shape for y in ys] == [(cycles, 5)] * len(names)
    # uniform state and trace ports come back equal in every lane
    for k in final:
        if k in sim.state_nets and k not in sim.varying:
            assert np.all(np.asarray(final[k]) == np.asarray(final[k])[0])


def _scan_hlo(kernel: str, kw: dict, ikw: dict) -> str:
    gal = GALLERY[kernel]
    mod, entry = gal.build(**kw)
    sim, prepared = rsim.simulator_for(mod, entry)
    batch = rsim.stack_stimulus(gal.make_inputs, 8, **ikw)
    cycles = rsim.probe_cycles(prepared, entry, [a[0] for a in batch])
    with jax.enable_x64(True):
        state = sim._init_state(batch, 8)
        scanner, _names = sim.scan_program()
        return jax.jit(scanner).lower(
            state, np.zeros(cycles, np.int64)).compile().as_text()


def _ops(hlo: str, name: str) -> int:
    return len(re.findall(rf"\s{name}\(", hlo))


def test_row_ports_compile_to_slices_not_gathers():
    hlo = _scan_hlo("conv2d", {"h": 6, "w": 6}, {"h": 6, "w": 6})
    assert _ops(hlo, "gather") == 0 and _ops(hlo, "scatter") == 0
    assert _ops(hlo, "dynamic-update-slice") > 0


def test_data_dependent_ports_keep_gathers_and_scatters():
    hlo = _scan_hlo("histogram", {"n": 8, "bins": 4}, {"n": 8, "bins": 4})
    assert _ops(hlo, "gather") > 0 and _ops(hlo, "scatter") > 0

