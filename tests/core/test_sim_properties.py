"""Hypothesis property tests for the vectorized RTL simulator.

Three properties, all derandomized/seeded for CI reproducibility:

  * random hand-built ``RTLModule``s simulate identically before and after
    every RTL pass in ``RTL_PIPELINE_SPEC`` (per-cycle output-port traces);
  * on the same random modules the numpy and jax backends produce identical
    traces;
  * on gallery kernels with hypothesis-drawn seeds, the vectorized
    simulator matches the event-driven ``lower.simulate`` oracle exactly.

Per-lane stimulus comes from ``sim.fold_in_stimulus`` — jax-native
``fold_in`` counter streams keyed by the hypothesis-drawn seed — rather
than a shared sequential generator, so lane values are stable under suite
growth while staying pinned by ``@seed``.
"""

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")

# seed-pinned fuzz: runs in the CI differential-fuzz step and the full job
pytestmark = pytest.mark.slow
from hypothesis import given, seed, settings, strategies as st  # noqa: E402

from repro.core import ir  # noqa: E402
from repro.core.codegen import sim as rsim  # noqa: E402
from repro.core.codegen.rtl import RTL_PIPELINE_SPEC, RTLDesign  # noqa: E402
from repro.core.gallery import array_add, stencil1d, transpose  # noqa: E402
from repro.core.lower import simulate  # noqa: E402
from repro.core.passmgr import PassManager  # noqa: E402

from test_backend_properties import rtl_modules  # noqa: E402

CYCLES = 64
LANES = 4


def _wrap(m):
    """Give a raw strategy-built RTLModule the hir.func facade the simulator
    binds against: every ``in*`` port becomes one scalar unsigned argument."""
    ins = [p for p in m.ports if p.name.startswith("in")]
    f = ir.FuncOp("pm", [ir.IntType(p.width, signed=False) for p in ins],
                  [p.name for p in ins])
    for i, p in enumerate(ins):
        m.arg_ports[i] = [(p.name, "input", "data", 0)]
    return f, ins


def _stimulus(ins, sd):
    # jax-native fold_in streams (per-input, per-lane).  Widths are capped at
    # 16 bits so multi-op datapaths stay inside the simulators' i64 domain.
    return rsim.fold_in_stimulus([min(p.width, 16) for p in ins], LANES,
                                 seed=sd)


def _signature(design, func, stim):
    s = rsim.RTLSimulator(design.copy(), func, "pm", backend="numpy")
    return s.run(stim, CYCLES, batched=True, check_conflicts=False,
                 trace=True)


@seed(20260808)
@given(rtl_modules(), st.integers(0, 2**16))
@settings(max_examples=15, deadline=None)
def test_rtl_passes_preserve_cycle_accuracy(m, sd):
    func, ins = _wrap(m)
    design = RTLDesign(entry="pm")
    design.add(m)
    stim = _stimulus(ins, sd)
    prev = _signature(design, func, stim)
    for name in [p.strip() for p in RTL_PIPELINE_SPEC.split(",") if p.strip()]:
        PassManager.from_spec(name).run(design)
        cur = _signature(design, func, stim)
        for p, tr in prev.trace.items():
            assert p in cur.trace, (name, p)
            assert np.array_equal(tr, cur.trace[p]), (name, p)
        prev = cur


@seed(20260808)
@given(rtl_modules(), st.integers(0, 2**16))
@settings(max_examples=10, deadline=None)
def test_backends_agree_on_random_modules(m, sd):
    func, ins = _wrap(m)
    design = RTLDesign(entry="pm")
    design.add(m)
    stim = _stimulus(ins, sd)
    a = _signature(design, func, stim)
    s = rsim.RTLSimulator(design.copy(), func, "pm", backend="jax")
    b = s.run(stim, CYCLES, batched=True, check_conflicts=False, trace=True)
    for p, tr in a.trace.items():
        assert np.array_equal(tr, b.trace[p]), p


_GALLERY = {
    "array_add": (array_add, {"n": 8}, {"n": 8}),
    "transpose": (transpose, {"n": 4}, {"n": 4}),
    "stencil1d": (stencil1d, {"n": 8}, {"n": 8}),
}


@seed(20260808)
@given(st.sampled_from(sorted(_GALLERY)), st.integers(0, 2**16))
@settings(max_examples=12, deadline=None)
def test_vectorized_matches_event_driven(kernel, sd):
    gal, bkw, ikw = _GALLERY[kernel]
    mod, entry = gal.build(**bkw)
    args = [np.asarray(a, dtype=np.int64)
            for a in gal.make_inputs(seed=sd, **ikw)]
    sim, prepared = rsim.simulator_for(mod, entry, backend="numpy")
    cycles = rsim.probe_cycles(prepared, entry, args)
    res = sim.run(args, cycles)
    ev_args = [a.copy() for a in args]
    simulate(prepared, entry, ev_args)
    for i, a in enumerate(ev_args):
        assert np.array_equal(res.arrays[i][0], a), f"arg {i}"
