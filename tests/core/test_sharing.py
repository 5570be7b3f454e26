"""Cross-instance time-multiplexing (`rtl-share-instances`) and II-aware
arbitration (`rtl-arbitrate`): the `activation-intervals` pulse analysis,
the merge itself (gemm_shared's staggered II=n schedule shares, plain gemm's
coincident schedule must refuse), resource accounting (`sharing_summary`),
the `PortConflictAssert` conflict lanes under the vectorized simulator on
both backends, the DSE `share_instances` knob, and the `rtl-dce`
dangling-net audit (`REPRO_RTL_AUDIT=1`)."""

import numpy as np
import pytest

from repro.core import ir
from repro.core.analysis import (PULSES_TOP, ActivationIntervals,
                                 ActivationIntervalsAnalysis)
from repro.core.builder import Builder
from repro.core.codegen import sim as rsim
from repro.core.codegen import (generate_verilog, report_design,
                                sharing_summary)
from repro.core.codegen.rtl import Instance
from repro.core.codegen.sim import RTLSimError
from repro.core.gallery import GALLERY, gemm, gemm_shared
from repro.core.passes import DEFAULT_PIPELINE_SPEC, PassManager


def _emit(name, hierarchy="modules", rtl_spec="default", **bkw):
    gal = GALLERY[name]
    m, entry = gal.build(**bkw)
    PassManager.from_spec(DEFAULT_PIPELINE_SPEC).run(m)
    kw = {} if rtl_spec == "default" else {"rtl_spec": rtl_spec}
    mods = generate_verilog(m, entry=entry, hierarchy=hierarchy, **kw)
    return mods, entry


# ---------------------------------------------------------------------------
# activation-intervals analysis
# ---------------------------------------------------------------------------


def test_pulses_of_staggered_instances_are_finite_and_disjoint():
    """On gemm_shared's hand schedule, every mac instance in one PE row has
    a finite t_start pulse set, and the sets within a row are pairwise
    disjoint — exactly the precondition rtl-share-instances merges on."""
    mods, entry = _emit("gemm_shared", rtl_spec=None, n=4)
    m = mods[entry].rtl
    ai = ActivationIntervalsAnalysis.run(m, None)
    assert isinstance(ai, ActivationIntervals)
    pulses = []
    for it in m.items:
        if isinstance(it, Instance):
            ts = dict((p, e) for p, e, _o in it.conns)["t_start"]
            pulses.append(ai.of_expr(ts))
    assert len(pulses) == 16
    assert all(p is not PULSES_TOP and len(p) == 4 for p in pulses)
    # 4 groups of 4 mutually-disjoint schedules (one group per PE row)
    rows = [pulses[i:i + 4] for i in range(0, 16, 4)]
    for row in rows:
        union = frozenset().union(*row)
        assert len(union) == sum(len(p) for p in row)   # pairwise disjoint


def test_pulses_of_coincident_instances_overlap():
    """Plain gemm fires all PEs of a wavefront in the same cycle: the sets
    must overlap (or be TOP), so sharing is correctly refused."""
    mods, entry = _emit("gemm", rtl_spec=None, n=4)
    m = mods[entry].rtl
    ai = ActivationIntervalsAnalysis.run(m, None)
    pulses = [ai.of_expr(dict((p, e) for p, e, _o in it.conns)["t_start"])
              for it in m.items if isinstance(it, Instance)]
    assert len(pulses) == 16
    finite = [p for p in pulses if p is not PULSES_TOP]
    assert any(a & b for i, a in enumerate(finite)
               for b in finite[i + 1:]), "expected coinciding pulses"


def test_tstart_is_cycle_zero():
    mods, entry = _emit("mac", rtl_spec=None, hierarchy="inline")
    ai = ActivationIntervalsAnalysis.run(mods[entry].rtl, None)
    assert ai.of_net("t_start") == frozenset({0})


# ---------------------------------------------------------------------------
# rtl-share-instances / rtl-arbitrate
# ---------------------------------------------------------------------------


def test_gemm_shared_merges_and_gemm_refuses():
    shared, entry = _emit("gemm_shared", n=4)
    sh = sharing_summary(shared, entry=entry)
    assert sh["per_module"]["mac"] == {
        "physical": 4, "logical": 16, "max_degree": 4}
    plain, gentry = _emit("gemm", n=4)
    ph = sharing_summary(plain, entry=gentry)
    assert ph["absorbed"] == 0
    assert ph["per_module"]["mac"]["physical"] == 16
    # the shared emission needs 4x fewer multipliers
    assert report_design(shared, entry=entry).dsp * 4 == \
        report_design(plain, entry=gentry).dsp


@pytest.mark.slow
def test_gemm_shared_16x_reduction_at_full_size():
    """Acceptance: hierarchical gemm at n=16 cuts physical macs >= 4x on the
    analysis-proven schedule (it achieves exactly 16x)."""
    mods, entry = _emit("gemm_shared", n=16)
    sh = sharing_summary(mods, entry=entry)
    assert sh["per_module"]["mac"]["logical"] == 256
    assert sh["per_module"]["mac"]["physical"] == 16
    assert sh["per_module"]["mac"]["logical"] >= \
        4 * sh["per_module"]["mac"]["physical"]
    assert report_design(mods, entry=entry).dsp == 48


def test_shared_netlist_records_degree_and_printers_annotate():
    mods, entry = _emit("gemm_shared", n=4)
    nl = mods[entry].netlist
    assert sorted(nl.shared) == [("mac", 4)] * 4
    for backend, mark in (("verilog", "//"), ("systemverilog", "//"),
                          ("vhdl", "--"), ("circt", "//")):
        bm, be = _emit("gemm_shared", n=4) if backend != "verilog" else \
            (mods, entry)
        if backend != "verilog":
            gal = GALLERY["gemm_shared"]
            m, be = gal.build(4)
            PassManager.from_spec(DEFAULT_PIPELINE_SPEC).run(m)
            bm = generate_verilog(m, entry=be, hierarchy="modules",
                                  backend=backend)
        text = bm[be].text
        assert f"{mark} time-shared x4" in text, backend


def test_shared_design_simulates_bit_for_bit():
    gal = GALLERY["gemm_shared"]
    mod, entry = gal.build(4)
    batch = rsim.stack_stimulus(gal.make_inputs, 32, base_seed=11, n=4)
    rep = rsim.run_differential(mod, entry, batch, kernel="gemm_shared",
                                hierarchy="modules", oracle=gal.oracle,
                                oracle_nargs=2)
    assert rep.ok, rep.mismatches[:5]
    assert rep.oracle_ok is True
    assert rep.passes_ok.get("rtl-share-instances") is True
    assert rep.passes_ok.get("rtl-arbitrate") is True


def test_proven_asserts_are_discharged():
    """rtl-arbitrate deletes PortConflictAsserts whose enables have finite
    pairwise-disjoint pulse sets (stencil1d's shift-register writes)."""
    from repro.core.codegen.rtl import PortConflictAssert
    before, entry = _emit("stencil1d",
                          rtl_spec="rtl-merge-ctrl,rtl-share-comb,"
                                   "rtl-share-mem,rtl-merge-srl,rtl-dce",
                          n=8)
    after, _ = _emit("stencil1d", n=8)
    n_before = sum(isinstance(it, PortConflictAssert)
                   for it in before[entry].rtl.items)
    n_after = sum(isinstance(it, PortConflictAssert)
                  for it in after[entry].rtl.items)
    assert n_before > 0 and n_after < n_before


# ---------------------------------------------------------------------------
# PortConflictAssert under the vectorized simulator
# ---------------------------------------------------------------------------


def _colliding_build():
    """Two writers hit the same output bank in the same cycle — the §4.5
    condition the static analysis cannot discharge away (same literal
    schedule), so the emitted PortConflictAssert must fire every lane."""
    b = Builder(ir.Module("collide"))
    wmem = ir.MemrefType((4,), ir.i32, ir.PORT_W)
    with b.func("collide", [ir.i32, wmem], ["x", "Out"]) as f:
        x, out = f.args
        b.write(x, out, [0], at=f.t + 1)
        b.write(x, out, [1], at=f.t + 1)
        b.ret()
    return b.module, "collide"


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_colliding_schedule_surfaces_conflict_lanes(backend):
    mod, entry = _colliding_build()
    sim, _prepared = rsim.simulator_for(mod, entry, backend=backend)
    batch = [np.arange(8, dtype=np.int64), np.zeros((8, 4), dtype=np.int64)]
    res = sim.run(batch, 6, batched=True, check_conflicts=False)
    assert res.backend == backend
    assert res.conflicts.shape == (8,)
    assert (res.conflicts >= 1).all(), res.conflicts   # every lane collides
    assert res.conflict_buses
    with pytest.raises(RTLSimError, match="port conflict"):
        sim.run(batch, 6, batched=True)


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_clean_schedule_has_no_conflicts(backend):
    gal = GALLERY["gemm_shared"]
    mod, entry = gal.build(4)
    sim, prepared = rsim.simulator_for(mod, entry, hierarchy="modules",
                                       backend=backend)
    lane = gal.make_inputs(4, seed=3)
    cycles = rsim.probe_cycles(prepared, entry, lane)
    batch = [np.asarray(a)[None].astype(np.int64) for a in lane]
    res = sim.run(batch, cycles, batched=True)
    assert not res.conflicts.any()


# ---------------------------------------------------------------------------
# DSE knob
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_dse_share_instances_knob_yields_dsp_tradeoff():
    from repro.core.hls import design_space, explore_design

    gal = GALLERY["gemm"]
    m, entry = gal.build(4)
    ins = gal.make_inputs(4)
    space = design_space(pipeline=(True,), unroll_parallel=(True, False),
                         share_instances=(False, True))
    res = explore_design(m, space, entry=entry,
                         inputs=[a.copy() for a in ins],
                         expected=gal.oracle(*ins[:2]))
    assert all(p.verified for p in res.points), \
        [p.error for p in res.points if not p.verified]
    shared = [p for p in res.points
              if p.config.share_instances and p.shared_absorbed > 0]
    assert shared, "no candidate actually time-multiplexed"
    spatial = min(p.dsp for p in res.points if p.shared_absorbed == 0)
    assert all(p.dsp < spatial for p in shared)
    # the tradeoff survives Pareto filtering (slower, but fewer DSPs)
    assert any(p.shared_absorbed > 0 for p in res.front)


# ---------------------------------------------------------------------------
# rtl-dce dangling-net audit
# ---------------------------------------------------------------------------


def test_audit_passes_on_clean_designs(monkeypatch):
    monkeypatch.setenv("REPRO_RTL_AUDIT", "1")
    for name, kw in (("gemm_shared", {"n": 4}), ("stencil1d", {"n": 8})):
        for hierarchy in ("inline", "modules"):
            _emit(name, hierarchy=hierarchy, **kw)


def test_audit_flags_dangling_net(monkeypatch):
    from repro.core.codegen.rtl import (CombAssign, DeadNetElim, Ref,
                                        RTLModule)

    monkeypatch.setenv("REPRO_RTL_AUDIT", "1")
    m = RTLModule("dangle")
    m.add_port("t_start", "input", 1)
    m.add_port("result_0", "output", 8)
    m.new_net("ghost", 8)   # read below but never driven
    m.add(CombAssign("result_0", Ref("ghost")))
    with pytest.raises(AssertionError, match="ghost"):
        DeadNetElim().run_module(m)
    monkeypatch.setenv("REPRO_RTL_AUDIT", "0")
    DeadNetElim().run_module(m)   # audit off: legacy behavior preserved
