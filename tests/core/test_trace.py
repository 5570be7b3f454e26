"""Program spans and counters (``repro.core.trace``), and the spans the
simulator, ``run_differential``, the PassManager and codegen open."""

import numpy as np
import pytest

from repro.core import trace
from repro.core.codegen import sim as rsim
from repro.core.codegen.verilog import generate_verilog
from repro.core.gallery import GALLERY, gemm
from repro.core.passes import DEFAULT_PIPELINE_SPEC, PassManager

SIM_SPANS = ["hir.sim.run", "hir.sim.layout", "hir.sim.put",
             "hir.sim.lower", "hir.sim.compile", "hir.sim.scan",
             "hir.sim.fetch", "hir.sim.collect"]


def _inside(child, parent):
    return parent[2] <= child[2] <= child[3] <= parent[3]


def test_nothing_is_kept_without_a_recorder():
    with trace.span("hir.test.outside") as s:
        trace.count("hir.test.n", 3)
    assert s.seconds >= 0
    with trace.record() as rec:
        pass
    assert rec.spans == [] and rec.counters == {}


def test_record_starts_empty_and_keeps_its_block():
    with trace.record() as first:
        with trace.span("hir.test.a"):
            trace.count("hir.test.n")
    with trace.record() as rec:
        assert rec.spans == [] and rec.counters == {}
        with trace.span("hir.test.b"):
            pass
    assert [sp[0] for sp in first.spans] == ["hir.test.a"]
    assert [sp[0] for sp in rec.spans] == ["hir.test.b"]


def test_nesting_records_the_parent_and_children_fall_inside():
    with trace.record() as rec:
        with trace.span("hir.test.outer"):
            with trace.span("hir.test.inner") as inner:
                with trace.span("hir.test.leaf"):
                    pass
            with trace.span("hir.test.second"):
                pass
    by = {sp[0]: sp for sp in rec.spans}
    assert by["hir.test.outer"][1] is None
    assert by["hir.test.inner"][1] == "hir.test.outer"
    assert by["hir.test.leaf"][1] == "hir.test.inner"
    assert by["hir.test.second"][1] == "hir.test.outer"
    for name, parent, start, end in rec.spans:
        assert end >= start
        if parent is not None:
            assert _inside(by[name], by[parent])
    assert inner.seconds == (by["hir.test.inner"][3]
                             - by["hir.test.inner"][2]) / 1e9
    assert rec.spans[-1][0] == "hir.test.outer"  # closing order


def test_counters_add_and_totals_sum():
    with trace.record() as rec:
        trace.count("hir.test.n")
        trace.count("hir.test.n", 4)
        trace.count("hir.test.bytes", 10)
        for _ in range(3):
            with trace.span("hir.test.x"):
                pass
    assert rec.counters == {"hir.test.n": 5, "hir.test.bytes": 10}
    tot = rec.totals()["hir.test.x"]
    assert tot["n"] == 3
    assert tot["s"] == pytest.approx(
        sum((e - s) / 1e9 for _n, _p, s, e in rec.spans))


def test_nested_recorders_both_keep_and_an_exception_still_closes():
    with trace.record() as outer:
        with pytest.raises(ValueError):
            with trace.record() as inner:
                with trace.span("hir.test.fails"):
                    trace.count("hir.test.n")
                    raise ValueError("no")
        with trace.span("hir.test.after"):
            pass
    assert [sp[0] for sp in inner.spans] == ["hir.test.fails"]
    assert [sp[0] for sp in outer.spans] == ["hir.test.fails",
                                             "hir.test.after"]
    assert outer.spans[1][1] is None  # the failed span was closed
    assert inner.counters == outer.counters == {"hir.test.n": 1}


def test_span_as_decorator_opens_one_span_per_call():
    @trace.span("hir.test.fn")
    def fn(x):
        with trace.span("hir.test.body"):
            return x + 1

    with trace.record() as rec:
        assert fn(1) == 2 and fn(2) == 3
    assert rec.totals()["hir.test.fn"]["n"] == 2
    assert {sp[1] for sp in rec.spans if sp[0] == "hir.test.body"} == {
        "hir.test.fn"}


# -- the program's spans -----------------------------------------------------

@pytest.fixture(scope="module")
def gemm4():
    mod, entry = gemm.build(n=4)
    batch = rsim.stack_stimulus(gemm.make_inputs, 16, base_seed=3, n=4)
    return mod, entry, batch


def test_each_sim_span_once_per_batched_run(gemm4):
    mod, entry, batch = gemm4
    with trace.record() as build:
        sim, prepared = rsim.simulator_for(mod, entry)
        cycles = rsim.probe_cycles(prepared, entry,
                                   [np.asarray(a)[0] for a in batch])
    assert build.totals()["hir.sim.build"]["n"] == 1
    assert build.totals()["hir.sim.probe"]["n"] == 1
    assert build.totals()["hir.codegen"]["n"] == 1
    with trace.record() as first:
        res = sim.run(batch, cycles, batched=True)
    tot = first.totals()
    assert {n: tot[n]["n"] for n in SIM_SPANS} == dict.fromkeys(SIM_SPANS, 1)
    assert not {n for n in tot if n.startswith("hir.sim.")} - set(SIM_SPANS)
    by = {sp[0]: sp for sp in first.spans}
    for n in SIM_SPANS[1:]:
        assert by[n][1] == "hir.sim.run" and _inside(by[n], by["hir.sim.run"])
    assert (by["hir.sim.scan"][3] - by["hir.sim.scan"][2]) / 1e9 == res.run_s
    assert res.compile_s == pytest.approx(
        tot["hir.sim.lower"]["s"] + tot["hir.sim.compile"]["s"])
    c = first.counters
    assert c["hir.sim.compiles"] == 1
    assert c["hir.sim.leaves_in"] == len(sim.state_shape) + 1
    assert c["hir.sim.bytes_in"] == 8 * 16 * sum(
        int(np.prod(s)) for s in sim.state_shape.values()) + 8 * cycles
    assert c["hir.sim.leaves_out"] >= len(sim.state_shape)
    assert c["hir.sim.bytes_out"] > 0

    with trace.record() as again:
        res2 = sim.run(batch, cycles, batched=True)
    assert again.counters.get("hir.sim.compiles", 0) == 0
    assert res2.compile_s == 0.0
    assert "hir.sim.lower" not in again.totals()
    assert "hir.sim.compile" not in again.totals()
    assert again.totals()["hir.sim.put"]["n"] == 1
    for k in res.arrays:
        np.testing.assert_array_equal(res.arrays[k], res2.arrays[k])


def test_unbatched_and_numpy_runs_open_one_run_span(gemm4):
    mod, entry, batch = gemm4
    sim, prepared = rsim.simulator_for(mod, entry, backend="numpy")
    cycles = rsim.probe_cycles(prepared, entry,
                               [np.asarray(a)[0] for a in batch])
    with trace.record() as rec:
        res = sim.run([np.asarray(a)[0] for a in batch], cycles)
    tot = rec.totals()
    assert tot["hir.sim.run"]["n"] == tot["hir.sim.scan"]["n"] == 1
    assert "hir.sim.put" not in tot and not rec.counters
    assert res.run_s == tot["hir.sim.scan"]["s"]


@pytest.mark.parametrize("kernel,kw,rows,gathers", [
    ("histogram", {"n": 8, "bins": 4}, 4, 2), ("gemm", {"n": 4}, 19, 0)])
def test_each_jax_run_counts_its_port_paths_and_uniform_nets(kernel, kw,
                                                             rows, gathers):
    gal = GALLERY[kernel]
    mod, entry = gal.build(**kw)
    batch = rsim.stack_stimulus(gal.make_inputs, 4, **kw)
    sim, prepared = rsim.simulator_for(mod, entry)
    cycles = rsim.probe_cycles(prepared, entry,
                               [np.asarray(a)[0] for a in batch])
    uniform = sum(1 for n in sim.widths if n not in sim.varying)
    assert 0 < uniform < len(sim.widths)
    for _ in range(2):  # once per run, compiled or not
        with trace.record() as rec:
            sim.run(batch, cycles, batched=True)
        c = rec.counters
        assert (c["hir.sim.row_ports"], c["hir.sim.gather_ports"],
                c["hir.sim.uniform_nets"]) == (rows, gathers, uniform)


def test_run_differential_spans(gemm4):
    mod, entry, batch = gemm4
    with trace.record() as rec:
        rep = rsim.run_differential(mod, entry, batch, oracle=gemm.oracle,
                                    oracle_nargs=2, check_passes=False)
    assert rep.ok, rep.mismatches
    roots = [sp for sp in rec.spans if sp[1] is None]
    assert [sp[0] for sp in roots] == ["hir.diff"]
    diff = roots[0]
    by = {}
    for sp in rec.spans:
        by.setdefault(sp[0], []).append(sp)
    for n in ("hir.codegen", "hir.sim.build", "hir.sim.probe",
              "hir.sim.run", "hir.diff.event_lanes", "hir.diff.oracle"):
        assert len(by[n]) == 1, n
        assert _inside(by[n][0], diff)
    assert by["hir.diff.event_lanes"][0][1] == "hir.diff"
    assert by["hir.diff.oracle"][0][1] == "hir.diff"
    assert by["hir.sim.build"][0][1] == "hir.diff"
    assert "hir.diff.passes" not in by
    assert rec.counters["hir.sim.compiles"] == 1
    scan = by["hir.sim.scan"][0]
    assert (scan[3] - scan[2]) / 1e9 == rep.run_s


def test_run_differential_pass_checks_in_their_span(gemm4):
    mod, entry, batch = gemm4
    with trace.record() as rec:
        rep = rsim.run_differential(mod, entry, batch[:], check_passes=True,
                                    pass_lanes=4)
    assert rep.ok and rep.passes_ok
    passes = [sp for sp in rec.spans if sp[0] == "hir.diff.passes"]
    assert len(passes) == 1 and passes[0][1] == "hir.diff"
    # the per-pass replays run inside it, each a simulator run of its own
    inside = [sp for sp in rec.spans
              if sp[0] == "hir.sim.run" and _inside(sp, passes[0])]
    assert len(inside) == len(rep.passes_ok) + 1


def test_pass_statistics_and_codegen_timings_come_from_spans():
    mod, _ = gemm.build(n=4)
    pm = PassManager.from_spec(DEFAULT_PIPELINE_SPEC)
    with trace.record() as rec:
        pm.run(mod)
    tot = rec.totals()
    for st in pm.statistics:
        if st.invocations:
            assert tot[f"hir.pass.{st.name}"]["n"] >= st.invocations
    by_pass = {}
    for name, _p, s, e in rec.spans:
        by_pass[name] = by_pass.get(name, 0.0) + (e - s) / 1e9
    assert sum(st.wall_s for st in pm.statistics) == pytest.approx(
        sum(by_pass.values()))

    mod, entry = gemm.build(n=4)
    timings = {}
    with trace.record() as rec:
        generate_verilog(mod, entry, timings=timings)
    tot = rec.totals()
    assert tot["hir.codegen"]["n"] == 1
    assert timings["lower"]["wall_s"] == round(
        tot["hir.codegen.lower"]["s"], 6)
    assert timings["emit:verilog"]["wall_s"] == round(
        tot["hir.codegen.emit"]["s"], 6)
    assert set(timings["lower"]) == {"invocations", "rewrites", "wall_s"}
    for name, st in timings.items():
        if name not in ("lower", "emit:verilog"):
            assert tot[f"hir.pass.{name}"]["n"] == st["invocations"]
    root = [sp for sp in rec.spans if sp[0] == "hir.codegen"][0]
    assert all(_inside(sp, root) for sp in rec.spans)


def test_hls_schedule_opens_its_span():
    from repro.core.hls import erase_schedule, hls_schedule

    mod, _ = gemm.build(n=4)
    um = erase_schedule(mod)
    with trace.record() as rec:
        hls_schedule(um)
    assert [sp[0] for sp in rec.spans if sp[1] is None] == [
        "hir.hls.schedule"]
