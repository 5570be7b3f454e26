"""The per-layer metrics that read the program's own spans
(``repro.core.trace``), at CPU test size, and the trace reduction's
attribution of idle time to a program span."""

import json

import pytest
from conftest import BENCH, ROOT

import harness
import program_spans
import xplane
from repro.core import trace

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
#: each cell's per-layer metrics that read the program's spans
SPAN_METRICS = {
    w["name"]: [m["name"] for m in SPEC["per_layer"]
                if m["source"] == "program_span"
                and ("workloads" not in m or w["name"] in m["workloads"])]
    for w in SPEC["workloads"]}
SPAN_METRICS = {w: names for w, names in SPAN_METRICS.items() if names}
ALL_SPAN_METRICS = {m for ms in SPAN_METRICS.values() for m in ms}
#: a residual metric's prefix, and the prefixes of the span metrics of
#: the same suffix that it holds, each with other work
PARTS = {"host_ms_per_batch": ["transfer_ms_per_batch", "pack_ms_per_batch"],
         "host_legs_s": ["codegen_s", "sim_build_s", "probe_s",
                         "event_lanes_s", "oracle_s"],
         "xla_compile_s": ["scan_lower_s"]}


@pytest.fixture(autouse=True)
def _stop_recording():
    program_spans.stop()
    yield
    program_spans.stop()


def test_every_span_metric_has_an_entry_and_a_reader():
    cells = {w["name"] for w in SPEC["workloads"]}
    assert SPAN_METRICS
    for m in SPEC["per_layer"]:
        if m["source"] == "program_span":
            assert set(m.get("workloads", cells)) <= cells
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def _slice_without_profiler(cell, state, first):
    """The traced slice's units, run as ``harness.traced_slice`` runs them
    but with no profiler (the CPU has no device plane to reduce)."""
    unit = getattr(cell.runner, "traced_unit", None)
    units = [unit(state, first + k, k) if unit
             else cell.runner.unit(state, first + k)
             for k in range(int(cell.traffic["trace_units"]))]
    return units, {"busy_s": 0.5, "window_s": 1.0, "device_ops": [],
                   "idle_gaps": []}


@pytest.mark.parametrize("workload", sorted(SPAN_METRICS))
def test_trace_run_reports_the_span_metrics(tiny_layout, run_cell,
                                            monkeypatch, workload):
    monkeypatch.setattr(harness, "traced_slice", _slice_without_profiler)
    r = run_cell(tiny_layout, workload, seconds=0.2, trace=True)
    assert r["correct"], r["checks"]
    got = {n: v["value"] for n, v in r["metrics"].items()}
    assert set(SPAN_METRICS[workload]) <= set(got)
    assert not (ALL_SPAN_METRICS - set(SPAN_METRICS[workload])) & set(got)
    assert all(got[n] > 0 for n in SPAN_METRICS[workload])
    # a residual holds its span-read parts (the lowering is a part of what
    # DiffReport.compile_s counts)
    for name, whole in got.items():
        prefix, _, suffix = name.partition(".")
        parts = [f"{p}.{suffix}" for p in PARTS.get(prefix, [])]
        if any(n in got for n in parts):
            assert sum(got[n] for n in parts) < whole


@pytest.mark.parametrize("workload", sorted(SPAN_METRICS))
def test_plain_run_reports_none_and_records_nothing(tiny_layout, run_cell,
                                                    workload):
    r = run_cell(tiny_layout, workload)
    assert r["correct"]
    assert not ALL_SPAN_METRICS & set(r["metrics"])
    assert program_spans.recorder() is None
    assert not trace._active


def test_bulk_window_units_compile_nothing(tiny_layout):
    """Set-up compiles the scan once; no unit of the window compiles."""
    cell = harness.load_cell(tiny_layout, "gemm16.bulk", trace=False)
    with trace.record() as setup:
        state = cell.runner.setup(cell.config, cell.traffic,
                                  cell.reference, 3)
    assert setup.counters["hir.sim.compiles"] == 1
    for k in range(3):
        with trace.record() as one:
            u = cell.runner.unit(state, k)
        assert one.counters.get("hir.sim.compiles", 0) == 0
        assert one.counters["hir.sim.leaves_in"] == \
            len(state.sim.state_shape) + 1
        scan = [(e - s) / 1e9 for n, _p, s, e in one.spans
                if n == "hir.sim.scan"]
        assert scan == [u["run_s"]]


def test_dse_designs_compile_once_each(tiny_layout):
    cell = harness.load_cell(tiny_layout, "conv2d16x64.dse", trace=False)
    with trace.record() as setup:
        state = cell.runner.setup(cell.config, cell.traffic,
                                  cell.reference, 3)
    assert setup.counters["hir.sim.compiles"] == len(cell.traffic["warmup"])
    with trace.record() as one:
        cell.runner.unit(state, 0)
    assert one.counters["hir.sim.compiles"] == 1
    assert one.totals()["hir.hls.schedule"]["n"] >= 1


def test_window_is_found_among_the_recorded_calls():
    """Set-up's and the traced slice's calls are left out; a run whose
    scans were not recorded reads None."""
    program_spans.start()
    rec = program_spans.recorder()
    for k, scan_ns in enumerate([700, 300, 500, 300, 900]):
        t = 10_000 * k
        rec.spans += [("hir.sim.put", "hir.sim.run", t + 10, t + 20),
                      ("hir.sim.scan", "hir.sim.run", t + 20,
                       t + 20 + scan_ns),
                      ("hir.sim.run", None, t, t + 2_000)]
    units = [{"run_s": 300e-9}, {"run_s": 500e-9}]
    run = harness.Run("bulk", 1.0, 1.0, units)
    calls = program_spans.unit_calls(run)
    assert [c[0][2] for c in calls] == [10_000, 20_000]
    assert program_spans.seconds_per_unit(run, "bulk", "hir.sim.put") == \
        pytest.approx(10e-9)
    assert program_spans.seconds_per_unit(run, "dse", "hir.sim.put") is None
    units[1]["run_s"] = 400e-9
    assert program_spans.unit_calls(run) is None
    program_spans.stop()
    assert program_spans.seconds_per_unit(run, "bulk", "hir.sim.put") is None


def test_idle_falls_to_the_innermost_program_span():
    """A hand-made trace: ``hir.sim.put`` nested in the runner's
    ``sim.run``; the idle time while it is open is put on it."""
    host = [["bench.window", 0, 1000], ["sim.run", 100, 800],
            ["hir.sim.put", 150, 250], ["hir.sim.scan", 400, 400]]
    ops = [["fusion.1", 420, 360]]
    t = {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}
    names = harness.HOST_SPANS + ("hir.sim.put", "hir.sim.scan")
    idle = dict(xplane.reduce(t, names)["idle_gaps"])
    ns = 1e-9
    assert idle == pytest.approx({
        "hir.sim.put": 250 * ns, "sim.run": 50 * ns + 100 * ns,
        "hir.sim.scan": 40 * ns, xplane.NO_SPAN: 200 * ns})


@pytest.mark.parametrize("workload", ["gemm16.bulk", "conv2d16x64.dse"])
def test_span_breakdown_off_the_chip(tiny_layout, workload):
    """The breakdown tool's records, without its profiled slice."""
    import span_breakdown

    out = span_breakdown.breakdown(tiny_layout, workload, 3, 0.0,
                                   require_chip=False, profile=False)
    json.dumps(out)
    assert out["correct"] and len(out["units"]) == 1
    unit = out["units"][0]
    if workload == "gemm16.bulk":
        assert out["setup"]["counters"]["hir.sim.compiles"] == 1
        assert unit["counters"].get("hir.sim.compiles", 0) == 0
        assert unit["spans"]["hir.sim.scan"]["s"] == unit["run_s"]
    else:
        assert unit["counters"]["hir.sim.compiles"] == 1
        assert unit["spans"]["hir.diff"]["n"] == 1
    assert out["window"]["spans"] == unit["spans"]
