"""The benchmark's cells at CPU test size, run through the harness.

The cells are those of ``BENCHMARK.json``.  Each run skips only the look
for a chip: set-up, the window, the check against the plain reference and
the metric readers are the benchmark's own.  Then the timed path is broken
underneath, once for each fault a one-chip verification cell can have
(``cell_checks.FAULTS``), and ``correct`` must read false; the control (the
configuration's wrong answer in the program's place) must too.  A dummy
configuration, traffic mix, reference and metric, added as new files and
entries only, are found and run.
"""

import json
import os
import subprocess
import sys

import pytest
from cell_checks import FAULTS, check_correct, check_not_correct
from conftest import BENCH, ROOT, write_json

import control
import harness

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_is_correct(tiny_layout, run_cell, workload):
    check_correct(run_cell(tiny_layout, workload), SPEC, workload)


def test_bulk_window_counts_whole_batches(tiny_layout, run_cell):
    r = run_cell(tiny_layout, "gemm16.bulk", seconds=0.5)
    assert r["correct"]
    assert r["attempted"] % 1024 == 0 and r["attempted"] >= 2048


def test_dse_runs_each_variant_and_refuses_a_short_list(tiny_layout,
                                                        run_cell):
    with pytest.raises(Exception, match="more than the 2 variants"):
        run_cell(tiny_layout, "conv2d16x64.dse", seconds=1e6)


def test_dse_traces_its_fixed_variants(tiny_layout, monkeypatch):
    """The traced slice takes ``trace_variants``, whatever number of
    variants the window took, and its designs are checked too."""
    import designs

    cell = harness.load_cell(tiny_layout, "conv2d16x64.dse", trace=False)
    built = []
    orig = designs.build

    def build(config, knobs=None):
        built.append(knobs)
        return orig(config, knobs)

    monkeypatch.setattr(designs, "build", build)
    state = cell.runner.setup(cell.config, cell.traffic, cell.reference, 5)
    units = [cell.runner.unit(state, 0),
             cell.runner.traced_unit(state, 1, 0)]
    assert built == cell.traffic["warmup"] + [
        cell.traffic["variants"][0], cell.traffic["trace_variants"][0]]
    attempted, failed, _ = cell.runner.check(state, units)
    assert (attempted, failed) == (2, 0)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", FAULTS)
def test_fault_in_timed_path_is_not_correct(tiny_layout, run_cell,
                                            monkeypatch, workload, fault):
    fault(monkeypatch)
    check_not_correct(run_cell(tiny_layout, workload))


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(tiny_layout, workload):
    results = control.run_control(tiny_layout, workload, [1, 2], 0.0,
                                  require_chip=False)
    for r in results:
        check_not_correct(r)


def test_new_files_and_entries_only(tiny_layout, run_cell):
    """A configuration, traffic mix, reference and metric added as files,
    with entries in BENCHMARK.json, are discovered and run."""
    d = tiny_layout.root
    write_json(d / "configs" / "dummy_add.json", {
        "design": "array_add", "build": {"n": 8}, "hierarchy": "inline",
        "inputs": [{"name": "a", "shape": [8], "low": -100, "high": 100},
                   {"name": "b", "shape": [8], "low": -100, "high": 100},
                   {"name": "c", "shape": [8], "fill": 0}],
        "output_arg": 2, "datapath_bits": 32, "reference": "dummy_sum"})
    (d / "reference").mkdir()
    (d / "reference" / "dummy_sum.py").write_text(
        "def reference(config, args, dtype=None):\n"
        "    return args[0] + args[1]\n")
    write_json(d / "traffic" / "dummy_mix.json",
               {"runner": "bulk", "lanes": 8, "trace_units": 1})
    (d / "metrics").mkdir()
    (d / "metrics" / "dummy_lanes.py").write_text(
        "def read(run):\n"
        "    return float(sum(u['lanes'] for u in run.units))\n")
    spec = json.loads((d / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy_add", "source": "test",
                            "file": "configs/dummy_add.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "dummy_add.mix", "config": "dummy_add",
                              "traffic": "dummy_mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "dummy_lanes", "unit": "lanes",
                               "better": "higher", "bound": 0.1,
                               "source": "host_clock",
                               "workloads": ["dummy_add.mix"]})
    write_json(d / "BENCHMARK.json", spec)
    r = run_cell(tiny_layout, "dummy_add.mix")
    assert r["correct"]
    assert r["metrics"]["dummy_lanes"]["value"] == r["attempted"] == 8
    assert "setup_s" in r["metrics"]


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v0 imaginary"

    monkeypatch.setattr(jax, "devices", lambda: [Fake()])
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.device_info(1)
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5 lite" in peaks["devices"] and "TPU v5e" in peaks["source"]


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "gemm16.bulk", "--seed", "3", "--seconds", "1", "--trace", "0",
         *args], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_cli_refuses_the_cpu_and_prints_no_result():
    r = _cli(ROOT)
    assert r.returncode == 3
    assert "no accelerator" in r.stderr
    assert r.stdout.strip() == ""


def test_cli_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path)
    assert r.returncode != 0
    assert "{" not in r.stdout
