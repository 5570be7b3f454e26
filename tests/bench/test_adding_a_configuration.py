"""A configuration and its cell join the benchmark as new files and entries
only, and get every check that the existing cells get.

On a copy of the real benchmark, the gallery's histogram is added: a
configuration file that gives its CPU test size (16 pixels, 4 bins), a
plain reference whose ``control`` drops each update to the bin that the
pixel before it updated (an II=1 schedule without forwarding reads the bin
before the last write lands), a workload on the existing ``bulk_512``
traffic, and the cell appended to ``verify_vcps``'s ``workloads``.  No file
the benchmark has is edited.  The copy is then cut to CPU test size by the
same ``tiny_copy`` as the real cells, and the cell runs through the
correctness check, the planted faults and the control of ``cell_checks``.
"""

import json
import shutil

import numpy as np
import pytest
from cell_checks import FAULTS, check_correct, check_not_correct
from conftest import ROOT, tiny_copy, write_json

import control
import harness

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIG = "histogram_example"
CELL = "histogram_example.bulk"

HISTOGRAM = {
    "name": CONFIG,
    "design": "histogram",
    "build": {"n": 256, "bins": 4},
    "hierarchy": "inline",
    "inputs": [{"name": "Img", "shape": [256], "low": 0, "high": 4},
               {"name": "Out", "shape": [4], "fill": 0}],
    "output_arg": 1,
    "datapath_bits": 32,
    "reference": "histogram_example",
    "guarantees": "every lane's Out counts the pixels of its Img in each bin",
    "cpu_test_size": {"build": {"n": 16, "bins": 4},
                      "shapes": {"Img": [16], "Out": [4]}},
}

REFERENCE = '''"""Plain reference of the histogram: pixels per bin per lane."""

import numpy as np


def _count(img, bins, weight):
    out = np.zeros((img.shape[0], bins), dtype=np.int64)
    np.add.at(out, (np.arange(img.shape[0])[:, None], img), weight)
    return out


def reference(config, args, dtype=np.int64):
    (img,) = args
    return _count(img, config["build"]["bins"], 1).astype(dtype)


def control(config, args):
    """An update to the bin that the pixel before it updated is lost."""
    (img,) = args
    kept = np.ones(img.shape, dtype=np.int64)
    kept[:, 1:] = img[:, 1:] != img[:, :-1]
    return _count(img, config["build"]["bins"], kept)
'''


def _add_histogram(src):
    """A copy of the real checkout's benchmark in ``src``, with the
    histogram added as new files and new entries."""
    src.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", src)
    shutil.copytree(ROOT / "benchmarks" / "chip" / "configs",
                    src / "benchmarks" / "chip" / "configs")
    write_json(src / "benchmarks" / "chip" / "configs" / f"{CONFIG}.json",
               HISTOGRAM)
    (src / "benchmarks" / "chip" / "reference").mkdir()
    (src / "benchmarks" / "chip" / "reference"
     / "histogram_example.py").write_text(REFERENCE)
    spec = json.loads((src / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": CONFIG, "source": "https://arxiv.org/abs/2103.00194",
        "file": f"benchmarks/chip/configs/{CONFIG}.json", "reduced": [],
        "why": "data-dependent bin addresses: per-lane gathers and scatters"})
    spec["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "bulk_512", "chips": 1,
        "why": "closed loop of 512-lane batches through the per-lane path"})
    vcps = {m["name"]: m for m in spec["end_to_end"]}["verify_vcps"]
    vcps["workloads"].append(CELL)
    write_json(src / "BENCHMARK.json", spec)
    return spec


@pytest.fixture
def added(tmp_path):
    """``(layout, spec)``: the benchmark with the histogram added, at CPU
    test size."""
    src = tmp_path / "src"
    spec = _add_histogram(src)
    layout = tiny_copy(src, tmp_path / "tiny")
    return layout, spec


def test_added_cell_is_correct(added, run_cell):
    layout, spec = added
    r = run_cell(layout, CELL)
    check_correct(r, spec, CELL)
    assert set(r["metrics"]) == {"verify_vcps", "setup_s"}
    cell = harness.load_cell(layout, CELL, trace=False)
    assert cell.config["build"] == {"n": 16, "bins": 4}


@pytest.mark.parametrize("fault", FAULTS)
def test_added_cell_fault_is_not_correct(added, run_cell, monkeypatch,
                                         fault):
    fault(monkeypatch)
    check_not_correct(run_cell(added[0], CELL))


def test_added_cell_control_is_not_correct(added):
    for r in control.run_control(added[0], CELL, [1, 2], 0.0,
                                 require_chip=False):
        check_not_correct(r)


def test_int16_control_cannot_fail_the_histogram(added):
    """Why the histogram brings a control of its own: its counts never
    reach 2^15, so int16 gives the exact answer."""
    cell = harness.load_cell(added[0], CELL, trace=False)
    img = np.random.default_rng(3).integers(0, 4, size=(64, 16))
    exact = cell.reference.reference(cell.config, [img])
    np.testing.assert_array_equal(
        cell.reference.reference(cell.config, [img], dtype=np.int16), exact)
    assert (cell.reference.control(cell.config, [img]) != exact).any(
        axis=1).mean() > 0.9


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_every_configuration_gives_its_cpu_test_size(name):
    entry = {c["name"]: c for c in SPEC["configs"]}[name]
    config = json.loads((ROOT / entry["file"]).read_text())
    size = config["cpu_test_size"]
    assert set(size["build"]) == set(config["build"])
    assert set(size["shapes"]) == {arg["name"] for arg in config["inputs"]}


def test_a_configuration_without_its_cpu_test_size_is_named(tmp_path):
    src = tmp_path / "src"
    _add_histogram(src)
    path = src / "benchmarks" / "chip" / "configs" / f"{CONFIG}.json"
    config = json.loads(path.read_text())
    del config["cpu_test_size"]
    write_json(path, config)
    with pytest.raises(ValueError, match=f"'{CONFIG}'.*cpu_test_size"):
        tiny_copy(src, tmp_path / "tiny")
