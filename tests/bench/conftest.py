"""Fixtures for the on-chip benchmark's CPU tests.

The harness (``benchmarks/chip``) is put on ``sys.path``.  ``tiny_layout``
gives a copy of ``BENCHMARK.json`` in a temporary directory whose
configurations are cut to the CPU test size each gives under
``cpu_test_size`` and whose DSE traffic lists two variants; the harness
finds those files before the real ones.  Runs skip the look for a chip,
and leave JAX's compilation cache settings alone.
"""

import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
ROOT = BENCH.parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SEED = 2**31 + 7


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    # JAX fixes its cache directory at the first compile of the process
    monkeypatch.setattr(harness, "use_compile_cache",
                        lambda enabled: None)


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def tiny_copy(root: Path, dest: Path) -> harness.Layout:
    """The benchmark of the checkout ``root`` at CPU test size, in ``dest``.

    Each configuration's build arguments and input shapes are replaced by
    its ``cpu_test_size`` (``{"build": {...}, "shapes": {arg: shape}}``),
    which no runner reads; every traffic mix of the ``dse`` runner keeps
    two variants on 16 lanes.  Files not cut are found under ``root``'s
    ``benchmarks/chip``, then the real one's."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((root / c["file"]).read_text())
        if "cpu_test_size" not in cfg:
            raise ValueError(f"configuration {c['name']!r} ({c['file']}) "
                             "gives no cpu_test_size")
        size = cfg["cpu_test_size"]
        cfg["build"] = size["build"]
        for arg in cfg["inputs"]:
            arg["shape"] = size["shapes"][arg["name"]]
        c["file"] = f"configs/{c['name']}.json"
        write_json(dest / c["file"], cfg)
    layout = harness.Layout(root=dest, search=list(dict.fromkeys(
        [dest, root / "benchmarks" / "chip", BENCH])))
    for w in spec["workloads"]:
        traffic = json.loads(layout.find("traffic", w["traffic"], ".json")
                             .read_text())
        if traffic["runner"] == "dse":
            traffic["variants"] = traffic["variants"][:2]
            traffic["lanes"] = 16
            write_json(dest / "traffic" / f"{w['traffic']}.json", traffic)
    write_json(dest / "BENCHMARK.json", spec)
    return layout


@pytest.fixture
def tiny_layout(tmp_path) -> harness.Layout:
    return tiny_copy(ROOT, tmp_path)


@pytest.fixture
def run_cell():
    """``run_cell(layout, workload, seconds=0.0, seed=SEED, trace=False)``
    off the chip."""

    def run(layout, workload, seconds=0.0, seed=SEED, trace=False):
        return harness.run_cell(layout, workload, seed, seconds, trace,
                                time.perf_counter(), require_chip=False)

    return run
