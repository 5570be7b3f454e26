"""Fixtures for the on-chip benchmark's CPU tests.

The harness (``benchmarks/chip``) is put on ``sys.path``.  ``tiny_layout``
gives a copy of ``BENCHMARK.json`` in a temporary directory whose
configurations are cut to CPU test size and whose DSE traffic lists two
variants; the harness finds those files before the real ones.  Runs skip
the look for a chip, and leave JAX's compilation cache settings alone.
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

#: test sizes: build arguments and argument shapes
TINY = {
    "gemm16": ({"n": 4}, {"A": [4, 4], "B": [4, 4], "C": [4, 4]}),
    "conv2d128x64": ({"h": 8, "w": 8}, {"Img": [8, 8], "Out": [6, 6]}),
    "conv2d16x64": ({"h": 6, "w": 8}, {"Img": [6, 8], "Out": [4, 6]}),
}
SEED = 2**31 + 7


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    # JAX fixes its cache directory at the first compile of the process
    monkeypatch.setattr(harness, "use_compile_cache",
                        lambda enabled: None)


def write_json(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


@pytest.fixture
def tiny_layout(tmp_path) -> harness.Layout:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        build, shapes = TINY[c["name"]]
        cfg["build"] = build
        for arg in cfg["inputs"]:
            arg["shape"] = shapes[arg["name"]]
        c["file"] = f"configs/{c['name']}.json"
        write_json(tmp_path / c["file"], cfg)
    dse = json.loads((BENCH / "traffic" / "conv2d16x64_variants.json")
                     .read_text())
    dse["variants"] = dse["variants"][:2]
    dse["lanes"] = 16
    write_json(tmp_path / "traffic" / "conv2d16x64_variants.json", dse)
    write_json(tmp_path / "BENCHMARK.json", spec)
    return harness.Layout(root=tmp_path, search=[tmp_path, BENCH])


@pytest.fixture
def run_cell():
    """``run_cell(layout, workload, seconds=0.0, seed=SEED, trace=False)``
    off the chip."""

    def run(layout, workload, seconds=0.0, seed=SEED, trace=False):
        return harness.run_cell(layout, workload, seed, seconds, trace,
                                time.perf_counter(), require_chip=False)

    return run
