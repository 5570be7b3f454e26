"""The on-chip benchmark's harness: one cell, one run, one result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(a design of the compiler's gallery at a stated size, with its stimulus
domain and its plain reference) under a traffic mix (which runner runs it,
and with what parameters).  Everything that belongs to one configuration,
traffic mix or metric sits in a file of its own, found by name:

* ``configs/<config>.json``    the path named by the ``configs`` entry;
* ``traffic/<traffic>.json``   the mix; its ``runner`` key names
* ``runners/<runner>.py``      the general code that runs that kind of mix;
* ``reference/<name>.py``      the plain reference a configuration names;
* ``metrics/<metric>.py``      one reader per metric of ``BENCHMARK.json``.

A run builds the runner's state (set-up), runs whole units (batches or
designs) until ``seconds`` have passed (the window), optionally traces a
short slice of further units under the profiler, reads the device's memory
peak, and only then checks every unit against the reference.  Readers turn
the run's record into metrics; a reader that finds nothing returns None and
its metric is left out.
"""

from __future__ import annotations

import importlib.util
import json
import math
import re
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

HERE = Path(__file__).resolve().parent
#: the checkout: ``BENCHMARK.json`` and ``src/`` live here
ROOT = HERE.parents[1]
PEAKS = HERE / "peaks.json"

#: host spans that idle gaps of the device are attributed to
HOST_SPANS = ("bench.stimulus", "sim.run", "bench.schedule",
              "run_differential")


class BenchError(RuntimeError):
    """The benchmark cannot run as specified (missing file, no chip, ...)."""


def load_source(path: Path):
    """Import a Python file by path (metric names contain dots)."""
    rel = path.relative_to(path.parents[1])
    name = "bench_" + re.sub(r"\W", "_", str(rel))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise BenchError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Layout:
    """Where the benchmark's files are.  ``root`` holds ``BENCHMARK.json``;
    ``search`` lists directories that hold ``traffic/``, ``runners/``,
    ``reference/`` and ``metrics/``, searched in order."""

    root: Path = ROOT
    search: list[Path] = field(default_factory=lambda: [HERE])

    def find(self, kind: str, name: str, suffix: str) -> Path:
        for d in self.search:
            p = d / kind / f"{name}{suffix}"
            if p.is_file():
                return p
        raise BenchError(f"no {kind}/{name}{suffix} under "
                         f"{[str(d) for d in self.search]}")

    def spec(self) -> dict:
        with open(self.root / "BENCHMARK.json") as f:
            return json.load(f)


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    runner: Any
    reference: Any
    metrics: list[dict]


def load_cell(layout: Layout, workload: str, trace: bool) -> Cell:
    spec = layout.spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; "
                         f"have {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(layout.root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(layout.find("traffic", w["traffic"], ".json")) as f:
        traffic = json.load(f)
    runner = load_source(layout.find("runners", traffic["runner"], ".py"))
    reference = load_source(layout.find("reference", config["reference"],
                                        ".py"))
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in spec[kind]
               if "workloads" not in m or workload in m["workloads"]]
    for m in metrics:
        m["reader"] = load_source(layout.find("metrics", m["name"], ".py"))
    return Cell(workload, int(w["chips"]), config, traffic, runner,
                reference, metrics)


@dataclass
class Run:
    """What one run measured; the metric readers take their numbers from it.

    ``units`` holds one record per unit of the window (a batch or a
    design): the runner's timings and counts.  ``trace`` is the reduction
    of the profiled slice (``xplane.reduce``) in a ``--trace 1`` run."""

    runner: str
    setup_s: float
    window_s: float
    units: list[dict]
    trace: Optional[dict] = None


def device_info(chips: int, require_chip: bool = True) -> dict:
    """Platform, kind and count of JAX's devices; with ``require_chip`` the
    run refuses a host without an accelerator, with fewer chips than the
    cell asks for, or whose ``device_kind`` has no row in ``peaks.json``."""
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if require_chip:
        if d.platform == "cpu":
            raise BenchError("JAX found no accelerator (platform cpu)")
        if len(devs) < chips:
            raise BenchError(f"cell needs {chips} chips, JAX has {len(devs)}")
        with open(PEAKS) as f:
            peaks = json.load(f)["devices"]
        if d.device_kind not in peaks:
            raise BenchError(f"device kind {d.device_kind!r} is not in "
                             f"{PEAKS.name}")
    return info


def memory_peak_bytes(chips: int) -> Optional[int]:
    """Peak bytes in use on the fullest of the cell's chips, where the
    backend reports it."""
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache(enabled: bool) -> None:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.core.compile_cache``: ``JAX_COMPILATION_CACHE_DIR`` if set,
    else ``.jax_cache/`` in the checkout), for every program however quick
    to compile; or off."""
    import jax

    if not enabled:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    from repro.core.compile_cache import use_compile_cache as program_cache

    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def traced_slice(cell: Cell, state, first: int) -> tuple[list[dict], dict]:
    """Run ``trace_units`` more units under the profiler (Python tracer
    off), then reduce the trace, which is written to a temporary directory
    and deleted."""
    import jax

    import xplane

    log_dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    units = []
    # a runner whose window units differ in cost traces fixed ones
    unit = getattr(cell.runner, "traced_unit", None)
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            for k in range(int(cell.traffic["trace_units"])):
                units.append(unit(state, first + k, k) if unit
                             else cell.runner.unit(state, first + k))
    finally:
        jax.profiler.stop_trace()
    try:
        summary = xplane.reduce(xplane.load_xplane(log_dir, HOST_SPANS),
                                HOST_SPANS)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return units, summary


def run_cell(layout: Layout, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, require_chip: bool = True) -> dict:
    """One run of one cell; returns the result object (the last line),
    with the set-up's phases under ``setup_phases`` (seconds each) and the
    window's per-unit records under ``units`` (timings and counts);
    ``main`` prints both to standard error."""
    cell = load_cell(layout, workload, trace)
    device = device_info(cell.chips, require_chip)
    use_compile_cache(cell.traffic.get("compile_cache", True))
    # imports, reading the cell, JAX's start and the device's
    phases = {"init_s": time.perf_counter() - t_start}

    state = cell.runner.setup(cell.config, cell.traffic, cell.reference,
                              seed)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    phases.update(state.phases)
    units: list[dict] = []
    t = t0
    while True:
        units.append(cell.runner.unit(state, len(units)))
        units[-1]["wall_s"], t = time.perf_counter() - t, time.perf_counter()
        if t - t0 >= seconds:
            break
    window_s = t - t0
    extra: list[dict] = []
    summary = None
    if trace:
        extra, summary = traced_slice(cell, state, len(units))
    peak = memory_peak_bytes(cell.chips)
    attempted, failed, checks = cell.runner.check(state, units + extra)
    correct = (failed == 0 and attempted > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))

    run = Run(cell.traffic["runner"], setup_s, window_s, units, summary)
    metrics = {}
    for m in cell.metrics:
        v = m["reader"].read(run)
        if v is not None:
            if not math.isfinite(v):
                raise BenchError(f"metric {m['name']} read {v}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["setup_phases"] = phases
    result["units"] = units
    result["checks"] = checks
    return result


def main(argv: list[str], t_start: float) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = p.parse_args(argv)
    try:
        result = run_cell(Layout(), a.workload, a.seed, a.seconds,
                          bool(a.trace), t_start)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    print("setup: " + json.dumps(result.pop("setup_phases")),
          file=sys.stderr)
    print("units: " + json.dumps(result.pop("units")), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0
