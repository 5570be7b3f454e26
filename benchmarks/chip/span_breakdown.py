#!/usr/bin/env python3
"""Where one cell's time goes, by the program's own spans.

    python benchmarks/chip/span_breakdown.py --workload <name> --seed <n> \\
        --seconds <s> [--out <file>]

Runs the cell as the benchmark does: set-up, whole units for ``--seconds``,
then the traffic's traced slice under the profiler, and the check against
the reference.  A recorder (``repro.core.trace.record``) is open around
set-up and around each unit.  Prints one JSON object (and writes it to
``--out``):

* ``setup``: set-up's phases, its span totals and counters;
* ``units``: each unit's record with ``spans`` (seconds and count per span
  name) and ``counters``;
* ``window``: the window's wall time, span totals and counters;
* ``trace``: the traced slice reduced as the benchmark reduces it, with
  every program span recorded in it kept beside the benchmark's host spans,
  so that idle gaps fall to the innermost ``hir.*`` span;
* ``correct``.

It is not a cell: the benchmark's runs never run it.  Exits 3 off the chip.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import harness  # noqa: E402
import xplane  # noqa: E402


def _summary(rec) -> dict:
    return {"spans": rec.totals(), "counters": dict(rec.counters)}


def traced_slice(cell, state, first: int) -> tuple[list[dict], dict]:
    """``harness.traced_slice``, keeping the program's spans too."""
    import jax

    from repro.core import trace

    log_dir = Path(tempfile.mkdtemp(prefix="span_breakdown_"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    unit = getattr(cell.runner, "traced_unit", None)
    units = []
    with trace.record() as rec:
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
                for k in range(int(cell.traffic["trace_units"])):
                    units.append(unit(state, first + k, k) if unit
                                 else cell.runner.unit(state, first + k))
        finally:
            jax.profiler.stop_trace()
    names = tuple(harness.HOST_SPANS) + tuple(rec.totals())
    try:
        reduced = xplane.reduce(xplane.load_xplane(log_dir, names), names,
                                top=20)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    reduced.update(_summary(rec))
    return units, reduced


def breakdown(layout, workload: str, seed: int, seconds: float,
              require_chip: bool = True, profile: bool = True) -> dict:
    from repro.core import trace

    cell = harness.load_cell(layout, workload, trace=False)
    harness.device_info(cell.chips, require_chip)
    harness.use_compile_cache(cell.traffic.get("compile_cache", True))
    with trace.record() as rec:
        state = cell.runner.setup(cell.config, cell.traffic, cell.reference,
                                  seed)
    setup = {"phases": state.phases, **_summary(rec)}
    units: list[dict] = []
    t0 = time.perf_counter()
    with trace.record() as window:
        while True:
            with trace.record() as one:
                units.append(cell.runner.unit(state, len(units)))
            units[-1].update(_summary(one))
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    extra, reduced = (traced_slice(cell, state, len(units)) if profile
                      else ([], None))
    attempted, failed, checks = cell.runner.check(state, units + extra)
    return {"workload": workload, "seed": seed,
            "correct": failed == 0 and attempted > 0, "checks": checks,
            "setup": setup, "units": units,
            "window": {"window_s": window_s, **_summary(window)},
            "trace": reduced}


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out")
    a = p.parse_args(argv)
    try:
        out = breakdown(harness.Layout(), a.workload, a.seed, a.seconds)
    except harness.BenchError as e:
        print(f"span_breakdown: {e}", file=sys.stderr)
        return 3
    text = json.dumps(out)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
