#!/usr/bin/env python3
"""The control of the benchmark's check: runs that must come out not correct.

    python benchmarks/chip/control.py --workload <name> --seconds <s> \\
        --seeds <n> [<n> ...]

The control is the plain reference put in the program's place and computed
in int16, the nearest integer precision below the configurations' int32
datapath.  A configuration whose answers int16 cannot change (a histogram
never counts past 2^15) gets its wrong answer from its reference module's
own ``control(config, args)`` instead: the answer the design's realistic
fault would give.  The cell runs as usual; after each ``RTLSimulator.run``
of the timed path, the output memref it returns is replaced by the
control's answer for the same stimulus.  One JSON line per seed gives
``correct`` and each number compared with its limit; every line must read
``"correct": false``, and the smallest reading of each number is its upper
reading in ``PERF.md``.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import numpy as np  # noqa: E402

import harness  # noqa: E402
import stimulus  # noqa: E402

#: the precision the control computes in: one step below int32
CONTROL_DTYPE = np.int16


@contextlib.contextmanager
def reference_in_place(config: dict, reference, dtype=CONTROL_DTYPE):
    """Within the block, every batched ``RTLSimulator.run`` returns the
    control's answer as its output memref: ``reference.control``'s where
    the module defines it, else the reference's in ``dtype``."""
    from repro.core.codegen import sim as rsim

    def answer(domain):
        if hasattr(reference, "control"):
            return reference.control(config, domain)
        return reference.reference(config, domain, dtype=dtype)

    orig = rsim.RTLSimulator.run

    def run(self, args, cycles, batched=False, **kw):
        res = orig(self, args, cycles, batched=batched, **kw)
        domain = stimulus.domain_args(config["inputs"],
                                      [np.asarray(a) for a in args])
        res.arrays[config["output_arg"]] = answer(domain)
        return res

    rsim.RTLSimulator.run = run
    try:
        yield
    finally:
        rsim.RTLSimulator.run = orig


def run_control(layout: harness.Layout, workload: str, seeds: list[int],
                seconds: float, require_chip: bool = True) -> list[dict]:
    """One control run per seed; each result as ``run_cell`` gives it."""
    cell = harness.load_cell(layout, workload, trace=False)
    out = []
    for seed in seeds:
        with reference_in_place(cell.config, cell.reference):
            out.append(harness.run_cell(layout, workload, seed, seconds,
                                        False, time.perf_counter(),
                                        require_chip=require_chip))
    return out


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    a = p.parse_args(argv)
    try:
        results = run_control(harness.Layout(), a.workload, a.seeds,
                              a.seconds)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 3
    for seed, r in zip(a.seeds, results):
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "correct": r["correct"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          "checks": r["checks"]}), flush=True)
    return 1 if any(r["correct"] for r in results) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
