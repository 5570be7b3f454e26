#!/usr/bin/env python3
"""One batch of a bulk configuration at several lane counts, in one process.

    python benchmarks/chip/lane_sweep.py [--config gemm16] \\
        [--lanes 1024 4096 16384] [--seed 1]

Sizes a wider bulk cell; it is not a cell itself.  For each lane count the
configuration's simulator compiles its scan (or loads it from the cache),
runs one batch of zeros to warm up, then one batch of seeded stimulus
(timed), and every lane is checked against the
plain reference.  Prints one JSON line per lane count: cycles, ``compile_s``
and ``run_s`` (the simulator's own), the batch's wall time, vector-cycles
per second over ``run_s`` and over the wall time, and the device's memory
peak so far.  Exits 3 off the chip.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import harness  # noqa: E402


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", default="gemm16")
    p.add_argument("--lanes", type=int, nargs="+",
                   default=[1024, 4096, 16384])
    p.add_argument("--seed", type=int, default=1)
    a = p.parse_args(argv)
    try:
        harness.device_info(1)
    except harness.BenchError as e:
        print(f"lane_sweep: {e}", file=sys.stderr)
        return 3
    harness.use_compile_cache(True)

    import numpy as np

    import designs
    import stimulus
    from compare import mismatched_lanes
    from repro.core.codegen.sim import probe_cycles, simulator_for

    layout = harness.Layout()
    entry = {c["name"]: c for c in layout.spec()["configs"]}[a.config]
    with open(layout.root / entry["file"]) as f:
        config = json.load(f)
    reference = harness.load_source(
        layout.find("reference", config["reference"], ".py"))
    module, name = designs.build(config)
    sim, prepared = simulator_for(module, name,
                                  hierarchy=config["hierarchy"])
    one = stimulus.batch(config["inputs"], 1, a.seed, 0)
    cycles = probe_cycles(prepared, name, [np.zeros_like(x[0]) for x in one])
    for lanes in a.lanes:
        args = stimulus.batch(config["inputs"], lanes, a.seed, lanes)
        warm = sim.run([np.zeros_like(x) for x in args], cycles,
                       batched=True)
        t0 = time.perf_counter()
        res = sim.run(args, cycles, batched=True)
        wall = time.perf_counter() - t0
        want = reference.reference(config,
                                   stimulus.domain_args(config["inputs"],
                                                        args))
        bad = mismatched_lanes(res.arrays[config["output_arg"]], want)
        print(json.dumps({
            "config": a.config, "lanes": lanes, "cycles": cycles,
            "compile_s": warm.compile_s, "run_s": res.run_s,
            "wall_s": wall, "vcps_over_run_s": lanes * cycles / res.run_s,
            "vcps_over_wall": lanes * cycles / wall,
            "mismatched_lanes": bad,
            "memory_peak_bytes": harness.memory_peak_bytes(1)}), flush=True)
        if bad:
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
