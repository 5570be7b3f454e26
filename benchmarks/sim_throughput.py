"""Vectorized RTL-simulator throughput vs the per-vector event-driven
oracle (acceptance gate: >= 100x test-vectors/sec on gemm).

For each kernel the same netlist is executed two ways over a random
stimulus batch:

  * ``lower.simulate_batch`` — the event-driven HIR interpreter, one full
    simulation per stimulus vector (the verification path before this
    benchmark's subject existed);
  * ``codegen.sim.RTLSimulator`` — the batched cycle-accurate interpreter
    (jax scan+vmap on JAX's default device), timed after a warm-up run so
    jit compilation is amortized the way a fuzzing loop amortizes it.

Writes ``artifacts/bench/BENCH_sim_throughput.json`` and exits nonzero (via
``RuntimeError`` -> ``benchmarks/run.py``) when the speed-up floor is
missed.  ``--quick`` shrinks the batch for CI smoke runs.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

ARTIFACTS = Path(__file__).resolve().parents[1] / "artifacts" / "bench"
OUT = ARTIFACTS / "BENCH_sim_throughput.json"

#: kernels measured: (build kwargs, make_inputs kwargs); gemm carries the
#: acceptance floor, the others are informational
KERNELS = {
    "gemm": ({"n": 16}, {"n": 16}),
    "stencil1d": ({"n": 16}, {"n": 16}),
    "array_add": ({"n": 16}, {"n": 16}),
}
FLOOR_KERNEL = "gemm"
#: full runs must clear 100x (the paper-repro acceptance gate); ``--quick``
#: smoke runs use a small batch that cannot amortize the per-cycle dispatch
#: cost, so they gate at a sandbagged floor that still catches order-of-
#: magnitude regressions
SPEEDUP_FLOOR = 100.0
QUICK_FLOOR = 20.0


def _bench_kernel(name: str, batch_size: int, event_lanes: int) -> dict:
    from repro.core.codegen.sim import (probe_cycles, simulator_for,
                                        stack_stimulus)
    from repro.core.gallery import GALLERY
    from repro.core.lower import simulate_batch

    gal = GALLERY[name]
    bkw, ikw = KERNELS[name]
    mod, entry = gal.build(**bkw)
    batch = stack_stimulus(gal.make_inputs, batch_size, base_seed=1, **ikw)

    sim, prepared = simulator_for(mod, entry)
    cycles = probe_cycles(prepared, entry, [c[0] for c in batch])

    # warm-up compiles the jit scan (a fuzzing loop pays this once)
    res = sim.run(batch, cycles, batched=True)
    t0 = time.perf_counter()
    res = sim.run(batch, cycles, batched=True)
    vec_s = time.perf_counter() - t0
    vec_rate = batch_size / vec_s

    lanes = min(event_lanes, batch_size)
    ev_batch = [c[:lanes] for c in batch]
    t0 = time.perf_counter()
    _, finals = simulate_batch(prepared, entry, ev_batch)
    ev_s = time.perf_counter() - t0
    ev_rate = lanes / ev_s

    # the comparison is only meaningful if both paths computed the same thing
    ridx = len(batch) - 1
    if finals[ridx] is not None and not np.array_equal(
            np.asarray(res.arrays[ridx][:lanes]), finals[ridx]):
        raise RuntimeError(f"{name}: vectorized != event-driven result")

    return {"kernel": name, "backend": sim.backend, "cycles": cycles,
            "batch": batch_size, "event_lanes": lanes,
            "vectorized_s": round(vec_s, 6),
            "event_driven_s": round(ev_s, 6),
            "vectorized_vectors_per_s": round(vec_rate, 1),
            "event_vectors_per_s": round(ev_rate, 1),
            "speedup": round(vec_rate / ev_rate, 1)}


def main(argv=None, profile: bool = False) -> dict:
    argv = list(sys.argv[1:] if argv is None else argv)
    quick = "--quick" in argv
    batch_size = 1024 if not quick else 256
    event_lanes = 4 if not quick else 2
    floor = SPEEDUP_FLOOR if not quick else QUICK_FLOOR
    rows = []
    for name in KERNELS:
        r = _bench_kernel(name, batch_size, event_lanes)
        print(f"  {r['kernel']:<10} backend={r['backend']} "
              f"batch={r['batch']} cycles={r['cycles']} "
              f"vec={r['vectorized_vectors_per_s']:.0f}/s "
              f"event={r['event_vectors_per_s']:.0f}/s "
              f"speedup={r['speedup']:.0f}x")
        rows.append(r)
    out = {"floor_kernel": FLOOR_KERNEL, "speedup_floor": floor,
           "quick": quick, "rows": rows}
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(out, indent=2))
    print(f"  wrote {OUT}")
    floor_row = next(r for r in rows if r["kernel"] == FLOOR_KERNEL)
    if floor_row["speedup"] < floor:
        raise RuntimeError(
            f"sim throughput regression: {FLOOR_KERNEL} speedup "
            f"{floor_row['speedup']:.1f}x < floor {floor:.0f}x")
    return out


if __name__ == "__main__":
    from repro.core.compile_cache import use_compile_cache

    use_compile_cache()
    main()
