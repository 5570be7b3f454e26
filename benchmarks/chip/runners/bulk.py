"""Bulk verification traffic: one design, a stream of fresh stimulus batches.

Set-up builds the configuration's design (``designs.build``), emits it and
builds its batched simulator once (``simulator_for``), takes the cycle
budget from one event-driven probe (``probe_cycles``), and runs one batch
of the window's
shape so the scan is compiled, or loaded from the persistent cache, before
the window.  Each unit of the window draws ``lanes`` fresh stimulus vectors
and runs them through ``RTLSimulator.run(..., batched=True)``: stimulus to
the device, the scan, final state back, ``_collect``.  The output memref of
every lane is kept, and after the window every lane of every batch is
compared with the configuration's plain reference.

Traffic keys: ``lanes`` (vectors per batch), ``trace_units`` (batches run
under the profiler in a ``--trace 1`` run).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np

import designs
import stimulus
from compare import mismatched_lanes
from repro.core.codegen.sim import probe_cycles, simulator_for


@dataclass
class State:
    config: dict
    traffic: dict
    reference: object
    seed: int
    sim: object
    cycles: int
    phases: dict
    outputs: list = field(default_factory=list)


def setup(config: dict, traffic: dict, reference, seed: int) -> State:
    """Build, emit and probe the design and warm its scan up; ``phases``
    times each step (the warm-up's own ``compile_s`` is the trace, lower
    and compile or cache load of the scan, its ``run_s`` one batch)."""
    phases = {}
    t = time.perf_counter()
    module, entry = designs.build(config)
    sim, prepared = simulator_for(module, entry,
                                  hierarchy=config["hierarchy"])
    phases["build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    shape_args = stimulus.batch(config["inputs"], int(traffic["lanes"]),
                                seed, 0)
    zeros = [np.zeros_like(a) for a in shape_args]
    cycles = probe_cycles(prepared, entry, [a[0] for a in zeros])
    phases["probe_s"] = time.perf_counter() - t
    t = time.perf_counter()
    res = sim.run(zeros, cycles, batched=True)
    phases.update(warmup_s=time.perf_counter() - t,
                  warmup_compile_s=res.compile_s, warmup_run_s=res.run_s)
    return State(config, traffic, reference, seed, sim, cycles, phases)


def unit(state: State, index: int) -> dict:
    lanes = int(state.traffic["lanes"])
    with jax.profiler.TraceAnnotation("bench.stimulus"):
        args = stimulus.batch(state.config["inputs"], lanes, state.seed,
                              index)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("sim.run"):
        res = state.sim.run(args, state.cycles, batched=True)
    wall = time.perf_counter() - t0
    state.outputs.append(res.arrays[state.config["output_arg"]])
    return {"lanes": lanes, "cycles": state.cycles, "run_s": res.run_s,
            "compile_s": res.compile_s, "sim_wall_s": wall}


def check(state: State, units: list[dict]) -> tuple[int, int, dict]:
    """Every lane of every batch against the reference; lanes count."""
    inputs = state.config["inputs"]
    attempted = failed = 0
    for index, (u, got) in enumerate(zip(units, state.outputs)):
        args = stimulus.batch(inputs, u["lanes"], state.seed, index)
        want = state.reference.reference(
            state.config, stimulus.domain_args(inputs, args))
        failed += mismatched_lanes(got, want)
        attempted += u["lanes"]
    return attempted, failed, {
        "mismatched_lanes": {"value": failed, "limit": 0}}
