"""Design-space-exploration traffic: a fixed, ordered stream of distinct
design variants, each taken from source to a verdict.

Each unit takes the next variant of the traffic's ``variants`` list (the
knobs of one ``DSEConfig``): the configuration's design is built, its
schedule erased, the variant's structural knobs applied and the design
scheduled again (``hls_schedule``); then ``run_differential`` emits it,
builds and compiles its simulator, runs ``lanes`` seeded stimulus vectors
on the device and checks them against the event-driven simulator and the
gallery's oracle (per-pass checks off).  The simulator's output memref is
kept, and after the window every lane of every design is compared with the
configuration's plain reference, and every verdict must be a pass.

Every run compiles the same designs; the seed changes only the stimulus.
The list opens with the variants a window holds, of like cost (cycle
counts within a third of each other), ordered dearer and cheaper in turn, so
that the mean over the designs of a window hardly depends on how many it
holds; the rest of the list is there for a faster program.
JAX's persistent compilation cache is off (traffic key
``compile_cache: false``): a new design is never in it.  Set-up takes the
``warmup`` designs (not in the list) through the whole path: the first few
compiles of a process run slower than the rest.  A list that
runs out before the window closes is an error.  A ``--trace 1`` run takes
the ``trace_variants`` after its window: the same designs in every run,
however many the window held.

Traffic keys: ``lanes``, ``variants``, ``warmup``, ``trace_variants`` and
``trace_units`` (how many of them are traced).
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field

import jax

import designs
import stimulus
from compare import mismatched_lanes
from repro.core.codegen import sim as rsim
from repro.core.gallery import GALLERY


class VariantsExhausted(RuntimeError):
    """The window outlasted the traffic's list of variants."""


@dataclass
class State:
    config: dict
    traffic: dict
    reference: object
    seed: int
    gallery: object
    phases: dict = field(default_factory=dict)
    outputs: list = field(default_factory=list)
    verdicts: list = field(default_factory=list)


@contextlib.contextmanager
def _captured_runs():
    """Keep the ``SimResult`` of every ``RTLSimulator.run`` in the block
    (``run_differential`` returns only its verdict), each under a
    ``sim.run`` host span."""
    got = []
    orig = rsim.RTLSimulator.run

    def run(self, *args, **kw):
        with jax.profiler.TraceAnnotation("sim.run"):
            res = orig(self, *args, **kw)
        got.append(res)
        return res

    rsim.RTLSimulator.run = run
    try:
        yield got
    finally:
        rsim.RTLSimulator.run = orig


def _design(state: State, knobs: dict, index: int) -> tuple[dict, object,
                                                            bool]:
    cfg = state.config
    with jax.profiler.TraceAnnotation("bench.schedule"):
        t0 = time.perf_counter()
        m, entry = designs.build(cfg, knobs)
        schedule_s = time.perf_counter() - t0
    lanes = int(state.traffic["lanes"])
    with jax.profiler.TraceAnnotation("bench.stimulus"):
        args = stimulus.batch(cfg["inputs"], lanes, state.seed, index)
    n_domain = len(stimulus.domain_args(cfg["inputs"], args))
    with _captured_runs() as runs, \
            jax.profiler.TraceAnnotation("run_differential"):
        t0 = time.perf_counter()
        rep = rsim.run_differential(
            m, entry, args, hierarchy=cfg["hierarchy"],
            oracle=state.gallery.oracle, oracle_nargs=n_domain,
            result_arg=cfg["output_arg"], check_passes=False)
        diff_s = time.perf_counter() - t0
    out = runs[-1].arrays[cfg["output_arg"]]
    record = {"lanes": lanes, "cycles": rep.cycles, "schedule_s": schedule_s,
              "diff_s": diff_s, "compile_s": rep.compile_s,
              "run_s": rep.run_s}
    return record, out, rep.ok


def setup(config: dict, traffic: dict, reference, seed: int) -> State:
    state = State(config, traffic, reference, seed,
                  GALLERY[config["design"]])
    t = time.perf_counter()
    records = [_design(state, knobs, -1 - k)[0]
               for k, knobs in enumerate(traffic["warmup"])]
    state.phases = {"warmup_s": time.perf_counter() - t,
                    "warmup_compile_s": [r["compile_s"] for r in records],
                    "warmup_run_s": [r["run_s"] for r in records]}
    return state


def unit(state: State, index: int) -> dict:
    variants = state.traffic["variants"]
    if index >= len(variants):
        raise VariantsExhausted(
            f"the window needs more than the {len(variants)} variants "
            f"listed")
    return _keep(state, *_design(state, variants[index], index))


def traced_unit(state: State, index: int, k: int) -> dict:
    """The ``k``-th of the fixed ``trace_variants``, whatever number of
    variants the window took."""
    return _keep(state, *_design(state, state.traffic["trace_variants"][k],
                                 index))


def _keep(state: State, record: dict, out, ok: bool) -> dict:
    state.outputs.append(out)
    state.verdicts.append(ok)
    return record


def check(state: State, units: list[dict]) -> tuple[int, int, dict]:
    """Every lane of every design against the reference; designs count."""
    inputs = state.config["inputs"]
    bad_lanes = rejected = failed = 0
    for index, (u, got, ok) in enumerate(zip(units, state.outputs,
                                            state.verdicts)):
        args = stimulus.batch(inputs, u["lanes"], state.seed, index)
        want = state.reference.reference(
            state.config, stimulus.domain_args(inputs, args))
        bad = mismatched_lanes(got, want)
        bad_lanes += bad
        rejected += not ok
        failed += bool(bad) or not ok
    return len(units), failed, {
        "mismatched_lanes": {"value": bad_lanes, "limit": 0},
        "rejected_designs": {"value": rejected, "limit": 0}}
