"""Cross-instance time-multiplexing benchmark: resources before/after the
``rtl-share-instances`` / ``rtl-arbitrate`` passes (paper §4.4/§4.5 applied
at module granularity).

For each kernel x hierarchy the harness emits the design twice — once with
the sharing passes stripped from the RTL pipeline, once with the full
pipeline — and reports the LUT/FF/DSP deltas plus the sharing summary
(physical vs logical instances, max time-division degree).  Shared designs
are then differentially verified: ``run_differential`` runs the vectorized
cycle-accurate simulator over a stimulus batch against the NumPy oracle
*and* replays the RTL pipeline pass-by-pass (so both new passes are checked
for per-cycle equivalence), and all four backend printers must lint clean
on the shared netlist.

``gemm`` (coincident pulses — the analysis proves nothing, sharing must
refuse) and ``gemm_shared`` (column-staggered II=n schedule — n-way
provable sharing) bracket the analysis; ``conv2d`` has no callee instances
at all and pins the no-op path.

A small DSE sweep (``share_instances`` x ``unroll_parallel``) records the
latency-vs-DSP Pareto frontier: the time-multiplexed candidate must survive
as a genuine tradeoff point next to its fully-spatial sibling.

``--smoke`` shrinks sizes and vector counts for CI.  ``main()`` writes
``artifacts/bench/BENCH_sharing.json``::

    {"sharing": [{kernel, hierarchy, size, before, after, saved,
                  physical, logical, absorbed, max_degree,
                  verified, vectors, lint_ok}, ...],
     "dse": {"kernel": ..., "pareto_front": [...], "sharing_points": [...]},
     "smoke": bool}
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.core.codegen import (BACKENDS, generate_verilog, lint_backend,
                                report_design, sharing_summary)
from repro.core.codegen import sim as rsim
from repro.core.codegen.rtl import RTL_PIPELINE_SPEC
from repro.core.gallery import GALLERY
from repro.core.hls import design_space, explore_design
from repro.core.passes import DEFAULT_PIPELINE_SPEC, PassManager

ARTIFACT = (Path(__file__).resolve().parents[1] / "artifacts" / "bench"
            / "BENCH_sharing.json")

SHARE_PASSES = ("rtl-share-instances", "rtl-arbitrate")
#: the RTL pipeline with only the sharing passes removed — the "before"
#: emission, so deltas isolate exactly what sharing buys.
NOSHARE_SPEC = ",".join(p for p in RTL_PIPELINE_SPEC.split(",")
                        if p not in SHARE_PASSES)

#: kernel -> (build kwargs, oracle nargs, differentially verify?)
FULL_KERNELS = [
    ("gemm", {"n": 8}, 2, True),
    ("gemm_shared", {"n": 8}, 2, True),
    ("gemm_shared", {"n": 16}, 2, False),   # resources only: 16x reduction
    ("conv2d", {"h": 8, "w": 8}, 1, True),
]
SMOKE_KERNELS = [
    ("gemm", {"n": 4}, 2, True),
    ("gemm_shared", {"n": 4}, 2, True),
    ("conv2d", {"h": 4, "w": 4}, 1, True),
]


def _resources(module, entry, hierarchy, rtl_spec):
    mods = generate_verilog(module.clone(), entry=entry, hierarchy=hierarchy,
                            rtl_spec=rtl_spec)
    return mods, report_design(mods, entry=entry).as_dict()


def _lint_all(module, entry, hierarchy) -> dict:
    """All four backend printers must emit a shared design that lints."""
    out = {}
    for be in BACKENDS:
        mods = generate_verilog(module.clone(), entry=entry,
                                hierarchy=hierarchy, backend=be)
        text = "\n".join(vm.text for vm in mods.values())
        out[be] = not lint_backend(text, be, known_modules=list(mods))
    return out


def bench_kernel(name: str, build_kwargs: dict, nargs: int, verify: bool,
                 hierarchy: str, n_vectors: int) -> dict:
    gal = GALLERY[name]
    module, entry = gal.build(**build_kwargs)
    PassManager.from_spec(DEFAULT_PIPELINE_SPEC).run(module)

    _, before = _resources(module, entry, hierarchy, NOSHARE_SPEC)
    mods, after = _resources(module, entry, hierarchy, RTL_PIPELINE_SPEC)
    sh = sharing_summary(mods, entry=entry)
    row = {
        "kernel": name, "hierarchy": hierarchy, "size": dict(build_kwargs),
        "before": before, "after": after,
        "saved": {k: before[k] - after[k] for k in before},
        "physical": sh["physical_instances"],
        "logical": sh["logical_instances"],
        "absorbed": sh["absorbed"],
        "max_degree": max((d["max_degree"]
                           for d in sh["per_module"].values()), default=0),
        "verified": None, "vectors": 0,
        "lint_ok": _lint_all(module, entry, hierarchy),
    }
    if verify:
        fresh, _ = gal.build(**build_kwargs)
        batch = rsim.stack_stimulus(gal.make_inputs, n_vectors, base_seed=7,
                                    **build_kwargs)
        rep = rsim.run_differential(fresh, entry, batch, kernel=name,
                                    hierarchy=hierarchy, oracle=gal.oracle,
                                    oracle_nargs=nargs)
        row["verified"] = bool(rep.ok and rep.oracle_ok
                               and all(rep.passes_ok.values()))
        row["vectors"] = n_vectors
    return row


def bench_dse(n: int, workers: int = 1) -> dict:
    """Sweep gemm with the sharing knob: `unroll_parallel=False` staggers
    the unrolled PE copies, which is what makes the pulses provably
    disjoint under the autotuner's own schedules."""
    gal = GALLERY["gemm"]
    module, entry = gal.build(n)
    inputs = gal.make_inputs(n)
    expected = gal.oracle(*inputs[:2])
    space = design_space(pipeline=(True,), unroll_parallel=(True, False),
                         share_instances=(False, True))
    res = explore_design(module, space, entry=entry,
                         inputs=[a.copy() for a in inputs],
                         expected=expected, max_workers=workers)
    front = [p.as_dict() for p in res.front]
    return {"kernel": "gemm", "size": {"n": n},
            "n_points": len(res.points),
            "n_verified": sum(p.verified for p in res.points),
            "pareto_front": front,
            "sharing_points": [p for p in front
                               if p["config"]["share_instances"]
                               and p["shared_absorbed"] > 0]}


def run(smoke: bool = False, workers: int = 1) -> dict:
    kernels = SMOKE_KERNELS if smoke else FULL_KERNELS
    n_vectors = 32 if smoke else 256
    rows = []
    for name, kw, nargs, verify in kernels:
        for hierarchy in ("inline", "modules"):
            t0 = time.perf_counter()
            row = bench_kernel(name, kw, nargs, verify, hierarchy, n_vectors)
            row["wall_s"] = round(time.perf_counter() - t0, 2)
            rows.append(row)
            print(f"{name}{kw} {hierarchy}: dsp {row['before']['DSP']} -> "
                  f"{row['after']['DSP']}, lut {row['before']['LUT']} -> "
                  f"{row['after']['LUT']}, absorbed {row['absorbed']} "
                  f"(x{row['max_degree']}), verified={row['verified']} "
                  f"({row['wall_s']}s)")
    dse = bench_dse(4, workers=workers)
    print(f"dse gemm n=4: {len(dse['pareto_front'])} frontier points, "
          f"{len(dse['sharing_points'])} time-multiplexed")
    return {"sharing": rows, "dse": dse, "smoke": smoke}


def main(json_out: bool = False, smoke: bool = False, workers: int = 1,
         artifact: bool = True) -> dict:
    payload = run(smoke=smoke, workers=workers)
    if artifact:
        ARTIFACT.parent.mkdir(parents=True, exist_ok=True)
        ARTIFACT.write_text(json.dumps(payload, indent=2))
    if json_out:
        print(json.dumps(payload, indent=2))
    return payload


if __name__ == "__main__":
    from repro.core.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true", help="emit payload as JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + 32 vectors for CI")
    ap.add_argument("--workers", type=int, default=1,
                    help="process-pool width for the DSE sweep")
    ap.add_argument("--no-artifact", action="store_true",
                    help="skip writing artifacts/bench/BENCH_sharing.json")
    args = ap.parse_args()
    main(json_out=args.json, smoke=args.smoke, workers=args.workers,
         artifact=not args.no_artifact)
