"""Design-space exploration benchmark: the II / clock / unroll / banking
autotuner over the paper's gemm and conv2d kernels (ScaleHLS-style DSE on
top of the HLS baseline).

For each kernel the harness sweeps a :func:`repro.core.hls.design_space` —
pipelining on/off, minimum II, clock budget, unroll staggering, local-bank
merging — through ``explore_design``: every candidate is scheduled under its
knobs, optimized, emitted, resource-scored with ``report_design`` and
simulated for its cycle count, then *verified* against the kernel's NumPy
oracle.  The result is the full scored point cloud plus the Pareto frontier
over (latency_ns, LUT, FF); non-verifying or erroring candidates are kept in
the cloud (with their error) but never reach the frontier.

Each kernel is swept twice: exhaustively, and with the adaptive
``strategy="halving"`` explorer (cheap schedule-only scoring of the full
pool, full compile+verify of the surviving half) — the artifact records
whether both reach the same verified Pareto front and how many full
evaluations halving saved.

Candidates run on a process pool with ``--workers N`` (serial at 1, the
default — results are identical either way).  ``--smoke`` shrinks the space
to a handful of candidates for CI.  ``main()`` writes
``artifacts/bench/BENCH_dse.json``::

    {"kernels": {gemm: {"points": [...], "pareto_front": [...],
                        "n_verified": int, "wall_s": float,
                        "halving": {"stats": {...}, "front_equal": bool,
                                    "wall_s": float}}, conv2d: ...},
     "space_axes": {...}, "workers": N}
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.core.gallery import GALLERY
from repro.core.hls import design_space, explore_design

ARTIFACT = (Path(__file__).resolve().parents[1] / "artifacts" / "bench"
            / "BENCH_dse.json")

#: kernel -> (build kwargs, number of oracle input args)
KERNELS = {
    "gemm": ({"n": 8}, 2),
    "conv2d": ({"h": 8, "w": 8}, 1),
    # traced through core/frontend: the autotuner sees the jnp.matmul
    # program exactly like a hand-written kernel
    "frontend_matmul": ({"m": 8, "k": 8, "n": 8}, 2),
}

#: Swept axes.  Three clock budgets trade cycle count against chaining
#: registers (faster clocks pipeline deeper -> more FF), which is what puts
#: genuine area-vs-latency tradeoffs on the frontier; ``merge_banks`` trades
#: RAM count against access serialization on kernels with distributed local
#: banks (gemm); ``min_ii`` relaxes the initiation interval.
SPACE_AXES = {
    "pipeline": (True, False),
    "min_ii": (1, 2),
    "clock_ns": (10.0, 5.0, 2.5),
    "unroll_parallel": (True, False),
    "merge_banks": (False, True),
    "tile": (0, 2),
}

SMOKE_AXES = {
    "pipeline": (True,),
    "min_ii": (1,),
    "clock_ns": (10.0, 5.0, 2.5),
    "unroll_parallel": (True,),
    "merge_banks": (False, True),
    "tile": (0, 2),
}


def run(kernels=None, axes=None, workers: int = 1) -> dict:
    axes = dict(axes or SPACE_AXES)
    out: dict = {}
    for name in (kernels or list(KERNELS)):
        build_kwargs, nargs = KERNELS[name]
        gal = GALLERY[name]
        module, entry = gal.build(**build_kwargs)
        inputs = gal.make_inputs(**build_kwargs)
        expected = gal.oracle(*inputs[:nargs])
        space = design_space(**axes)
        t0 = time.perf_counter()
        res = explore_design(module, space, entry=entry,
                             inputs=[a.copy() for a in inputs],
                             expected=expected, max_workers=workers)
        wall = time.perf_counter() - t0
        t0 = time.perf_counter()
        res_h = explore_design(module, space, entry=entry,
                               inputs=[a.copy() for a in inputs],
                               expected=expected, max_workers=workers,
                               strategy="halving")
        wall_h = time.perf_counter() - t0
        front = lambda r: sorted(repr(p.config.as_dict()) for p in r.front)
        out[name] = {
            **res.as_dict(),
            "n_points": len(res.points),
            "n_verified": sum(p.verified for p in res.points),
            "n_front": len(res.front),
            "wall_s": round(wall, 2),
            "halving": {
                "stats": res_h.stats,
                "front_equal": front(res_h) == front(res),
                "n_front": len(res_h.front),
                "wall_s": round(wall_h, 2),
            },
        }
    return out


def main(json_out: bool = False, kernels=None, workers: int = 1,
         smoke: bool = False, artifact: bool = True) -> dict:
    axes = SMOKE_AXES if smoke else SPACE_AXES
    kernel_rows = run(kernels=kernels, axes=axes, workers=workers)
    payload = {"kernels": kernel_rows,
               "space_axes": {k: list(v) for k, v in axes.items()},
               "workers": workers}
    if artifact:
        ARTIFACT.parent.mkdir(parents=True, exist_ok=True)
        ARTIFACT.write_text(json.dumps(payload, indent=2))
    if json_out:
        print(json.dumps(payload, indent=2))
        return payload
    for name, row in kernel_rows.items():
        print(f"{name}: {row['n_points']} candidates, "
              f"{row['n_verified']} verified, "
              f"{row['n_front']} on the Pareto frontier "
              f"({row['wall_s']}s, workers={workers})")
        print(f"  {'latency_ns':>10s} {'lut':>6s} {'ff':>6s}  config")
        for p in row["pareto_front"]:
            cfg = p["config"]
            knobs = (f"pipeline={cfg['pipeline']} min_ii={cfg['min_ii']} "
                     f"clock={cfg['clock_ns']}ns "
                     f"stagger={cfg['unroll_parallel']} "
                     f"merge_banks={cfg['merge_banks']} "
                     f"tile={cfg.get('tile', 0)}")
            print(f"  {p['latency_ns']:10.1f} {p['lut']:6d} {p['ff']:6d}  "
                  f"{knobs}")
        errs = [p for p in row["points"] if p["error"]]
        if errs:
            print(f"  ({len(errs)} candidates errored out)")
        h = row["halving"]
        print(f"  halving: {h['stats']['n_full']}/{h['stats']['n_candidates']}"
              f" full evaluations ({h['stats']['evaluations_saved']} saved), "
              f"front_equal={h['front_equal']}, {h['wall_s']}s")
    return payload


if __name__ == "__main__":
    from repro.core.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true", help="emit payload as JSON")
    ap.add_argument("--kernels", default=None,
                    help="comma-separated kernel subset (default: gemm,conv2d)")
    ap.add_argument("--workers", type=int, default=1,
                    help="process-pool width (1 = serial)")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI space (6 candidates per kernel)")
    ap.add_argument("--no-artifact", action="store_true",
                    help="skip writing artifacts/bench/BENCH_dse.json")
    args = ap.parse_args()
    names = args.kernels.split(",") if args.kernels else None
    main(json_out=args.json, kernels=names, workers=args.workers,
         smoke=args.smoke, artifact=not args.no_artifact)
