"""Benchmark orchestrator — one module per paper table plus the roofline
report mandated by the assignment:

  codegen_speed    paper Table 6 (HIR vs HLS codegen time)
  dse              Pareto-front design-space exploration (gemm, conv2d)
  incremental      per-function warm recompilation + pooled emission
  resource_usage   paper Table 5 (LUT/FF/DSP/BRAM per kernel)
  precision_opt    paper Table 4 (precision-opt ablation)
  roofline         EXPERIMENTS §Roofline source (reads dry-run artifacts)
  sim_throughput   vectorized vs event-driven simulation throughput
  sharing          cross-instance time-multiplexing resources + verification

``python -m benchmarks.run [name ...]`` runs all (or the named) benchmarks
and writes artifacts/bench/BENCH_<name>.json (the same naming every
self-writing suite uses, so the artifacts directory holds exactly one file
per benchmark).  ``--only a,b`` / ``--skip x,y``
filter the suite list (combinable with positional names); a failing
benchmark is reported and turns the final exit status nonzero instead of
silently passing, so CI perf-smoke steps can gate on it.  ``--profile``
reruns the suites that support it (codegen_speed) under cProfile, printing
the top cumulative hotspots instead of benchmarking — the starting point
for perf PRs.
"""

from __future__ import annotations

import inspect
import json
import sys
import time
import traceback
from pathlib import Path

ARTIFACTS = Path(__file__).resolve().parents[1] / "artifacts" / "bench"


def _split_opt(argv: list, flag: str) -> set:
    """Pop ``--flag a,b`` / ``--flag=a,b`` occurrences; returns the names."""
    names: set = set()
    out = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == flag and i + 1 < len(argv):
            names.update(x for x in argv[i + 1].split(",") if x)
            i += 2
            continue
        if a.startswith(flag + "="):
            names.update(x for x in a[len(flag) + 1:].split(",") if x)
            i += 1
            continue
        out.append(a)
        i += 1
    argv[:] = out
    return names


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    profile = "--profile" in argv
    if profile:
        argv = [a for a in argv if a != "--profile"]
    only = _split_opt(argv, "--only")
    skip = _split_opt(argv, "--skip")
    from . import (codegen_scaling, codegen_speed, dse, incremental,
                   precision_opt, resource_usage, roofline, sharing,
                   sim_throughput)

    suites = {
        "codegen_speed": codegen_speed,
        "codegen_scaling": codegen_scaling,
        "dse": dse,
        "incremental": incremental,
        "resource_usage": resource_usage,
        "precision_opt": precision_opt,
        "roofline": roofline,
        "sim_throughput": sim_throughput,
        "sharing": sharing,
    }
    passthrough = [a for a in argv if a.startswith("--")]
    argv = [a for a in argv if not a.startswith("--")]
    names = argv or list(suites)
    unknown = [n for n in set(names) | only | skip if n not in suites]
    if unknown:
        print(f"unknown benchmark(s): {', '.join(sorted(unknown))}; "
              f"available: {', '.join(suites)}")
        return 2
    if only:
        names = [n for n in names if n in only]
    names = [n for n in names if n not in skip]
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    failed: list[str] = []
    for name in names:
        mod = suites[name]
        print(f"\n=== {name} ===")
        t0 = time.time()
        try:
            params = inspect.signature(mod.main).parameters
            kw = {}
            if "argv" in params:
                # suites parse sys.argv when argv is None; hand them exactly
                # the flags not consumed here (e.g. --quick) instead
                kw["argv"] = list(passthrough)
            if profile:
                if "profile" not in params:
                    print(f"({name}: no --profile support, skipped)")
                    continue
                rows = mod.main(profile=True, **kw)
            else:
                rows = mod.main(**kw)
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"({name}: FAILED after {time.time() - t0:.1f}s)")
            continue
        dt = time.time() - t0
        print(f"({name}: {dt:.1f}s)")
        if rows and not isinstance(rows, int):
            (ARTIFACTS / f"BENCH_{name}.json").write_text(
                json.dumps(rows, indent=2, default=str))
    if failed:
        print(f"\nFAILED benchmarks: {', '.join(failed)}")
        return 1
    return 0


if __name__ == "__main__":
    from repro.core.compile_cache import use_compile_cache

    use_compile_cache()
    raise SystemExit(main())
