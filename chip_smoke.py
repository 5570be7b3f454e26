#!/usr/bin/env python3
"""Run the compile-and-verify path once on one TPU chip.

    python chip_smoke.py

Three phases, each through the entry points a user of the compiler calls:

1. the paper's 16x16 systolic GEMM from the gallery is verified, optimized
   and emitted as Verilog, then differentially verified over 1024 random
   stimulus lanes with the batched netlist simulator on the device
   (``run_differential``, ``backend="jax"``), against the event-driven
   simulator, the NumPy oracle and a per-RTL-pass check;
2. ``frontend_matmul``, traced from ``jax.numpy``, goes through the same
   differential verification;
3. the Pallas bindings of the five loop kernels (``lower_to_pallas``) and the
   gemm's MXU binding (``kernels.ops.matmul``) are compiled for the TPU, run,
   and compared with their oracles.

Every phase prints its lines as it finishes; device timings are taken after
``block_until_ready``.  The last line is one JSON object naming the device.
The script exits non-zero, without that line, when JAX finds no TPU or any
phase fails.  Each phase is a plain function with its sizes as arguments, so
the tests run them on the CPU at tiny sizes with ``interpret=True``.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.core import verifier  # noqa: E402
from repro.core.codegen import (generate_verilog, report_design,  # noqa: E402
                                run_differential, stack_stimulus)
from repro.core.gallery import GALLERY  # noqa: E402
from repro.core.passes import DEFAULT_PIPELINE_SPEC, PassManager  # noqa: E402

#: the loop kernels ``lower_to_pallas`` binds (the gemm binds to the MXU)
PALLAS_KERNELS = ("array_add", "transpose", "stencil1d", "histogram",
                  "conv2d")


class SmokeError(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def _differential(module, entry: str, gal, batch: int, seed: int,
                  **input_kw) -> list[str]:
    """``run_differential`` on the device over ``batch`` lanes; the lines
    that report it."""
    args = stack_stimulus(gal.make_inputs, batch, base_seed=seed, **input_kw)
    t0 = time.perf_counter()
    rep = run_differential(module, entry, args, kernel=entry,
                           oracle=gal.oracle, oracle_nargs=2, backend="jax")
    wall = time.perf_counter() - t0
    _check(rep.backend == "jax", f"{entry}: simulator backend {rep.backend}")
    _check(rep.event_ok, f"{entry}: vectorized != event-driven: "
           f"{rep.mismatches[:4]}")
    _check(rep.oracle_ok is True, f"{entry}: vectorized != oracle: "
           f"{rep.mismatches[:4]}")
    _check(bool(rep.passes_ok) and all(rep.passes_ok.values()),
           f"{entry}: RTL pass check failed: {rep.passes_ok}")
    host_s = wall - rep.compile_s - rep.run_s
    return [
        f"{entry} device sim: backend={rep.backend} platform={rep.platform} "
        f"lanes={rep.n_vectors} cycles={rep.cycles} "
        f"compile_s={rep.compile_s} run_s={rep.run_s} "
        f"vectors_per_s={rep.n_vectors / rep.run_s} "
        f"vector_cycles_per_s={rep.n_vectors * rep.cycles / rep.run_s}",
        f"{entry} host legs: event-driven lanes={rep.event_lanes_checked} ok, "
        f"oracle lanes={rep.n_vectors} ok, per-pass checks "
        f"(backend=numpy on the host) "
        f"{sum(rep.passes_ok.values())}/{len(rep.passes_ok)} ok, "
        f"host_s={host_s}",
    ]


def verify_gemm(n: int = 16, batch: int = 1024, seed: int = 7) -> list[str]:
    """Phase 1: the gallery systolic GEMM at ``n``, verified end to end."""
    gal = GALLERY["gemm"]
    module, entry = gal.build(n)
    t0 = time.perf_counter()
    verifier.verify(module)
    PassManager.from_spec(DEFAULT_PIPELINE_SPEC).run(module)
    mods = generate_verilog(module.clone(), entry, hierarchy="modules")
    host_s = time.perf_counter() - t0
    pes = mods[entry].netlist.instances.count("mac")
    dsp = report_design(mods, entry).dsp
    _check(pes == n * n, f"gemm: {pes} PE instances, want {n * n}")
    _check(dsp == 3 * n * n, f"gemm: {dsp} DSPs, want {3 * n * n}")
    lines = [f"gemm n={n}: verify + {DEFAULT_PIPELINE_SPEC} + verilog "
             f"({len(mods[entry].text.splitlines())} lines) on the host in "
             f"{host_s} s; {pes} PEs, {dsp} DSPs"]
    return lines + _differential(module, entry, gal, batch, seed, n=n)


def verify_traced(batch: int = 1024, seed: int = 7) -> list[str]:
    """Phase 2: ``frontend_matmul`` traced from ``jax.numpy`` and verified."""
    gal = GALLERY["frontend_matmul"]
    t0 = time.perf_counter()
    module, entry = gal.build()
    lines = [f"{entry}: traced and scheduled on the host in "
             f"{time.perf_counter() - t0} s"]
    return lines + _differential(module, entry, gal, batch, seed)


def pallas_bindings(interpret: bool = False, gemm_n: int = 16,
                    tile: int = 128) -> list[str]:
    """Phase 3: every loop kernel's ``lower_to_pallas`` binding and the
    gemm's MXU binding, compiled (or interpreted) and checked against the
    oracles."""
    import jax
    import jax.numpy as jnp

    from repro.core.lower.to_pallas import lower_to_pallas
    from repro.kernels import ops

    mode = "interpreted" if interpret else "compiled"

    def run(name, fn, ins, want):
        t0 = time.perf_counter()
        exe = jax.jit(fn).lower(*ins).compile()
        compile_s = time.perf_counter() - t0
        _check(interpret or "tpu_custom_call" in exe.as_text(),
               f"pallas {name}: no Mosaic kernel in the compiled program")
        out = jax.block_until_ready(exe(*ins))
        _check(np.array_equal(np.asarray(out, np.int64), want),
               f"pallas {name}: output != oracle")
        return (f"pallas {name}: {mode} in {compile_s} s, ran on "
                f"{out.devices().pop().platform}, "
                f"{tuple(out.shape)} == oracle")

    lines = []
    for name in PALLAS_KERNELS:
        gal = GALLERY[name]
        module, entry = gal.build()
        fn = lower_to_pallas(module, entry, interpret=interpret)
        n_in = sum(1 for a in module.get(entry).args if a.type.port == "r")
        ins = [jnp.asarray(x, jnp.int32) for x in gal.make_inputs()[:n_in]]
        want = gal.oracle(*[np.asarray(x) for x in ins])
        lines.append(run(name, lambda *xs, f=fn: next(iter(f(*xs).values())),
                         ins, want))

    gal = GALLERY["gemm"]
    a, b, _ = gal.make_inputs(gemm_n)
    ins = [jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)]
    lines.append(run(
        f"gemm n={gemm_n} -> kernels.ops.matmul ({tile}x{tile}x{tile} tiles)",
        lambda x, y: ops.matmul(x, y, bm=tile, bn=tile, bk=tile,
                                interpret=interpret),
        ins, gal.oracle(a, b)))
    return lines


def main() -> int:
    from repro.core.compile_cache import use_compile_cache

    cache = use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU present (JAX found platform "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache: {cache}", flush=True)
    for phase in (verify_gemm, verify_traced, pallas_bindings):
        t0 = time.perf_counter()
        for line in phase():
            print(line, flush=True)
        print(f"phase {phase.__name__}: ok in {time.perf_counter() - t0} s",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
