"""Quickstart: the paper's workflow end-to-end in two minutes.

1. Write a kernel in HIR (explicit schedule).
2. The schedule verifier catches a pipelining bug (paper Fig. 1).
3. Optimize (precision opt etc.) and generate Verilog (paper's target).
4. Lower the SAME IR to a Pallas TPU kernel (this repo's hardware
   adaptation) and execute it against the NumPy oracle.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import ir, verifier
from repro.core.builder import Builder
from repro.core.codegen.verilog import generate_verilog
from repro.core.gallery import array_add
from repro.core.lower import lower_to_jax, simulate
from repro.core.lower.to_pallas import lower_to_pallas
from repro.core.passes import DEFAULT_PIPELINE_SPEC, PassManager
from repro.core.printer import print_module


def main():
    # -- 1. a fresh HIR kernel: out[i] = a[i] + b[i], pipelined at II=1 ----
    module, entry = array_add.build(n=64)
    print("== HIR (explicit schedule) ==")
    print_module(module)

    # -- 2. the paper's Fig. 1 bug is caught statically ---------------------
    broken, _ = array_add.build_broken(n=64)
    diags = verifier.verify(broken, raise_on_error=False)
    print("\n== schedule verifier on the Fig. 1 design ==")
    for d in diags:
        print(d.render())

    # -- 3. optimize + Verilog ---------------------------------------------
    # pipelines are declarative specs; the PassManager reports per-pass
    # rewrite counts and wall time
    pm = PassManager.from_spec(DEFAULT_PIPELINE_SPEC)
    stats = pm.run(module)
    print(f"\n== optimization pipeline ({pm.spec}) ==")
    print(pm.render_stats())
    vmods = generate_verilog(module, entry)
    v = vmods[entry].text
    print(f"== Verilog: {len(v.splitlines())} lines, module {entry} ==")
    print("\n".join(v.splitlines()[:12]), "\n...")

    # -- 3b. same netlist, other backends ----------------------------------
    # every backend is a printer over the same optimized RTLModule; the
    # resource summary is derived from the structure, so it never changes
    from repro.core.codegen import get_printer
    from repro.core.codegen.rtl import RTLDesign

    design = RTLDesign({n: vm.rtl for n, vm in vmods.items()})
    for backend in ("systemverilog", "vhdl", "circt"):
        text = get_printer(backend).print_design(design)
        first = next(l for l in text.splitlines() if l and not l.startswith(("//", "--")))
        print(f"== {backend}: {len(text.splitlines())} lines | {first[:60]}")

    # -- 4. same IR -> Pallas TPU kernel ------------------------------------
    # interpreted, so this walkthrough runs on any host; without
    # interpret=True the kernel is compiled for the TPU (chip_smoke.py)
    inputs = array_add.make_inputs(n=64)
    fn = lower_to_pallas(module, entry, interpret=True)
    out = fn(inputs[0], inputs[1])["C"]
    want = array_add.oracle(inputs[0], inputs[1])
    np.testing.assert_array_equal(np.asarray(out, np.int64), want)
    print("\n== Pallas lowering matches the NumPy oracle ==")

    # cross-check: cycle-accurate simulation and functional JAX lowering
    sim_inputs = array_add.make_inputs(n=64)
    simulate(module, entry, sim_inputs)
    np.testing.assert_array_equal(sim_inputs[-1], want)
    jout = lower_to_jax(module, entry)(*array_add.make_inputs(n=64))
    np.testing.assert_array_equal(np.asarray(jout["C"], np.int64), want)
    print("== cycle-accurate sim and functional lowering agree ==")
    print("\nquickstart OK")


if __name__ == "__main__":
    main()
