"""The paper -> TPU bridge on the 1-d stencil (paper Listing 2).

Shows the three design components (algorithm / schedule / binding) moving
from the paper's FPGA world to TPU:

  * the HIR source is identical (explicit II=1 pipelined schedule,
    register-window banking);
  * the FPGA binding emits Verilog (shift registers, FSMs) + a resource
    estimate under the Table-5 cost model;
  * the TPU binding emits a ``pl.pallas_call`` whose grid realises the
    pipelined schedule and whose SMEM scratch realises the register window
    (interpreted here, so the example runs on any host) — then a retiming
    error is introduced and the schedule verifier rejects it BEFORE any
    lowering (paper Fig. 2's class of bug).

    PYTHONPATH=src python examples/hir_to_pallas.py
"""

import numpy as np

from repro.core import ir, verifier
from repro.core.builder import Builder
from repro.core.codegen.resources import report_module
from repro.core.codegen.verilog import generate_verilog
from repro.core.gallery import stencil1d
from repro.core.lower.to_pallas import lower_to_pallas
from repro.core.passes import DEFAULT_PIPELINE_SPEC, PassManager


def main():
    module, entry = stencil1d.build(n=64)
    verifier.verify(module)
    print("== schedule verified (II=1 pipelined stencil) ==")

    # FPGA binding: Verilog + resources
    m2, _ = stencil1d.build(n=64)
    pm = PassManager.from_spec(DEFAULT_PIPELINE_SPEC)
    pm.run(m2)
    vmods = generate_verilog(m2, entry)
    res = None
    for vm in vmods.values():
        r = report_module(vm)
        res = r if res is None else res + r
    print(f"FPGA binding:  {sum(len(v.text.splitlines()) for v in vmods.values())} "
          f"lines of Verilog, resources {res.as_dict()}")

    # TPU binding: Pallas kernel (grid = the pipelined loop, scratch = the
    # register window), validated against the oracle
    inputs = stencil1d.make_inputs(n=64)
    fn = lower_to_pallas(module, entry, interpret=True)
    out = fn(inputs[0])["Bw"]
    want = stencil1d.oracle(inputs[0])
    np.testing.assert_array_equal(np.asarray(out, np.int64), want)
    print("TPU binding:   pallas_call(grid=(62,), scratch=SMEM(2)) matches oracle")

    # now break the schedule the way a retiming would (paper Fig. 2) and
    # watch the verifier refuse it statically
    b = Builder(ir.Module("broken"))
    with b.func("mac", [ir.i32, ir.i32, ir.i32], ["a", "b", "c"],
                result_types=[ir.i32], result_delays=[3]) as f:
        m = b.mult(f.args[0], f.args[1], at=f.t, stages=3)   # 3-stage multiplier
        c2 = b.delay(f.args[2], 2, at=f.t)                   # ...2-stage delay
        s = b.add(m, c2)                                     # imbalance!
        b.ret([s])
    diags = verifier.verify(b.module, raise_on_error=False)
    print("\n== retimed design rejected by the schedule verifier ==")
    for d in diags:
        print(d.render())
    assert any("mismatched delay" in d.message for d in diags)
    print("\nhir_to_pallas OK")


if __name__ == "__main__":
    main()
