"""``scan_lower_s.dse``: seconds per design of the window in the program's
``hir.sim.lower`` span: the step function's build, the scan's trace and
its StableHLO lowering (``xla_compile_s.dse`` less this is XLA's own
compile)."""

import program_spans

program_spans.start()


def read(run):
    return program_spans.seconds_per_unit(run, "dse", "hir.sim.lower")
