"""``codegen_s.dse``: seconds per design of the window in the program's
``hir.codegen`` span: ``generate_verilog`` (pre-codegen HIR passes, RTL
lowering, RTL passes, emission)."""

import program_spans

program_spans.start()


def read(run):
    return program_spans.seconds_per_unit(run, "dse", "hir.codegen")
