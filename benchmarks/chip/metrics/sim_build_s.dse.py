"""``sim_build_s.dse``: seconds per design of the window in the program's
``hir.sim.build`` span: the simulator's build (flatten, closing the
memory ports, the compiled tape)."""

import program_spans

program_spans.start()


def read(run):
    return program_spans.seconds_per_unit(run, "dse", "hir.sim.build")
