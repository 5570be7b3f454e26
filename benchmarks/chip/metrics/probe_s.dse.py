"""``probe_s.dse``: seconds per design of the window in the program's
``hir.sim.probe`` span: the event-driven simulation that fixes the
cycle budget."""

import program_spans

program_spans.start()


def read(run):
    return program_spans.seconds_per_unit(run, "dse", "hir.sim.probe")
