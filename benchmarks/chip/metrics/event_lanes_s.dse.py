"""``event_lanes_s.dse``: seconds per design of the window in the program's
``hir.diff.event_lanes`` span: the sample lanes re-run by the
event-driven simulator and compared."""

import program_spans

program_spans.start()


def read(run):
    return program_spans.seconds_per_unit(run, "dse", "hir.diff.event_lanes")
