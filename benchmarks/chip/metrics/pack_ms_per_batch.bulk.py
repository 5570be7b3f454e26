"""``pack_ms_per_batch.bulk``: milliseconds per batch of the window in the
program's host re-layout spans: ``hir.sim.layout`` (stimulus into the
simulator's state leaves) and ``hir.sim.collect`` (final state back into
the argument arrays)."""

import program_spans

program_spans.start()


def read(run):
    s = program_spans.seconds_per_unit(run, "bulk", "hir.sim.layout",
                                       "hir.sim.collect")
    return None if s is None else 1e3 * s
