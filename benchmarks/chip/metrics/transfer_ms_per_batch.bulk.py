"""``transfer_ms_per_batch.bulk``: milliseconds per batch of the window in
the program's ``hir.sim.put`` (state and stimulus to the device, until
they are ready there) and ``hir.sim.fetch`` (final state and per-cycle
outputs back to the host) spans."""

import program_spans

program_spans.start()


def read(run):
    s = program_spans.seconds_per_unit(run, "bulk", "hir.sim.put",
                                       "hir.sim.fetch")
    return None if s is None else 1e3 * s
