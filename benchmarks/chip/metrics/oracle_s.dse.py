"""``oracle_s.dse``: seconds per design of the window in the program's
``hir.diff.oracle`` span: every lane against the gallery's oracle."""

import program_spans

program_spans.start()


def read(run):
    return program_spans.seconds_per_unit(run, "dse", "hir.diff.oracle")
