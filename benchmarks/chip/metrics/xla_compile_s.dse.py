"""``xla_compile_s.dse``: seconds per design that the simulator spent
tracing, lowering and compiling its scan (``DiffReport.compile_s``)."""


def read(run):
    if run.runner != "dse" or not run.units:
        return None
    return sum(u["compile_s"] for u in run.units) / len(run.units)
