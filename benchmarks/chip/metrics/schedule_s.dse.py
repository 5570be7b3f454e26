"""``schedule_s.dse``: seconds per design in the benchmark's
``bench.schedule`` span (build, erase, structural knobs, ``hls_schedule``),
host clock."""


def read(run):
    if run.runner != "dse" or not run.units:
        return None
    return sum(u["schedule_s"] for u in run.units) / len(run.units)
