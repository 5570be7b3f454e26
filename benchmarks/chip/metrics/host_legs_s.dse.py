"""``host_legs_s.dse``: seconds per design inside ``run_differential`` that
are neither the scan's compile nor its device run: emission, RTL passes,
simulator build, the event-driven probe and lanes, the oracle."""


def read(run):
    if run.runner != "dse" or not run.units:
        return None
    return sum(u["diff_s"] - u["compile_s"] - u["run_s"]
               for u in run.units) / len(run.units)
