"""``design_s``: the window's wall time over the number of designs taken
from source to a verdict in it (whole designs only), host clock."""


def read(run):
    if run.runner != "dse" or not run.units:
        return None
    return run.window_s / len(run.units)
