"""``host_ms_per_batch.bulk``: the window's wall time not spent in the
device scan (stimulus, transfer to the device and back, ``_collect``), per
batch, in milliseconds."""


def read(run):
    if run.runner != "bulk" or not run.units:
        return None
    scan = sum(u["run_s"] for u in run.units)
    return 1e3 * (run.window_s - scan) / len(run.units)
