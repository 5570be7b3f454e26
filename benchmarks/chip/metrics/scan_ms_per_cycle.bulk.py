"""``scan_ms_per_cycle.bulk``: the simulator's own device time
(``SimResult.run_s``, taken after ``block_until_ready``) summed over the
window's batches, per simulated cycle, in milliseconds."""


def read(run):
    if run.runner != "bulk" or not run.units:
        return None
    return 1e3 * (sum(u["run_s"] for u in run.units)
                  / sum(u["cycles"] for u in run.units))
