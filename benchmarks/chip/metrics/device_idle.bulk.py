"""``device_idle.bulk``: share of the traced window in which no op ran on
the device, in percent (profiler trace, ``xplane.reduce``)."""


def read(run):
    if run.runner != "bulk" or run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
