"""``verify_vcps``: lanes x simulated cycles of every batch of the window,
over the window's wall time (first batch's start to last batch's end, whole
batches only), host clock.  Every lane is checked against the reference
after the window."""


def read(run):
    if run.runner != "bulk" or not run.units:
        return None
    return sum(u["lanes"] * u["cycles"] for u in run.units) / run.window_s
