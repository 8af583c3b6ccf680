"""Device: share of the traced window in which no operation ran on the
chip (averaged over the chips used)."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or not run.busy_s:
        return None
    return 100.0 * (1.0 - run.busy_s / run.trace.window_s)
