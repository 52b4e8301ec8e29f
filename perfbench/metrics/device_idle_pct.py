"""Device: 1 - (union of the device's operation intervals / window), from
the profiler's trace of the window."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
