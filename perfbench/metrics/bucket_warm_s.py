"""Set-up: seconds in the warm-up dispatches (one canonical batch of each
model the traffic holds: compile, or load from the persistent cache)."""


def read(run):
    return run.timings.get("bucket_warm_s")
