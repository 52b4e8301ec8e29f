"""Bucket programs: seconds in which at least one bucket is dispatched and
its result not yet ready (from the start of `runner.dispatch` to the
moment `block_until_ready` returns on its result; overlapping buckets of
the staged path count once), per real solution. Source: the benchmark's
spans around the runner's calls."""
from perfbench.spans import named, union_seconds


def read(run):
    if not run.solutions:
        return None
    start = {s["attrs"]["key"]: s["t0"] for s in named(run.spans,
                                                        "bench.dispatch")}
    spans = [(start[s["attrs"]["key"]], s["t1"])
             for s in named(run.spans, "bench.device_wait")
             if s["attrs"]["key"] in start]
    if not spans:
        return None
    return union_seconds(spans) / run.solutions
