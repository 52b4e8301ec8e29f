"""Solve path: seconds inside encode (the benchmark's wrapper around
`runner.finalize`, after the device result is ready) + `solve.cid` +
`solve.pin` + `solve.commit` + `solve.reveal`, per solution. The staged
executor computes CIDs on worker threads outside any span, so there
`solve.cid` is silent. A run without the wrapper (an untraced one) reads
nothing: the journal's part alone is not this number. Source: obs journal
and the benchmark's spans."""
from perfbench.spans import named, seconds_in


def read(run):
    if not run.solutions or not named(run.spans, "bench.encode"):
        return None
    total = seconds_in(run.spans, "bench.encode", "solve.cid", "solve.pin",
                       "solve.commit", "solve.reveal")
    return total / run.solutions if total else None
