"""Bucket programs: seconds in which at least one chunk is dispatched and
its device result not yet ready, per real solution, from the program's own
spans: from the start of a chunk's `solve.dispatch` to the end of its
`solve.device_wait`, joined on their `chunk` attribute; overlapping chunks
count once. The twin of `infer_s_per_sol`, which times the same interval
from the benchmark's wrappers. A program without these spans reads
nothing. Source: the program's obs journal."""
from perfbench.spans import named, union_seconds


def read(run):
    if not run.solutions:
        return None
    start = {tuple(s["attrs"]["chunk"]): s["t0"]
             for s in named(run.spans, "solve.dispatch")
             if "chunk" in s["attrs"]}
    busy = [(start[tuple(s["attrs"]["chunk"])], s["t1"])
            for s in named(run.spans, "solve.device_wait")
            if tuple(s["attrs"].get("chunk", ())) in start]
    if not busy:
        return None
    return union_seconds(busy) / run.solutions
