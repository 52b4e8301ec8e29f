"""Solve path: seconds inside the program's `solve.encode` (transfer and
codec, after the device result is ready) + `solve.cid` + `solve.pin` +
`solve.commit` + `solve.reveal`, per solution, on whichever thread ran
them. The twin of `host_tail_s_per_sol`, whose encode part is the
benchmark's wrapper. A program whose encode stage journals no
`solve.encode` (the staged executor before PR 25) reads nothing. Source:
the program's obs journal."""
from perfbench.spans import named, seconds_in


def read(run):
    if not run.solutions or not named(run.spans, "solve.encode"):
        return None
    return seconds_in(run.spans, "solve.encode", "solve.cid", "solve.pin",
                      "solve.commit", "solve.reveal") / run.solutions
