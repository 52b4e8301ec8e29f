"""Node loop: window seconds covered by no `solve.batch`/`solve.pipeline`
span (event intake, hydration, sqlite, claims), per solution. Source: the
program's obs journal."""
from perfbench.spans import named, union_seconds


def read(run):
    if not run.solutions:
        return None
    solve = named(run.spans, "solve.batch", "solve.pipeline")
    if not solve:
        return None
    covered = union_seconds((s["t0"], s["t1"]) for s in solve)
    return max(run.seconds - covered, 0.0) / run.solutions
