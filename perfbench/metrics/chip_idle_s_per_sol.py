"""Solve path: seconds in the program's `solve.idle` spans, per solution:
the stretches inside a solve pass in which no dispatched chunk awaited its
device result, as the program reckons them from its own ready stamps (the
sum `arbius_chip_idle_seconds_total` counts). It is the host tail that
nothing on the chip hides, which is what the tail costs in `sol_per_hour`;
`device_idle_pct` is its check from the device's side. A pass ends with
its last chunk's tail, so a program that has these spans always has one; a
program without them reads nothing. Source: the program's obs journal."""
from perfbench.spans import named, seconds_in


def read(run):
    if not run.solutions or not named(run.spans, "solve.idle"):
        return None
    return seconds_in(run.spans, "solve.idle") / run.solutions
