"""Speculative decode: of the row-steps the bucket programs' loops ran
(steps x the rows of the bucket), the share spent on rows that already
had their tokens — a loop ends when its slowest row does, so this is
what waiting for the slowest row costs. The program's own int32 counts
on `text.speculate` spans (`steps`, `batch`, `idle_row_steps`). Source:
the program's obs journal; a count. 0 while no draft is accepted (every
row takes one token a step). A program without such spans returns
nothing."""
from perfbench.spans import named


def read(run):
    ran = idle = 0
    for s in named(run.spans, "text.speculate"):
        a = s["attrs"]
        ran += a.get("steps", 0) * a.get("batch", 0)
        idle += a.get("idle_row_steps", 0)
    if not ran:
        return None
    return 100.0 * idle / ran
