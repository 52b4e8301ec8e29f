"""Solutions whose commitment and reveal landed on the chain inside the
window, over the seconds that really passed, x3600. Padding slots are not
solutions. Source: host clock around the whole window."""


def read(run):
    w = run.window
    solved = sum(1 for t in w["tasks"] if t["solved"] is not None)
    return solved / (w["t1"] - w["t0"]) * 3600.0
