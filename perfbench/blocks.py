"""Device time by named block of the bucket programs, inside the traced
window: the program's map from HLO instruction to block (its
`Obs.blocks(tag)`, arbius_tpu/obs/blocks.py) joined to the profiler's
operations.

- A chunk is a `solve.dispatch` span that names its `program` (the
  executable's cache tag) and the `solve.device_wait` of the same
  `chunk`. Its device interval is [max(its dispatch start, the previous
  chunk's ready), its ready], moved to the profiler's clock by the
  trace's shift.
- A chunk is whole when that interval lies inside the traced window and
  the trace's last kept operation ends no more than `READY_SLACK_S`
  before its ready stamp: the profiler stops keeping events once its
  buffer is full, and a chunk whose end it lost is read nowhere.
- Each operation that starts inside a whole chunk takes the blocks its
  name has in that chunk's program map; a name the map lacks, or an
  instruction with no block, is unblocked. A block's seconds are its
  operations' durations summed, as `trace_reduce.top_ops` reckons them.

With no whole chunk (no trace, a program whose spans name no program or
whose obs builds no map) every reader returns None.
"""
from __future__ import annotations

import bisect

from perfbench.spans import named

# the host sees a result ready a little after the device's last
# operation of it ends; a trace cut inside a chunk lost far more
READY_SLACK_S = 0.01


def chunks(spans: list[dict]) -> list[dict]:
    """[{program, n, t0, t1}] on the host's clock, in dispatch order:
    each chunk's device interval as the module docstring defines it."""
    ready = {tuple(s["attrs"]["chunk"]): s["t1"]
             for s in named(spans, "solve.device_wait")
             if "chunk" in s["attrs"]}
    out, prev = [], None
    for s in sorted(named(spans, "solve.dispatch"), key=lambda s: s["t0"]):
        key = tuple(s["attrs"].get("chunk", ()))
        if key not in ready or not s["attrs"].get("program"):
            continue
        t0 = s["t0"] if prev is None else max(s["t0"], prev)
        out.append({"program": s["attrs"]["program"],
                    "n": s["attrs"].get("n", 0), "t0": t0,
                    "t1": ready[key]})
        prev = ready[key]
    return out


def join(chunk_list, events, shift: float, t0: float, t1: float,
         maps) -> list[dict]:
    """The whole chunks of `chunk_list` (host clock, traced window
    [t0, t1]) with their operations' seconds by block: each
    {program, n, ops_s, blocks: {block: s}, unblocked_s, unmapped_s}.
    `events`: [(name, start, dur)] on the profiler's clock (host clock +
    `shift`); `maps(tag)`: the program's {instruction: blocks}, or
    None."""
    last = max((s + d for _, s, d in events), default=None)
    whole = []
    for ch in chunk_list:
        bmap = maps(ch["program"])
        if (bmap is None or last is None or ch["t0"] <= t0
                or ch["t1"] >= t1
                or last < ch["t1"] + shift - READY_SLACK_S):
            continue
        whole.append({**ch, "map": bmap, "ops_s": 0.0, "blocks": {},
                      "unblocked_s": 0.0, "unmapped_s": 0.0})
    if not whole:
        return []
    starts = [ch["t0"] + shift for ch in whole]
    for name, s, d in events:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= whole[i]["t1"] + shift:
            continue
        ch = whole[i]
        ch["ops_s"] += d
        path = ch["map"].get(name)
        if path is None:
            ch["unmapped_s"] += d
        if not path:
            ch["unblocked_s"] += d
            continue
        for b in path:
            ch["blocks"][b] = ch["blocks"].get(b, 0.0) + d
    for ch in whole:
        del ch["map"]
    return whole


# the last run joined: the readers of one run share one pass over its
# events (up to 4 M in a traced tick)
_LAST: dict = {}


def split(run) -> list[dict]:
    """`join` over a run's traced window, once a run."""
    if _LAST.get("run") is run:
        return _LAST["whole"]
    whole = []
    node = getattr(run.system, "node", None)
    maps = getattr(getattr(node, "obs", None), "blocks", None)
    if run.trace is not None and maps is not None:
        t0 = run.window["t0"]
        whole = join(chunks(run.spans), run.trace["events"],
                     run.trace["shift"], t0, t0 + run.seconds, maps)
    _LAST.update(run=run, whole=whole)
    return whole


def block_s_per_sol(run, block: str):
    """Seconds of `block`'s operations in the whole chunks, per real
    solution of those chunks; None where no operation of it was read."""
    whole = split(run)
    sols = sum(ch["n"] for ch in whole)
    if not sols or not any(block in ch["blocks"] for ch in whole):
        return None
    return sum(ch["blocks"].get(block, 0.0) for ch in whole) / sols


def unblocked_pct(run):
    """Percent of the whole chunks' operation seconds on operations of
    no block."""
    whole = split(run)
    total = sum(ch["ops_s"] for ch in whole)
    if not total:
        return None
    return 100.0 * sum(ch["unblocked_s"] for ch in whole) / total
