"""From a profiler trace to numbers: device busy union, idle gaps by what
the host was doing, the operations that took most time, a kernel's sum.

The reduction works on plain lists of `(name, start_s, duration_s)` so a
small recorded trace can check it; `load_xplane` is the thin reader that
makes those lists from the `.xplane.pb` the JAX profiler writes, with
nothing but JAX (`jax.profiler.ProfileData`).
"""
from __future__ import annotations

import glob
import os
import re

from perfbench.spans import union_seconds

# lines of a device plane that repeat the operations at a coarser grain
# (whole programs, steps) or are bookkeeping; the busy union reads the
# operation line(s) only
COARSE_LINES = re.compile(r"(XLA Modules|Steps|Framework|Source|TraceMe)",
                          re.I)
OP_LINES = re.compile(r"XLA Ops", re.I)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path: str) -> dict:
    """{"devices": {plane: {line: [(name, start_s, dur_s)]}},
        "host": [(name, start_s, dur_s)]} — times in seconds on the
    profiler's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:") \
            and "TPU" in plane.name.upper()
        lines = {}
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                   for ev in line.events]
            if is_dev:
                lines[line.name] = evs
            elif plane.name.startswith("/host:"):
                host.extend(evs)
        if is_dev:
            devices[plane.name] = lines
    return {"devices": devices, "host": host}


# An event of the operation line is named by its whole HLO instruction
# ("%fusion.12 = bf16[...] fusion(...)"). Containers (%while, %conditional,
# %call) span the operations inside them, and "-start" operations span an
# asynchronous copy that runs beside the compute: neither is the device
# computing, so the busy union and the totals leave them out.
NOT_LEAF = re.compile(r"^%?(while|conditional|call)\b|^%?[\w\-]*-start\b")


def op_name(event_name: str) -> str:
    """'%fusion.12 = bf16[..] fusion(..)' -> 'fusion.12'."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def op_events(lines: dict) -> list[tuple[str, float, float]]:
    """The leaf operation events of one device plane, short-named: the
    'XLA Ops' line(s) if the plane has them, else every line that is not a
    coarser repeat."""
    picked = [n for n in lines if OP_LINES.search(n)] \
        or [n for n in lines if not COARSE_LINES.search(n)]
    out = []
    for n in picked:
        for name, start, dur in lines[n]:
            short = op_name(name)
            if not NOT_LEAF.match(short):
                out.append((short, start, dur))
    return out


def clip(events, t0: float, t1: float):
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_seconds(events) -> float:
    return union_seconds((s, s + d) for _, s, d in events)


def idle_gaps(events, t0: float, t1: float, min_gap: float = 1e-4):
    """[(start, end)] of the window in which no operation ran."""
    gaps, end = [], t0
    for s, e in sorted((s, s + d) for _, s, d in events):
        if s - end >= min_gap:
            gaps.append((end, s))
        end = max(end, e)
    if t1 - end >= min_gap:
        gaps.append((end, t1))
    return gaps


def base_name(op: str) -> str:
    """'flash_attention.98' -> 'flash_attention': an HLO name without its
    trailing instance number, so that the copies of one operation across
    layers go by one name."""
    return re.sub(r"[.\-_]?\d+$", "", op)


def top_ops(events, n: int = 10) -> list[list]:
    """[[name, seconds]] of the operations, by `base_name`, that took most
    time."""
    total: dict[str, float] = {}
    for name, _, d in events:
        key = base_name(name)
        total[key] = total.get(key, 0.0) + d
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def kernel_events(events, pattern: str):
    """The events whose `base_name` the pattern finds: the same names, and
    so the same seconds, as the breakdown's `device_ops` show."""
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(base_name(ev[0]))]


def kernel_roofline_pct(run, pattern: str, bucket_floor_s):
    """A kernel's share of its roofline in the traced window, in percent:
    the least seconds the chip could take for the calls the kernel serves
    — `bucket_floor_s(model, parts)` for one bucket program of a model at
    the canonical batch (`parts`: flops.count_parts), once for each
    `bench.dispatch` span of that model — over the device time of the
    events `pattern` finds. Nothing to read (no trace, no such event, no
    such call): None."""
    if run.trace is None or run.peaks is None:
        return None
    spent = sum(d for _, _, d in kernel_events(run.trace["events"], pattern))
    if not spent:
        return None
    batch = run.system.canonical_batch
    per_model = {}
    for m in run.system.models:
        parts = run.parts.get(m.template, {}).get(batch)
        if parts is not None:
            per_model[m.template] = bucket_floor_s(m, parts)
    floor = sum(per_model.get(s["attrs"].get("model"), 0.0)
                for s in run.spans if s["name"] == "bench.dispatch")
    return 100.0 * floor / spent if floor else None


def name_gaps(gaps, host_spans: list[dict], to_host_clock, n: int = 10):
    """[[span name, seconds]]: the longest idle gaps, each named by the
    innermost host span that covers the gap's middle (`to_host_clock`
    maps the profiler's clock to the spans' clock); gaps with one name
    add up."""
    total: dict[str, float] = {}
    for a, b in gaps:
        mid = to_host_clock((a + b) / 2)
        cover = [s for s in host_spans if s["t0"] <= mid <= s["t1"]]
        name = min(cover, key=lambda s: s["t1"] - s["t0"])["name"] \
            if cover else "outside any span"
        total[name] = total.get(name, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]
