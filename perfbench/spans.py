"""Host-side spans on one clock (`time.perf_counter`).

Two sources. The program's obs journal (`obs/trace.py`: name, wall start,
seconds, attributes), read once the window has closed. And, in the traced
run only, the benchmark's own wrappers around the calls into the bucket
programs: `runner.dispatch` (`bench.dispatch`) and `runner.finalize`,
split at the moment the device result is ready (`bench.device_wait`,
then `bench.encode`). The staged executor finalizes on worker threads
that carry no ambient obs, so its `solve.encode` and `solve.cid` never
reach the journal; the wrappers see both paths alike. Each wrapper also
writes a `jax.profiler.TraceAnnotation`, which puts it on the profiler's
clock next to the device's operations.
"""
from __future__ import annotations

import threading
import time


class SpanLog:
    def __init__(self):
        self.spans: list[dict] = []
        self._lock = threading.Lock()

    def add(self, name: str, t0: float, t1: float, **attrs) -> None:
        with self._lock:
            self.spans.append({"name": name, "t0": t0, "t1": t1,
                               "attrs": attrs})

    def wrap_runner(self, runner, model: str = "") -> None:
        import jax

        dispatch, finalize = runner.dispatch, runner.finalize
        log = self

        def timed_dispatch(items):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                dev = dispatch(items)
            log.add("bench.dispatch", t0, time.perf_counter(),
                    batch=len(items), key=id(dev), model=model)
            return dev

        def timed_finalize(dev, n_real):
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.device_wait"):
                jax.block_until_ready(dev)
            t1 = time.perf_counter()
            log.add("bench.device_wait", t0, t1, n=n_real, key=id(dev))
            with jax.profiler.TraceAnnotation("bench.encode"):
                out = finalize(dev, n_real)
            log.add("bench.encode", t1, time.perf_counter(), n=n_real)
            return out

        runner.dispatch = timed_dispatch
        runner.finalize = timed_finalize

    def add_journal(self, events: list[dict]) -> None:
        """Journal span events carry `wall_start` on `time.time`'s clock."""
        offset = time.perf_counter() - time.time()
        for e in events:
            if e.get("kind") != "span" or "wall_start" not in e:
                continue
            t0 = e["wall_start"] + offset
            attrs = dict(e.get("attrs") or {})
            if "taskid" in e:
                attrs["taskid"] = e["taskid"]
            self.add(e["name"], t0, t0 + e["wall_s"], **attrs)

    def within(self, t0: float, t1: float) -> list[dict]:
        """Spans clipped to [t0, t1]; those wholly outside are dropped."""
        out = []
        for s in self.spans:
            a, b = max(s["t0"], t0), min(s["t1"], t1)
            if b > a or (s["t0"] == s["t1"] and t0 <= s["t0"] <= t1):
                out.append({**s, "t0": a, "t1": b})
        return out


def union_seconds(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def named(spans: list[dict], *names: str) -> list[dict]:
    return [s for s in spans if s["name"] in names]


def seconds_in(spans: list[dict], *names: str) -> float:
    return sum(s["t1"] - s["t0"] for s in named(spans, *names))
