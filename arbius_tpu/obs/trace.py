"""Trace spans — per-task lifecycle timing with parent/child nesting.

`Tracer.span(name, **attrs)` is a context manager: on exit it records a
completed-span event into the journal (wall-clock start + duration,
chain-time start/end when the tracer has a chain clock, error status if
an exception passed through) and observes the duration into the
registry's `arbius_span_seconds{name=...}` histogram. Nesting is a
per-thread stack, so a span opened inside another becomes its child —
the solve path produces e.g.

    solve.batch → solve.infer → solve.dispatch → solve.device_wait
                                               → solve.encode
                                               → solve.cid
                → solve.idle
                → solve.task → solve.pin → pin.files
                             → solve.commit → chain.signal_commitment
                             → solve.reveal → chain.submit_solution

A span whose cause ran on another thread names it: `span(name,
parent=<span_id>)` for one span, `under(<span_id>)` for whatever a
block opens from the ambient stack (the staged executor's encode
workers hang `solve.device_wait`/`solve.encode`/`solve.cid` under the
tick thread's `solve.dispatch` this way). `record(name, t0, t1)` journals
an interval no `with` block encloses, from two `time.perf_counter`
stamps (`solve.idle`, `task.queue_wait`). Every span event carries
`mono_start`, its `time.perf_counter` start, beside `wall_start`; the
yielded `Span` has the same stamps (`t0`, and `t1` once closed), which
is how the solve path reckons chip idle without a clock of its own.
With `enabled=False` spans are still stamped — counters fed from them
stay truthful — but nothing is journaled.

While jax is loaded every span is also entered as a
`jax.profiler.TraceAnnotation`: inside a profiler session that puts the
program's spans in the profile's host plane, on the profiler's clock,
next to the device's operations; outside one it is the profiler's own
flag check (half a microsecond). This module never imports jax.

`task_trace(events, taskid)` reassembles the journal's flat span events
into trees for one task: spans that carry the taskid (or list it in a
batch-level `taskids` attr), all their descendants, and the ancestor
path up to each root.
"""
from __future__ import annotations

import sys
import threading
import time
from contextlib import contextmanager, nullcontext


class Span:
    __slots__ = ("name", "span_id", "parent_id", "attrs", "t0", "t1")

    def __init__(self, name: str, span_id: int, parent_id: int | None,
                 attrs: dict, t0: float = 0.0):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.attrs = attrs  # mutable: callers may annotate mid-span
        self.t0 = t0        # time.perf_counter at open
        self.t1: float | None = None   # ... and at close


def _annotation(name: str):
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return nullcontext()
    return profiler.TraceAnnotation(name)


class Tracer:
    def __init__(self, journal, registry=None, now_fn=None,
                 enabled: bool = True):
        self.journal = journal
        self.registry = registry
        self.now_fn = now_fn
        self.enabled = enabled
        self._tls = threading.local()
        self._id_lock = threading.Lock()
        self._next_id = 0
        if registry is not None:
            self._h_span = registry.histogram(
                "arbius_span_seconds",
                "Wall-clock seconds per completed trace span",
                labelnames=("name",))
            self._c_err = registry.counter(
                "arbius_span_errors_total",
                "Trace spans that exited with an exception",
                labelnames=("name",))

    def _new_id(self) -> int:
        with self._id_lock:
            self._next_id += 1
            return self._next_id

    def _stack(self) -> list:
        s = getattr(self._tls, "stack", None)
        if s is None:
            s = self._tls.stack = []
        return s

    def _parent(self, parent: int | None) -> int | None:
        if parent is not None:
            return parent
        stack = self._stack()
        return stack[-1].span_id if stack else None

    @contextmanager
    def span(self, name: str, parent: int | None = None, **attrs):
        stack = self._stack()
        wall_start = time.time()
        # detlint: allow[DET101] span start stamp; observability only, never reaches solve bytes
        p0 = time.perf_counter()
        sp = Span(name, self._new_id(), self._parent(parent), attrs, p0)
        chain_start = None
        if self.enabled and self.now_fn is not None:
            try:
                chain_start = self.now_fn()
            except Exception:  # noqa: BLE001 — tracing never breaks work
                pass
        stack.append(sp)
        error = None
        try:
            with _annotation(name):
                yield sp
        except BaseException as e:
            error = f"{type(e).__name__}: {e}"
            raise
        finally:
            stack.pop()
            # detlint: allow[DET101] span end stamp; observability only, never reaches solve bytes
            sp.t1 = time.perf_counter()
            self._finish(sp, wall_start, chain_start, error)

    @contextmanager
    def under(self, parent: int | None):
        """Spans this thread opens inside the block, with no open span
        of their own round them, become children of `parent`: the way a
        span that library code opens from the ambient stack (a runner's
        `solve.encode`) names a cause on another thread. Records
        nothing itself."""
        if parent is None:
            yield
            return
        stack = self._stack()
        stack.append(Span("", parent, None, {}))
        try:
            yield
        finally:
            stack.pop()

    def record(self, name: str, t0: float, t1: float,
               parent: int | None = None, **attrs) -> None:
        """Journal a completed span after the fact, from two
        `time.perf_counter` stamps: same event fields as `span()`'s,
        no chain stamps."""
        sp = Span(name, self._new_id(), self._parent(parent), attrs, t0)
        sp.t1 = t1
        # detlint: allow[DET101] puts t0 on the wall clock for trace display; observability only
        wall_start = time.time() - (time.perf_counter() - t0)
        self._finish(sp, wall_start, None, None)

    def _finish(self, sp: Span, wall_start: float, chain_start,
                error) -> None:
        if not self.enabled:
            return
        dur = sp.t1 - sp.t0
        a = dict(sp.attrs)
        ev = {
            "name": sp.name,
            "span_id": sp.span_id,
            "parent_id": sp.parent_id,
            "wall_start": wall_start,
            "mono_start": sp.t0,
            "wall_s": round(dur, 6),
            "status": "error" if error else "ok",
        }
        if chain_start is not None:
            ev["chain_start"] = chain_start
            if self.now_fn is not None:
                try:
                    ev["chain_end"] = self.now_fn()
                except Exception:  # noqa: BLE001
                    pass
        if error:
            ev["error"] = error
        # taskid/taskids are hoisted so the journal can filter on them
        tid = a.pop("taskid", None)
        if tid is not None:
            ev["taskid"] = tid
        tids = a.pop("taskids", None)
        if tids:
            ev["taskids"] = list(tids)
        if a:
            ev["attrs"] = a
        self.journal.record("span", **ev)
        if self.registry is not None:
            self._h_span.observe(dur, name=sp.name)
            if error:
                self._c_err.inc(name=sp.name)


def idle_gaps(t0: float, t1: float, busy: list[tuple],
              min_gap: float = 1e-3) -> list[tuple]:
    """The stretches of [t0, t1] that no busy interval covers:
    [(start, end, tag)] of at least `min_gap` seconds, `tag` being that
    of the interval whose end opened the gap (None before the first).
    `busy` is [(start, end, tag)]; overlapping intervals count once."""
    gaps, end, tag = [], t0, None
    for a, b, k in sorted(busy, key=lambda iv: iv[:2]):
        if min(a, t1) - end >= min_gap:
            gaps.append((end, min(a, t1), tag))
        if b > end:
            end, tag = b, k
    if t1 - end >= min_gap:
        gaps.append((end, t1, tag))
    return gaps


def task_trace(events: list[dict], taskid: str) -> list[dict]:
    """Span trees for one task from flat journal events.

    Includes every span that names the taskid (directly or via a
    batch-level `taskids` list), all descendants of those spans, and the
    ancestor path to each root — so a `solve.infer` span that only knows
    its bucket still appears under the `job.solve_batch` that knows the
    task. Roots (and children) sort by wall start time.
    """
    spans = [e for e in events if e.get("kind") == "span"
             and "span_id" in e]
    by_id = {e["span_id"]: e for e in spans}

    def matches(e: dict) -> bool:
        return (e.get("taskid") == taskid
                or taskid in (e.get("taskids") or ()))

    include: set[int] = set()
    for e in spans:
        path: list[int] = []
        cur = e
        while cur is not None and cur["span_id"] not in path:
            path.append(cur["span_id"])
            if cur["span_id"] in include or matches(cur):
                include.update(path)
                break
            cur = by_id.get(cur.get("parent_id"))
    # ancestor paths of everything included (context for the tree roots)
    for sid in list(include):
        cur = by_id.get(by_id[sid].get("parent_id"))
        while cur is not None and cur["span_id"] not in include:
            include.add(cur["span_id"])
            cur = by_id.get(cur.get("parent_id"))

    nodes = {sid: dict(by_id[sid], children=[]) for sid in include}
    roots = []
    for sid in sorted(nodes):
        n = nodes[sid]
        parent = nodes.get(n.get("parent_id"))
        if parent is not None:
            parent["children"].append(n)
        else:
            roots.append(n)
    key = lambda n: (n.get("wall_start", 0.0), n["span_id"])  # noqa: E731
    for n in nodes.values():
        n["children"].sort(key=key)
    roots.sort(key=key)
    return roots
