"""The three readers of the program's own solve-path spans (PR 25), on
hand-built span lists in the form `harness.Run.spans` has: they read the
journal's `solve.*` spans and nothing of the benchmark's `bench.*`
wrappers, and read nothing from a program that lacks the spans."""
from __future__ import annotations

import pytest

from pb_paths import ROOT  # noqa: F401 — puts the repo root on the path

from perfbench import manifest as mf


def _span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs}


class _Run:
    def __init__(self, spans, solutions):
        self.spans, self.solutions = spans, solutions


@pytest.fixture(scope="module")
def readers():
    cell = mf.Cell(mf.DEFAULT_MANIFEST, "k2-768-backlog")
    listed = {m["name"] for m in cell.per_layer()}
    names = ("bucket_busy_s_per_sol", "solve_tail_s_per_sol",
             "chip_idle_s_per_sol")
    assert set(names) <= listed
    return {n: cell.reader(n) for n in names}


def _tick():
    """Two chunks of 4 at depth 2, as a `k2-768-backlog` tick lays them
    out: both dispatched at once, ready 8 s apart; the first chunk's tail
    hidden behind the second, the second's exposed."""
    return [
        _span("solve.pipeline", 0.0, 16.7, n=8),
        _span("solve.dispatch", 0.0, 0.1, n=4, batch=4, chunk=[3, 0]),
        _span("solve.dispatch", 0.1, 0.2, n=4, batch=4, chunk=[3, 1]),
        _span("solve.device_wait", 0.1, 8.0, chunk=[3, 0]),
        _span("solve.device_wait", 0.2, 16.0, chunk=[3, 1]),
        _span("solve.encode", 8.0, 8.5, n=4, codec="png"),
        _span("solve.cid", 8.5, 8.6, n=4, chunk=[3, 0]),
        _span("solve.encode", 16.0, 16.5, n=4, codec="png"),
        _span("solve.cid", 16.5, 16.6, n=4, chunk=[3, 1]),
        _span("solve.pin", 16.6, 16.62, taskid="0x1"),
        _span("solve.commit", 16.62, 16.65, taskid="0x1"),
        _span("solve.reveal", 16.65, 16.7, taskid="0x1"),
        _span("solve.idle", 16.0, 16.7, after_chunk=1),
        # the benchmark's wrappers are in the list too, and are not read
        _span("bench.dispatch", 0.0, 0.1, key=1, batch=4),
        _span("bench.device_wait", 0.1, 99.0, key=1, n=4),
        _span("bench.encode", 8.0, 99.0, n=4),
    ]


def test_bucket_busy_counts_overlapping_chunks_once(readers):
    read = readers["bucket_busy_s_per_sol"]
    # [0.0, 8.0] and [0.1, 16.0] are one busy stretch of 16 s
    assert read(_Run(_tick(), 8)) == pytest.approx(16.0 / 8)
    # a second pass (another generation) with a gap between adds its own
    more = _tick() + [
        _span("solve.dispatch", 20.0, 20.1, n=1, batch=4, chunk=[4, 0]),
        _span("solve.device_wait", 20.1, 24.0, chunk=[4, 0])]
    assert read(_Run(more, 9)) == pytest.approx(20.0 / 9)
    # a wait whose dispatch fell outside the traced window joins nothing
    lone = [_span("solve.device_wait", 0.0, 3.0, chunk=[9, 9])]
    assert read(_Run(lone, 1)) is None


def test_solve_tail_sums_the_programs_spans_on_every_thread(readers):
    read = readers["solve_tail_s_per_sol"]
    assert read(_Run(_tick(), 8)) == pytest.approx(
        (0.5 + 0.1 + 0.5 + 0.1 + 0.02 + 0.03 + 0.05) / 8)


def test_chip_idle_is_the_idle_spans_sum(readers):
    read = readers["chip_idle_s_per_sol"]
    assert read(_Run(_tick(), 8)) == pytest.approx(0.7 / 8)
    two = _tick() + [_span("solve.idle", 30.0, 30.3, after_chunk=None)]
    assert read(_Run(two, 8)) == pytest.approx(1.0 / 8)


@pytest.mark.parametrize("name", ["bucket_busy_s_per_sol",
                                  "solve_tail_s_per_sol",
                                  "chip_idle_s_per_sol"])
def test_readers_read_nothing_without_solutions_or_spans(readers, name):
    read = readers[name]
    assert read(_Run(_tick(), 0)) is None
    # the program before PR 25 on the staged path: solve.dispatch with n
    # and batch only, no device_wait, encode, cid or idle span
    parent = [_span("solve.pipeline", 0.0, 16.7, n=8),
              _span("solve.dispatch", 0.0, 0.1, n=4, batch=4),
              _span("solve.pin", 16.6, 16.62, taskid="0x1"),
              _span("solve.commit", 16.62, 16.65, taskid="0x1"),
              _span("bench.dispatch", 0.0, 0.1, key=1, batch=4),
              _span("bench.device_wait", 0.1, 8.0, key=1, n=4),
              _span("bench.encode", 8.0, 8.5, n=4)]
    assert read(_Run(parent, 8)) is None
    assert read(_Run([], 8)) is None
