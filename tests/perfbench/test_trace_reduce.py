"""The reduction from a trace to numbers, on a small recorded trace."""
from __future__ import annotations

import json
import os

import pytest

import pb_paths  # noqa: F401

from perfbench import trace_reduce as tr
from perfbench.spans import SpanLog, union_seconds


@pytest.fixture(scope="module")
def trace():
    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "small_trace.json")
    with open(path) as f:
        t = json.load(f)
    t["devices"] = {p: {ln: [tuple(e) for e in evs]
                        for ln, evs in lines.items()}
                    for p, lines in t["devices"].items()}
    return t


def test_busy_is_the_union_of_the_operation_line(trace):
    lines = trace["devices"]["/device:TPU:0"]
    ops = tr.op_events(lines)
    assert len(ops) == 8            # the coarser module line is not read
    # 1-5 and 6-9: the overlap of flash 7.0-8.0 and copy 7.5-7.75 counts once
    assert tr.busy_seconds(ops) == pytest.approx(7.0)
    clipped = tr.clip(ops, 2.25, 8.5)
    assert tr.busy_seconds(clipped) == pytest.approx(2.75 + 2.5)
    assert min(s for _, s, _ in clipped) == 2.25


def test_idle_gaps_and_their_names(trace):
    ops = tr.op_events(trace["devices"]["/device:TPU:0"])
    gaps = tr.idle_gaps(ops, 0.5, 10.0)
    assert gaps == [(0.5, 1.0), (5.0, 6.0), (9.0, 10.0)]
    # the profiler's clock is 100 s behind the spans' clock here
    named = tr.name_gaps(gaps, trace["spans"], lambda p: p + 100.0)
    assert named == [["solve.pipeline", pytest.approx(1.5)],
                     ["bench.encode", pytest.approx(1.0)]]
    far = tr.name_gaps([(50.0, 51.0)], trace["spans"], lambda p: p)
    assert far == [["outside any span", pytest.approx(1.0)]]


def test_kernel_sum_and_top_operations(trace):
    ops = tr.op_events(trace["devices"]["/device:TPU:0"])
    flash = tr.kernel_events(ops, r"flash")
    assert sum(d for _, _, d in flash) == pytest.approx(2.5)
    top = dict(tr.top_ops(ops, n=3))
    # the instance numbers are dropped, so the copies of an op add up
    assert top == {"flash_attention": pytest.approx(2.5),
                   "fusion": pytest.approx(2.5),
                   "convolution": pytest.approx(2.0)}
    assert len(tr.top_ops(ops, n=2)) == 2
    assert tr.kernel_events(ops, r"no_such_kernel") == []


def test_a_plane_without_an_op_line_reads_what_is_not_coarser():
    lines = {"Steps": [("step", 0.0, 9.0)], "Stream #1": [("k", 1.0, 2.0)]}
    assert tr.op_events(lines) == [("k", 1.0, 2.0)]


def test_span_log_clips_and_unions():
    log = SpanLog()
    log.add("a", 0.0, 4.0)
    log.add("a", 3.0, 6.0)
    log.add("b", 10.0, 11.0)
    inside = log.within(2.0, 5.0)
    assert [(s["t0"], s["t1"]) for s in inside] == [(2.0, 4.0), (3.0, 5.0)]
    assert union_seconds((s["t0"], s["t1"]) for s in inside) == 3.0
    log.add_journal([{"kind": "span", "name": "solve.pin", "wall_start": 0.0,
                      "wall_s": 0.5, "taskid": "0x1", "attrs": {"n": 1}},
                     {"kind": "job_failed"}])
    pin = [s for s in log.spans if s["name"] == "solve.pin"]
    assert len(pin) == 1 and pin[0]["attrs"] == {"n": 1, "taskid": "0x1"}
    assert pin[0]["t1"] - pin[0]["t0"] == pytest.approx(0.5)


def test_the_traced_window_is_put_on_the_hosts_clock(trace, monkeypatch):
    """The harness's reduction end to end on the recorded trace: the
    device's clock is pinned to the host's by the annotation or by the last
    bucket program's end, whichever leaves more device work inside."""
    from perfbench import harness

    monkeypatch.setattr(tr, "find_xplane", lambda d: d)
    monkeypatch.setattr(tr, "load_xplane", lambda p: {
        "devices": trace["devices"], "host": [tuple(e) for e in trace["host"]]})
    log = SpanLog()
    # host clock = profiler clock + 100; the annotation in the trace is at
    # 0.9, ours at 100.9; the last module ends at 9.0 = host 109.0
    log.add("bench.dispatch", 100.9, 100.95, model="m", batch=4, key=1)
    log.add("bench.device_wait", 100.95, 109.0, n=4, key=1)
    log.add("solve.pipeline", 100.0, 110.0)
    run = harness.Run()
    run.spans = log.within(100.5, 109.5)
    out = harness._reduce_trace(run, "unused", log, 100.5, 109.5)
    assert out["shift"] == pytest.approx(-100.0)
    assert out["busy_s"] == pytest.approx(7.0)
    assert out["window_s"] == pytest.approx(9.0)
    assert out["breakdown"]["device_ops"][0][1] == pytest.approx(2.5)
    assert dict(out["breakdown"]["idle_gaps"]) == {
        "solve.pipeline": pytest.approx(1.0),
        "bench.device_wait": pytest.approx(1.0)}
    # an annotation on another clock loses to the module's end
    monkeypatch.setattr(tr, "load_xplane", lambda p: {
        "devices": trace["devices"], "host": [("bench.dispatch", 500.0, .1)]})
    out = harness._reduce_trace(run, "unused", log, 100.5, 109.5)
    assert out["aligned_by"] == "module_end"
    assert out["busy_s"] == pytest.approx(7.0)
