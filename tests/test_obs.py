"""obs subsystem tests — registry semantics, Prometheus exposition,
span nesting, journal ring-buffer eviction, expretry reporting, and the
end-to-end task lifecycle trace through `MinerNode.tick()` on the fake
chain (ISSUE 1 acceptance: /metrics parses, /debug/trace returns the
full span tree, obs overhead stays bounded)."""
from __future__ import annotations

import json
import re
import time
import urllib.error
import urllib.request

import pytest

from arbius_tpu.node import ConfigError, MiningConfig, load_config
from arbius_tpu.node.retry import RetriesExhausted, expretry
from arbius_tpu.obs import (
    EventJournal,
    MetricsRegistry,
    Obs,
    current_obs,
    span,
    task_trace,
    use_obs,
)

from test_node import build_world, drain, submit


# -- registry --------------------------------------------------------------

def test_counter_monotonic_and_get_or_create():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help")
    assert reg.counter("t_total") is c
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert c.value() == 3.5


def test_labeled_counter_and_shape_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("ops_total", labelnames=("op",))
    c.inc(op="a")
    c.inc(op="a")
    c.inc(op="b")
    assert c.value(op="a") == 2 and c.value(op="b") == 1
    with pytest.raises(ValueError):
        c.inc()  # missing declared label
    with pytest.raises(ValueError):
        reg.counter("ops_total", labelnames=())  # shape mismatch
    with pytest.raises(ValueError):
        reg.gauge("ops_total")  # kind mismatch
    h = reg.histogram("h_seconds", buckets=(1.0, 2.0))
    assert reg.histogram("h_seconds", buckets=(1.0, 2.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("h_seconds", buckets=(5.0,))  # bucket mismatch


def test_gauge_set_and_callback():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(7)
    assert g.value() == 7
    box = [3]
    f = reg.gauge("live_depth", fn=lambda: box[0])
    assert f.value() == 3
    box[0] = 9
    assert "live_depth 9" in reg.render()


def test_dead_callback_gauge_does_not_kill_scrape():
    reg = MetricsRegistry()
    reg.counter("survivor_total").inc()
    reg.gauge("dead_depth", fn=lambda: 1 / 0)
    text = reg.render()  # must not raise
    assert "dead_depth NaN" in text
    assert "survivor_total 1" in text


def test_read_paths_do_not_materialize_series():
    reg = MetricsRegistry()
    h = reg.histogram("s_seconds", buckets=(1.0,), labelnames=("stage",))
    assert h.percentile(0.5, stage="infer") is None
    assert h.values(stage="infer") == []
    assert h.count(stage="infer") == 0
    c = reg.counter("r_total", labelnames=("op",))
    assert c.value(op="never") == 0
    text = reg.render()  # no empty series from the reads above
    assert "s_seconds_bucket" not in text
    assert "r_total{" not in text


def test_histogram_bucket_edges_and_percentile():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 10.0):
        h.observe(v)
    text = reg.render()
    # le is inclusive: 1.0 lands in the le="1" bucket, 2.0 in le="2"
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="2"} 4' in text
    assert 'lat_seconds_bucket{le="5"} 4' in text
    assert 'lat_seconds_bucket{le="+Inf"} 5' in text
    assert "lat_seconds_sum 15" in text
    assert "lat_seconds_count 5" in text
    # exact rolling percentiles (numpy 'linear' semantics)
    h2 = reg.histogram("p_seconds", buckets=(1.0,))
    for v in range(1, 11):
        h2.observe(float(v))
    assert h2.percentile(0.5) == pytest.approx(5.5)
    assert h2.percentile(0.95) == pytest.approx(9.55)
    assert reg.histogram("empty_seconds", buckets=(1.0,)).percentile(0.5) \
        is None


def test_histogram_recent_window_bounded_and_tagged():
    reg = MetricsRegistry()
    h = reg.histogram("w_seconds", buckets=(1.0,), recent_window=3)
    for i in range(5):
        h.observe(float(i), tag=f"t{i}")
    assert h.values() == [2.0, 3.0, 4.0]
    assert h.recent() == [("t2", 2.0), ("t3", 3.0), ("t4", 4.0)]
    assert h.count() == 5  # cumulative count unaffected by the window


def test_prometheus_golden_text():
    reg = MetricsRegistry()
    reg.counter("a_total", "things counted").inc(3)
    reg.gauge("b_depth", "queue depth").set(2)
    h = reg.histogram("c_seconds", "span time", buckets=(0.1, 1.0),
                      labelnames=("stage",))
    h.observe(0.05, stage="infer")
    h.observe(0.5, stage="infer")
    assert reg.render() == (
        "# HELP a_total things counted\n"
        "# TYPE a_total counter\n"
        "a_total 3\n"
        "# HELP b_depth queue depth\n"
        "# TYPE b_depth gauge\n"
        "b_depth 2\n"
        "# HELP c_seconds span time\n"
        "# TYPE c_seconds histogram\n"
        'c_seconds_bucket{stage="infer",le="0.1"} 1\n'
        'c_seconds_bucket{stage="infer",le="1"} 2\n'
        'c_seconds_bucket{stage="infer",le="+Inf"} 2\n'
        'c_seconds_sum{stage="infer"} 0.55\n'
        'c_seconds_count{stage="infer"} 2\n')


_PROM_LABEL = r'[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    rf"(\{{{_PROM_LABEL}(,{_PROM_LABEL})*\}})? "
    r"(NaN|[+-]?Inf|[+-]?[0-9.e+-]+)$")


def assert_valid_prometheus(text: str) -> dict:
    """Minimal exposition-format check: every line is a comment or a
    `name{labels} value` sample; histogram buckets are cumulative and
    agree with _count. Returns {sample_line_name: value}."""
    samples = {}
    assert text.endswith("\n")
    for line in text.splitlines():
        if not line or line.startswith("# "):
            continue
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        name_part, value = line.rsplit(" ", 1)
        samples[name_part] = float(value)
    # bucket series must be cumulative, ending at the matching _count
    by_series: dict[str, list[float]] = {}
    for k, v in samples.items():
        if "_bucket{" in k:
            series = k.split("_bucket{")[0] + "{" + ",".join(
                p for p in k.split("{")[1].rstrip("}").split(",")
                if not p.startswith("le=")).rstrip(",")
            by_series.setdefault(series, []).append(v)
    for series, counts in by_series.items():
        assert counts == sorted(counts), f"non-cumulative {series}"
        base, labels = series.split("{", 1)
        labels = labels.rstrip("}").rstrip(",")
        count_key = f"{base}_count" + ("{" + labels + "}" if labels else "")
        assert samples[count_key] == counts[-1]
    return samples


def test_render_parses_as_prometheus():
    reg = MetricsRegistry()
    reg.counter("x_total", labelnames=("op",)).inc(op='we"ird\nname')
    reg.histogram("y_seconds", buckets=(0.5, 1.5)).observe(1.0)
    reg.gauge("z")
    assert_valid_prometheus(reg.render())


# -- journal ---------------------------------------------------------------

def test_journal_ring_buffer_eviction():
    j = EventJournal(capacity=4)
    for i in range(6):
        j.record("e", i=i)
    assert len(j) == 4
    assert j.dropped == 2
    evs = j.events()
    assert [e["i"] for e in evs] == [2, 3, 4, 5]
    assert [e["seq"] for e in evs] == [3, 4, 5, 6]  # seq keeps counting


def test_journal_filters():
    j = EventJournal(capacity=10)
    j.record("span", taskid="0xa")
    j.record("span", taskids=["0xa", "0xb"])
    j.record("retry", op="pin")
    assert len(j.events(kind="retry")) == 1
    assert len(j.events(taskid="0xa")) == 2
    assert len(j.events(taskid="0xb")) == 1
    assert len(j.events(limit=2)) == 2
    assert j.events(limit=0) == []  # not the evs[-0:] = everything trap
    assert j.events(limit=-5) == []


# -- spans -----------------------------------------------------------------

def test_span_nesting_attrs_and_chain_time():
    clock = [100]
    obs = Obs(journal_capacity=64, now_fn=lambda: clock[0])
    with obs.span("outer", taskid="0x1", model="m"):
        clock[0] = 105
        with obs.span("inner", taskid="0x1"):
            pass
    inner, outer = obs.journal.events(kind="span")
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["parent_id"] == outer["span_id"]
    assert outer["parent_id"] is None
    assert outer["attrs"] == {"model": "m"}  # taskid hoisted out of attrs
    assert outer["taskid"] == "0x1"
    assert outer["chain_start"] == 100 and outer["chain_end"] == 105
    assert inner["chain_start"] == 105
    assert outer["wall_s"] >= inner["wall_s"] >= 0
    # span durations feed the registry histogram
    assert obs.registry.histogram(
        "arbius_span_seconds", labelnames=("name",)).count(name="outer") == 1


def test_span_error_status_propagates():
    obs = Obs(journal_capacity=8)
    with pytest.raises(RuntimeError):
        with obs.span("boom", taskid="0x2"):
            raise RuntimeError("kaput")
    (ev,) = obs.journal.events(kind="span")
    assert ev["status"] == "error" and "kaput" in ev["error"]
    assert obs.registry.counter(
        "arbius_span_errors_total", labelnames=("name",)).value(
        name="boom") == 1


def test_ambient_span_noop_without_active_obs():
    assert current_obs() is None
    with span("nobody.listening", taskid="0x3"):
        pass  # must not raise, must not record anywhere
    obs = Obs(journal_capacity=8)
    with use_obs(obs):
        assert current_obs() is obs
        with span("heard", taskid="0x3"):
            pass
    assert current_obs() is None
    assert [e["name"] for e in obs.journal.events(kind="span")] == ["heard"]


def test_disabled_obs_records_nothing_but_counts():
    obs = Obs(journal_capacity=8, enabled=False)
    with use_obs(obs):
        with span("quiet"):
            pass
        obs.event("retry", op="x")
    assert len(obs.journal) == 0
    obs.registry.counter("still_counts_total").inc()
    assert obs.registry.counter("still_counts_total").value() == 1


def test_task_trace_tree_assembly():
    obs = Obs(journal_capacity=64)
    with obs.span("job.solve", taskid="0xaa"):
        with obs.span("solve.batch", taskids=["0xaa", "0xbb"]):
            with obs.span("solve.infer"):  # no taskid: included as child
                pass
    with obs.span("job.other", taskid="0xcc"):
        pass
    roots = task_trace(obs.journal.events(), "0xaa")
    assert [r["name"] for r in roots] == ["job.solve"]
    batch = roots[0]["children"][0]
    assert batch["name"] == "solve.batch"
    assert [c["name"] for c in batch["children"]] == ["solve.infer"]
    # the unrelated task is excluded
    assert task_trace(obs.journal.events(), "0xcc")[0]["name"] == "job.other"
    assert len(task_trace(obs.journal.events(), "0xcc")) == 1


# -- expretry --------------------------------------------------------------

def _always_fail(calls):
    def fn():
        calls.append(1)
        raise ValueError("nope")
    return fn


def test_expretry_default_curve_unchanged():
    sleeps = []
    with pytest.raises(RetriesExhausted):
        expretry(_always_fail([]), tries=5, sleep=sleeps.append)
    assert sleeps == [1.5 ** a for a in range(4)]


def test_expretry_max_delay_caps_backoff():
    sleeps = []
    with pytest.raises(RetriesExhausted):
        expretry(_always_fail([]), tries=10, max_delay=2.0,
                 sleep=sleeps.append)
    assert sleeps[:2] == [1.0, 1.5]
    assert all(s <= 2.0 for s in sleeps)
    assert sleeps[-1] == 2.0  # the cap binds where 1.5**a exceeds it


def test_expretry_reports_into_obs():
    obs = Obs(journal_capacity=32)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ValueError("transient")
        return "ok"

    with use_obs(obs):
        assert expretry(flaky, sleep=lambda s: None, op="pin_files") == "ok"
        with pytest.raises(RetriesExhausted):
            expretry(_always_fail([]), tries=2, sleep=lambda s: None,
                     op="submit_solution")
    c = obs.registry.counter("arbius_retry_attempts_total",
                             labelnames=("op",))
    assert c.value(op="pin_files") == 2
    assert c.value(op="submit_solution") == 2
    assert obs.registry.counter("arbius_retry_exhausted_total",
                                labelnames=("op",)).value(
        op="submit_solution") == 1
    retries = obs.journal.events(kind="retry")
    assert {e["op"] for e in retries} == {"pin_files", "submit_solution"}
    assert retries[0]["attempt"] == 1 and "transient" in retries[0]["error"]
    (exhausted,) = obs.journal.events(kind="retry_exhausted")
    assert exhausted["op"] == "submit_solution"


def test_expretry_counters_survive_disabled_tracing():
    """obs_enabled=False stops span/journal recording only — the
    registry keeps counting (the /metrics contract)."""
    obs = Obs(journal_capacity=8, enabled=False)
    with use_obs(obs):
        with pytest.raises(RetriesExhausted):
            expretry(_always_fail([]), tries=3, sleep=lambda s: None,
                     op="pin_files")
    assert obs.registry.counter("arbius_retry_attempts_total",
                                labelnames=("op",)).value(
        op="pin_files") == 3
    assert len(obs.journal) == 0  # journal stays quiet when disabled


# -- config ----------------------------------------------------------------

def test_config_obs_knobs_validate():
    cfg = load_config(json.dumps({
        "obs_enabled": False, "obs_journal_capacity": 16,
        "retry_max_delay": None}))
    assert cfg.obs_enabled is False
    assert cfg.obs_journal_capacity == 16
    assert cfg.retry_max_delay is None
    assert MiningConfig().retry_max_delay == 30.0
    with pytest.raises(ConfigError):
        MiningConfig(obs_journal_capacity=0)
    with pytest.raises(ConfigError):
        MiningConfig(retry_max_delay=-1.0)


# -- tracer: causes on other threads, after-the-fact spans, one clock -------

def test_span_names_an_explicit_parent_across_threads():
    import threading

    obs = Obs(journal_capacity=16)
    with obs.span("cause") as cause:
        pass

    def worker():
        with use_obs(obs):
            with span("effect", parent=cause.span_id):
                with span("nested"):
                    pass

    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=5)
    assert not t.is_alive()
    by_name = {e["name"]: e for e in obs.journal.events(kind="span")}
    assert by_name["effect"]["parent_id"] == by_name["cause"]["span_id"]
    assert by_name["nested"]["parent_id"] == by_name["effect"]["span_id"]
    # an explicit parent wins over the thread's own stack too
    with obs.span("outer"):
        with obs.span("adopted", parent=cause.span_id):
            pass
    adopted = obs.journal.events(kind="span")[-2]
    assert adopted["name"] == "adopted"
    assert adopted["parent_id"] == cause.span_id


def test_under_lends_a_parent_to_ambient_spans_and_records_nothing():
    from arbius_tpu.obs import under

    obs = Obs(journal_capacity=16)
    with use_obs(obs):
        with span("cause") as cause:
            pass
        with under(cause.span_id):
            with span("library.span"):
                pass
        with under(None):
            with span("orphan"):
                pass
    evs = obs.journal.events(kind="span")
    assert [e["name"] for e in evs] == ["cause", "library.span", "orphan"]
    assert evs[1]["parent_id"] == cause.span_id
    assert evs[2]["parent_id"] is None
    with under(cause.span_id):   # nothing active: a no-op
        pass


def test_record_journals_an_interval_after_the_fact():
    import time

    obs = Obs(journal_capacity=16)
    t0 = time.perf_counter() - 2.0
    with obs.span("root") as root:
        obs.tracer.record("solve.idle", t0, t0 + 0.5, after_chunk=1)
    obs.tracer.record("task.queue_wait", t0, t0 + 1.5, parent=root.span_id,
                      taskid="0x9")
    idle, _, wait = obs.journal.events(kind="span")
    # the same fields as any span's: perfbench's add_journal and
    # task_trace take these
    assert idle["kind"] == "span" and idle["status"] == "ok"
    assert idle["parent_id"] == root.span_id       # the open span
    assert idle["wall_s"] == 0.5 and idle["mono_start"] == t0
    assert idle["attrs"] == {"after_chunk": 1}
    assert idle["wall_start"] == pytest.approx(time.time() - 2.0, abs=0.05)
    assert wait["parent_id"] == root.span_id and wait["taskid"] == "0x9"
    assert wait["span_id"] not in (idle["span_id"], root.span_id)
    assert obs.registry.histogram("arbius_span_seconds",
                                  labelnames=("name",)).count(
        name="solve.idle") == 1
    # task_trace: the recorded span under its parent, like any other
    (tree,) = obs.task_trace("0x9")
    assert tree["name"] == "root"
    assert [n["name"] for n in tree["children"]] == ["task.queue_wait"]


def test_spans_carry_both_clocks_and_stamp_even_when_disabled():
    import time

    obs = Obs(journal_capacity=16)
    m0, w0 = time.perf_counter(), time.time()
    with obs.span("stamped") as sp:
        time.sleep(0.01)
    (ev,) = obs.journal.events(kind="span")
    assert ev["mono_start"] == sp.t0 and m0 <= sp.t0 <= sp.t1
    assert ev["wall_s"] == pytest.approx(sp.t1 - sp.t0, abs=1e-6)
    assert ev["wall_start"] - w0 == pytest.approx(sp.t0 - m0, abs=0.01)
    off = Obs(journal_capacity=16, enabled=False)
    with off.span("quiet") as sp:
        pass
    off.tracer.record("quiet.too", sp.t0, sp.t1)
    assert sp.t1 >= sp.t0 > 0 and len(off.journal) == 0


def test_idle_gaps_are_what_no_busy_interval_covers():
    from arbius_tpu.obs.trace import idle_gaps

    busy = [(1.0, 3.0, 0), (2.0, 5.0, 1), (5.0005, 6.0, 2), (8.0, 9.0, 3)]
    assert idle_gaps(0.0, 10.0, busy) == [
        (0.0, 1.0, None), (6.0, 8.0, 2), (9.0, 10.0, 3)]
    # overlapping chunks count once; a gap under a millisecond is none
    assert idle_gaps(1.0, 6.0, busy) == []
    assert idle_gaps(0.0, 2.0, []) == [(0.0, 2.0, None)]
    # a chunk still busy at the window's end leaves no tail
    assert idle_gaps(0.0, 4.0, [(1.0, 7.0, 0)]) == [(0.0, 1.0, None)]
    # ... and one nested in a longer one does not cut it short
    assert idle_gaps(0.0, 9.0, [(1.0, 8.0, 0), (2.0, 3.0, 1)]) == [
        (0.0, 1.0, None), (8.0, 9.0, 0)]
