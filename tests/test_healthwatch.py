"""healthwatch tier-1 suite (docs/healthwatch.md): the alert state
machine's hysteresis edges, the rule catalog's config plumbing, the
engine over a fake node, the /debug/alerts + /debug/journal surfaces,
and the offline tool (tools/healthwatch.py) against its fixture
goldens. The simnet coverage invariant (SIM113) and the CID on-vs-off
pins live in tests/test_sim.py."""
from __future__ import annotations

import json
import os
import sys
import urllib.request

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))

from arbius_tpu.node.config import AlertsConfig, ConfigError
from arbius_tpu.obs import Obs
from arbius_tpu.obs.healthwatch import (
    RULE_NAMES,
    AlertRule,
    AlertStateMachine,
    HealthWatch,
    default_catalog,
)

FIXDIR = os.path.join(os.path.dirname(__file__), "fixtures")


def _machine(for_ticks: int, resolve_ticks: int = 1) -> AlertStateMachine:
    return AlertStateMachine(
        AlertRule(name="t", summary="t", signal="t",
                  for_ticks=for_ticks),
        resolve_ticks=resolve_ticks)


def _walk(m: AlertStateMachine, actives) -> list:
    out = []
    for i, active in enumerate(actives):
        change = m.step(bool(active), now=i)
        if change is not None:
            out.append(change)
    return out


# -- the state machine's hysteresis edges (the satellite contract) ----------

def test_breach_resolving_at_for_ticks_minus_one_never_fires():
    """A condition active for exactly for_ticks-1 evaluations then
    clear goes ok → pending → ok and NEVER fires."""
    m = _machine(for_ticks=3)
    changes = _walk(m, [1, 1, 0, 0])
    assert changes == [("ok", "pending"), ("pending", "ok")]
    assert all("firing" not in c for c in changes)
    assert m.state == "ok"


def test_sustained_breach_fires_exactly_once():
    m = _machine(for_ticks=3)
    changes = _walk(m, [1, 1, 1, 1, 1, 1])
    # one pending entry, one firing entry — NOT one event per active
    # evaluation (the perf_drift once-per-crossing contract)
    assert changes == [("ok", "pending"), ("pending", "firing")]
    assert m.state == "firing"


def test_firing_resolves_then_returns_to_ok():
    m = _machine(for_ticks=1, resolve_ticks=2)
    changes = _walk(m, [1, 0, 0, 0])
    assert changes == [("ok", "firing"), ("firing", "resolved"),
                       ("resolved", "ok")]
    # resolve_ticks=2: the resolved → ok edge waited 2 quiet evals
    assert m.state == "ok"


def test_flapping_series_journals_one_transition_per_state_change():
    """Alternating condition: every recorded change is a genuine state
    change (no duplicates), and the walk is a legal chain."""
    m = _machine(for_ticks=1, resolve_ticks=1)
    changes = _walk(m, [1, 0, 1, 0, 1])
    assert changes == [("ok", "firing"), ("firing", "resolved"),
                       ("resolved", "firing"), ("firing", "resolved"),
                       ("resolved", "firing")]
    state = "ok"
    for old, new in changes:
        assert old == state and new != old
        state = new


def test_reactivation_from_resolved_respects_hysteresis():
    """With for_ticks > 1 a resolved alert re-arms through pending —
    one blip after resolution does not re-fire."""
    m = _machine(for_ticks=2)
    changes = _walk(m, [1, 1, 0, 1, 0, 0])
    assert changes == [("ok", "pending"), ("pending", "firing"),
                       ("firing", "resolved"), ("resolved", "pending"),
                       ("pending", "ok")]
    assert "firing" not in {new for _, new in changes[3:]}, \
        "one blip after resolution must not re-fire"


# -- catalog / config plumbing ----------------------------------------------

def test_rule_names_match_default_catalog():
    names = tuple(r.name for r in default_catalog(AlertsConfig()))
    assert names == RULE_NAMES
    assert len(set(names)) == len(names)


def test_per_rule_override_reaches_the_machine():
    cfg = AlertsConfig(per_rule={"rpc_degraded": 7})
    by_name = {r.name: r for r in default_catalog(cfg)}
    assert by_name["rpc_degraded"].for_ticks == 7
    assert by_name["pin_degraded"].for_ticks == cfg.for_ticks


def test_alerts_config_validation_one_sentence_errors():
    with pytest.raises(ConfigError, match="unknown rule"):
        AlertsConfig(per_rule={"not_a_rule": 2})
    with pytest.raises(ConfigError, match="for_ticks"):
        AlertsConfig(for_ticks=0)
    with pytest.raises(ConfigError, match="per_rule"):
        AlertsConfig(per_rule={"rpc_degraded": 0})
    with pytest.raises(ConfigError, match="stall_burst"):
        AlertsConfig(stall_burst=0)
    from arbius_tpu.node.config import load_config

    with pytest.raises(ConfigError, match="alerts"):
        load_config('{"alerts": {"bogus_knob": 1}}')
    cfg = load_config('{"alerts": {"enabled": true, '
                      '"per_rule": {"stuck_tick": 2}}}')
    assert cfg.alerts.enabled


def test_example_config_ships_a_validated_alerts_block():
    from arbius_tpu.node.config import load_config

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cfg = load_config(open(os.path.join(
        repo, "MiningConfig.example.json")).read())
    assert cfg.alerts.enabled is False
    assert cfg.alerts.for_ticks == 3 and cfg.alerts.per_rule == {}


# -- the engine over a fake node --------------------------------------------

class _FakeChain:
    def __init__(self):
        self.now = 0


class _FakeDB:
    def __init__(self):
        self.due = []

    def get_jobs(self, now, limit=None):
        return self.due[:limit]


class _FakeNode:
    def __init__(self, obs):
        self.obs = obs
        self.chain = _FakeChain()
        self.db = _FakeDB()
        self.task_feed = None


def _watch(**cfg):
    obs = Obs()
    hw = HealthWatch(obs, AlertsConfig(enabled=True, **cfg))
    return obs, hw, _FakeNode(obs)


def test_quarantine_rule_fires_on_counter_delta():
    obs, hw, node = _watch()
    c = obs.registry.counter("arbius_jobs_failed_total",
                             labelnames=("method",))
    hw.evaluate(node)
    assert hw.states()["job_quarantine"] == "ok"
    c.inc(method="solve")
    node.chain.now = 5
    hw.evaluate(node)
    assert hw.states()["job_quarantine"] == "firing"   # for_ticks=1
    node.chain.now = 10
    hw.evaluate(node)                                  # no new failures
    assert hw.states()["job_quarantine"] == "resolved"
    trans = obs.journal.events(kind="alert_transition")
    assert [(e["prev"], e["state"]) for e in trans] == \
        [("ok", "firing"), ("firing", "resolved")]
    assert obs.registry.counter(
        "arbius_alert_transitions_total",
        labelnames=("alert",)).value(alert="job_quarantine") == 2


def test_stuck_tick_watchdog_uses_chain_time_only():
    obs, hw, node = _watch(stuck_after_seconds=10)
    node.db.due = [object()]
    hw.evaluate(node, 0)                 # t=0: anchors progress
    node.chain.now = 8
    hw.evaluate(node, 0)
    assert hw.states()["stuck_tick"] == "ok"
    node.chain.now = 20                  # 20s with due jobs, no work
    hw.evaluate(node, 0)
    assert hw.states()["stuck_tick"] == "firing"
    node.chain.now = 25
    hw.evaluate(node, 3)                 # progress: jobs processed
    assert hw.states()["stuck_tick"] == "resolved"


def test_unprofitable_streak_needs_consecutive_ticks():
    obs, hw, node = _watch(unprofitable_streak=3)
    c = obs.registry.counter("arbius_tasks_unprofitable_total",
                             labelnames=("model",))
    for now in (1, 2):
        c.inc(model="0xm")
        node.chain.now = now
        hw.evaluate(node)
    assert hw.states()["unprofitable_streak"] == "pending"
    node.chain.now = 3
    hw.evaluate(node)                    # a tick with NO rejects
    assert hw.states()["unprofitable_streak"] == "ok", \
        "the streak must reset — that is the hysteresis edge"
    for now in (4, 5, 6):
        c.inc(model="0xm")
        node.chain.now = now
        hw.evaluate(node)
    assert hw.states()["unprofitable_streak"] == "firing"


def test_pipeline_stall_is_a_storm_threshold_not_backpressure():
    obs, hw, node = _watch(stall_burst=4, for_ticks=1)
    c = obs.registry.counter("arbius_pipeline_stalls_total",
                             labelnames=("stage",))
    c.inc(stage="encode")                # routine backpressure
    hw.evaluate(node)
    assert hw.states()["pipeline_stall"] == "ok"
    c.inc(4, stage="network")            # a storm in one tick
    node.chain.now = 5
    hw.evaluate(node)
    assert hw.states()["pipeline_stall"] == "firing"


def test_crash_recovered_holds_then_resolves():
    obs = Obs()
    hw = HealthWatch(obs, AlertsConfig(enabled=True, crash_hold_ticks=2),
                     recovered=True)
    node = _FakeNode(obs)
    hw.evaluate(node)
    assert hw.states()["crash_recovered"] == "firing"
    node.chain.now = 5
    hw.evaluate(node)
    assert hw.states()["crash_recovered"] == "firing"
    node.chain.now = 10
    hw.evaluate(node)                    # hold expired
    assert hw.states()["crash_recovered"] == "resolved"


def test_slo_rules_use_bucket_estimates():
    from arbius_tpu.node.config import SLOConfig
    from arbius_tpu.obs.registry import CHAIN_SECONDS_BUCKETS

    obs = Obs()
    hw = HealthWatch(obs, AlertsConfig(enabled=True, for_ticks=1),
                     slo=SLOConfig(queue_wait_p95=10.0))
    node = _FakeNode(obs)
    h = obs.registry.histogram("arbius_fleet_queue_wait_seconds",
                               buckets=CHAIN_SECONDS_BUCKETS)
    for _ in range(20):
        h.observe(2.0)
    hw.evaluate(node)
    assert hw.states()["slo_queue_wait"] == "ok"
    for _ in range(80):
        h.observe(500.0)                 # p95 now far above 10s
    node.chain.now = 5
    hw.evaluate(node)
    assert hw.states()["slo_queue_wait"] == "firing"
    # an undeclared objective never evaluates
    assert hw.states()["slo_time_to_commit"] == "ok"


def test_evaluate_never_raises(monkeypatch):
    obs, hw, node = _watch()
    monkeypatch.setattr(hw, "_signals",
                        lambda *a: (_ for _ in ()).throw(RuntimeError()))
    hw.evaluate(node)                    # must not propagate
    assert [e["kind"] for e in obs.journal.events(
        kind="healthwatch_skip")] == ["healthwatch_skip"]


def test_alert_gauges_render_states_and_prometheus_alerts_block():
    obs, hw, node = _watch()
    obs.registry.counter("arbius_jobs_failed_total",
                         labelnames=("method",)).inc(method="solve")
    hw.evaluate(node)
    text = obs.registry.render()
    assert 'arbius_alert_state{alert="job_quarantine"} 2' in text
    assert 'arbius_alert_state{alert="stuck_tick"} 0' in text
    assert ('ALERTS{alertname="job_quarantine",alertstate="firing"} 1'
            in text)
    # every catalog rule is enumerable from the one scrape
    for name in RULE_NAMES:
        assert f'arbius_alert_state{{alert="{name}"}}' in text


# -- node + RPC surfaces ----------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return json.loads(r.read().decode())


@pytest.fixture()
def alert_world():
    from arbius_tpu.node.rpc import ControlRPC

    from test_node import build_world

    eng, tok, chain, node, mid = build_world(
        alerts=AlertsConfig(enabled=True))
    rpc = ControlRPC(node)
    rpc.start()
    yield eng, node, rpc
    rpc.stop()
    node.close()


def test_debug_alerts_endpoint_and_journal_filters(alert_world):
    eng, node, rpc = alert_world
    doc = _get(rpc.port, "/debug/alerts")
    assert doc["enabled"] is True
    assert [a["alert"] for a in doc["alerts"]] == sorted(RULE_NAMES)
    assert all(a["state"] == "ok" for a in doc["alerts"])

    # force a flap: job_quarantine fires, resolves, returns to ok
    c = node.obs.registry.counter("arbius_jobs_failed_total",
                                  labelnames=("method",))
    c.inc(method="x")
    node.tick()
    doc = _get(rpc.port, "/debug/alerts")
    by_name = {a["alert"]: a for a in doc["alerts"]}
    assert by_name["job_quarantine"]["state"] == "firing"
    assert by_name["job_quarantine"]["transitions"] == 1
    eng.advance_time(5)
    node.tick()
    eng.advance_time(5)
    node.tick()

    # /debug/journal?kind=alert_transition: exactly the transition
    # record, in seq (journal) order — test-pinned ordering
    doc = _get(rpc.port, "/debug/journal?kind=alert_transition")
    events = doc["events"]
    assert [e["kind"] for e in events] == ["alert_transition"] * 3
    assert [(e["prev"], e["state"]) for e in events] == \
        [("ok", "firing"), ("firing", "resolved"), ("resolved", "ok")]
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
    # kind + limit compose: limit keeps the NEWEST events post-filter
    doc = _get(rpc.port, "/debug/journal?kind=alert_transition&limit=1")
    assert [(e["prev"], e["state"]) for e in doc["events"]] == \
        [("resolved", "ok")]


def test_debug_journal_taskid_filter_mirrors_trace_semantics(alert_world):
    from arbius_tpu.chain import WAD

    from test_node import drain, submit

    eng, node, rpc = alert_world
    mid = node.registry.ids()[0]
    tid = submit(eng, mid, fee=10 * WAD)
    drain(node)
    doc = _get(rpc.port, f"/debug/journal?taskid={tid}")
    events = doc["events"]
    assert events, "the task's lifecycle journaled nothing"
    assert all(e.get("taskid") == tid or tid in (e.get("taskids") or ())
               for e in events)
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)
    # identical to the journal API the /debug/trace view uses
    assert events == node.obs.journal.events(taskid=tid, limit=200)
    # an unknown task filters to nothing (not an error)
    doc = _get(rpc.port, "/debug/journal?taskid=0x" + "ab" * 32)
    assert doc["events"] == []


# -- tools/healthwatch.py (fixture-goldened) --------------------------------

def make_eval_sidecars(dirpath: str) -> None:
    """A deterministic 3-member sidecar set: worker-0 ends with
    rpc_degraded FIRING and pin_degraded pending, worker-1 is healthy,
    and the coordinator never ran healthwatch (unwatched). Shared by
    the golden test and the golden regeneration snippet in
    tests/fixtures/healthwatch/README.md."""
    from arbius_tpu.obs.fleetscope import ObsSidecar, sidecar_path

    def member(name, build):
        obs = Obs()
        build(obs)
        side = ObsSidecar(sidecar_path(dirpath, name), name, obs)
        side.flush(now=123)
        side.close()

    def worker0(obs):
        hw = HealthWatch(obs, AlertsConfig(enabled=True))
        for now in (100, 105, 110):
            hw._machines["rpc_degraded"].step(True, now)
        hw._machines["pin_degraded"].step(True, 110)
        hw._c_transitions.inc(2, alert="rpc_degraded")
        hw._c_transitions.inc(alert="pin_degraded")

    member("worker-0", worker0)
    member("worker-1",
           lambda obs: HealthWatch(obs, AlertsConfig(enabled=True)))
    member("coordinator", lambda obs: None)


def test_healthwatch_tool_eval_matches_goldens(tmp_path, capsys):
    import healthwatch as hw_tool

    make_eval_sidecars(str(tmp_path))
    rc = hw_tool.main(["--eval", str(tmp_path)])
    out = capsys.readouterr().out
    want = open(os.path.join(FIXDIR, "healthwatch",
                             "eval.golden.txt")).read()
    assert out == want
    assert rc == 1                      # a firing alert fails the audit

    rc = hw_tool.main(["--eval", str(tmp_path), "--json"])
    out = capsys.readouterr().out
    want = open(os.path.join(FIXDIR, "healthwatch",
                             "eval.golden.json")).read()
    assert out == want
    doc = json.loads(out)
    assert [f["rule"] for f in doc["findings"]] == ["HW701"]
    assert doc["findings"][0]["path"] == "worker-0"


def test_healthwatch_tool_eval_is_byte_deterministic(tmp_path, capsys):
    import healthwatch as hw_tool

    outs = []
    for d in ("a", "b"):
        (tmp_path / d).mkdir()
        make_eval_sidecars(str(tmp_path / d))
        hw_tool.main(["--eval", str(tmp_path / d), "--json"])
        outs.append(capsys.readouterr().out)
    assert outs[0] == outs[1]


def test_healthwatch_tool_rules_and_usage(tmp_path, capsys):
    import healthwatch as hw_tool

    assert hw_tool.main(["--rules"]) == 0
    out = capsys.readouterr().out
    for name in RULE_NAMES:
        assert name in out
    assert hw_tool.main([]) == 2
    capsys.readouterr()
    assert hw_tool.main(["--eval", str(tmp_path / "nope")]) == 2


def test_healthwatch_tool_clean_fleet_exits_0(tmp_path, capsys):
    import healthwatch as hw_tool

    from arbius_tpu.obs.fleetscope import ObsSidecar, sidecar_path

    obs = Obs()
    HealthWatch(obs, AlertsConfig(enabled=True))
    side = ObsSidecar(sidecar_path(str(tmp_path), "worker-0"),
                      "worker-0", obs)
    side.flush(now=1)
    side.close()
    assert hw_tool.main(["--eval", str(tmp_path)]) == 0
    assert "0 firing alert(s)" in capsys.readouterr().out
