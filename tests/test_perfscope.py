"""perfscope (docs/perfscope.md) acceptance suite.

The non-negotiable is determinism: CIDs must be byte-identical
perfscope-on vs off — pinned here for the image probe (mesh-off AND
dp2), the video-shaped seq probe, a real tiny SD-1.5 through
solve_cid_batch, and a full simnet clean scenario. Around that: card
capture (XLA cost/memory facts, padding, drift band, persistence,
aotcache header amortization), the byte-deterministic Chrome-trace
export, and the PERF601 auditor's fail-closed behavior on a mispriced
bucket.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "perfscope")


def _scoped_obs(**scope_kw):
    from arbius_tpu.obs import Obs
    from arbius_tpu.obs.perfscope import PerfScope

    obs = Obs(journal_capacity=256)
    obs.perfscope = PerfScope(obs, **scope_kw)
    return obs


# -- CID byte-equality: perfscope on vs off ---------------------------------

def _probe_bytes(probe_cls, scope_on, mesh=None, **probe_kw):
    from arbius_tpu.obs import Obs, use_obs

    obs = _scoped_obs() if scope_on else Obs(journal_capacity=64)
    probe = probe_cls(mesh=mesh, **probe_kw)
    items = [({"prompt": "perf x"}, 7), ({"prompt": "perf y"}, 8)]
    with use_obs(obs):
        out = np.asarray(probe.dispatch(items)).tobytes()
        np.asarray(probe.dispatch(items))  # memory-tier hit
    return out, obs


def test_image_probe_cids_identical_scope_on_off_and_dp2():
    from arbius_tpu.parallel import meshsolve
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe

    off, _ = _probe_bytes(ShardedImageProbe, False)
    on, obs = _probe_bytes(ShardedImageProbe, True)
    assert off == on
    # the card captured at the compile seam, with real XLA statics
    (card,) = obs.perfscope.cards()
    assert card.tag == "meshprobe.img.b2"
    assert card.flops > 0 and card.bytes_accessed > 0
    assert card.compile_seconds > 0 and card.source == "compiled"
    assert card.roofline_s > 0
    # dp2: sharded program, wire bytes land on the card
    mesh = meshsolve.boot_mesh({"dp": 2})
    off2, _ = _probe_bytes(ShardedImageProbe, False, mesh=mesh)
    on2, obs2 = _probe_bytes(ShardedImageProbe, True, mesh=mesh)
    assert off2 == on2
    (card2,) = obs2.perfscope.cards()
    assert card2.wire_bytes.get("dp", 0) > 0


def test_seq_probe_cids_identical_scope_on_off():
    from arbius_tpu.parallel.meshsolve import ShardedSeqProbe

    off, _ = _probe_bytes(ShardedSeqProbe, False)
    on, obs = _probe_bytes(ShardedSeqProbe, True)
    assert off == on
    (card,) = obs.perfscope.cards()
    assert card.tag.startswith("meshprobe.seq.") and card.flops > 0


def test_sd15_cids_identical_scope_on_off():
    """A real (tiny) SD-1.5 solve through solve_cid_batch: perfscope
    off vs on must emit byte-identical (cid, files)."""
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.node.factory import tiny_byte_tokenizer
    from arbius_tpu.node.solver import (
        ModelRegistry,
        RegisteredModel,
        SD15Runner,
        solve_cid_batch,
    )
    from arbius_tpu.obs import Obs, use_obs
    from arbius_tpu.templates.engine import load_template

    cfg = SD15Config.tiny()
    params = SD15Pipeline(
        cfg, tokenizer=tiny_byte_tokenizer(cfg.text)).init_params(
        seed=0, height=64, width=64)
    tmpl = load_template("anythingv3")
    items = [({"prompt": "perf cat", "negative_prompt": "", "width": 64,
               "height": 64, "num_inference_steps": 2,
               "scheduler": "DDIM", "seed": 7}, 7)]

    def life(scope_on: bool):
        pipe = SD15Pipeline(cfg, tokenizer=tiny_byte_tokenizer(cfg.text))
        model = RegisteredModel(id="0x" + "11" * 32, template=tmpl,
                                runner=SD15Runner(pipe, params))
        ModelRegistry().register(model)
        obs = _scoped_obs() if scope_on else Obs(journal_capacity=64)
        with use_obs(obs):
            out = solve_cid_batch(model, items, canonical_batch=1)
        return out, obs

    off, _ = life(False)
    on, obs = life(True)
    assert off == on  # (cid, files) pairs, bytes and all
    (card,) = obs.perfscope.cards()
    assert card.tag.startswith("sd15.") and card.flops > 0
    assert card.arg_bytes > 0 and card.out_bytes > 0


def test_sim_clean_scenario_cids_identical_scope_on_off(tmp_path):
    """Cards must not perturb CIDs through the whole signed-tx node
    path: a clean simnet run perfscope-on matches perfscope-off."""
    from arbius_tpu.sim.harness import run_scenario
    from arbius_tpu.sim.invariants import check_all
    from arbius_tpu.sim.scenario import get_scenario

    def cids(r):
        return {"0x" + t.hex(): "0x" + s.cid.hex()
                for t, s in r.engine.solutions.items()}

    base = run_scenario(get_scenario("clean"), 1, mesh={})
    scoped = run_scenario(get_scenario("clean"), 1, mesh={},
                          perfscope=True)
    for r in (base, scoped):
        findings = check_all(r)
        assert not findings, [f.text() for f in findings]
    assert cids(base) == cids(scoped) and cids(base)


# -- capture / bind / drift --------------------------------------------------

def _captured_scope(**scope_kw):
    """One image-probe dispatch under a fresh scoped obs → (scope, tag)."""
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe

    _, obs = _probe_bytes(ShardedImageProbe, False)  # warm numpy etc.
    obs = _scoped_obs(**scope_kw)
    from arbius_tpu.obs import use_obs

    probe = ShardedImageProbe()
    with use_obs(obs):
        probe.dispatch([({"prompt": "a"}, 1), ({"prompt": "b"}, 2)])
    return obs, "meshprobe.img.b2"


def test_observe_dispatch_binds_accrues_and_journals_drift_on_crossing():
    obs, tag = _captured_scope(drift_min=0.5, drift_max=2.0)
    scope = obs.perfscope
    card = scope.cards()[0]
    roof = card.roofline_s

    def disp(bucket_wall):
        # a 3-real-task bucket at canonical batch 2 = 2 executable
        # dispatches (one padded slot); the observed window stores the
        # PER-DISPATCH wall, so drift is queue-depth-invariant
        return scope.observe_dispatch(
            tag, model="0xmm", bucket="64x64.s2.DDIM.f-",
            layout="single", mode="bf16", batch=2, real=3, padded=1,
            dispatches=2, seconds=bucket_wall)

    assert disp(roof * 2 * 1.0) == pytest.approx(1.0)
    assert obs.journal.events(kind="perf_drift") == []
    # crossing out of band journals ONCE; staying out journals nothing
    # (upper-middle window median: p50 of [1x, 9x] is 9x)
    assert disp(roof * 2 * 9.0) == pytest.approx(9.0)
    disp(roof * 2 * 9.0)
    drifts = obs.journal.events(kind="perf_drift")
    assert len(drifts) == 1
    assert drifts[0]["model"] == "0xmm" and \
        drifts[0]["band"] == [0.5, 2.0]
    card = scope.cards()[0]
    assert card.bound and card.mode == "bf16"
    assert card.dispatches == 6 and card.real_tasks == 9
    assert card.padded_slots == 3
    assert card.padding_waste() == pytest.approx(0.25)
    # the live gauge serves the same ratio, per cost key
    g = obs.registry.get("arbius_perf_drift_ratio")
    val = g.value(model="0xmm", bucket="64x64.s2.DDIM.f-",
                  layout="single", mode="bf16")
    assert val == pytest.approx(card.drift_ratio())
    assert obs.registry.get("arbius_perf_cards").value() == 1.0


def test_dirty_rows_persist_and_reload_through_nodedb(tmp_path):
    from arbius_tpu.node.db import NodeDB

    obs, tag = _captured_scope()
    scope = obs.perfscope
    # unbound cards never persist
    assert scope.dirty_rows(5) == []
    scope.observe_dispatch(tag, model="0xmm", bucket="b", layout="single",
                           mode="bf16", batch=2, real=2, padded=0,
                           seconds=0.5)
    rows = scope.dirty_rows(7)
    assert len(rows) == 1 and rows[0][:4] == ("0xmm", "b", "single",
                                              "bf16")
    assert scope.dirty_rows(8) == []  # drained
    db = NodeDB(str(tmp_path / "n.sqlite"))
    try:
        db.upsert_perf_cards(rows)
        loaded = db.load_perf_cards()
    finally:
        db.close()
    ((model, bucket, layout, mode, card, updated),) = loaded
    assert (model, bucket, layout, mode, updated) == \
        ("0xmm", "b", "single", "bf16", 7)
    assert card["flops"] > 0 and card["observed_p50_seconds"] == 0.5


def test_capture_failure_degrades_to_lazy_path():
    """A broken aot_args thunk must fall back to the exact pre-perfscope
    contract: lazy callable, warm=False, skip counted + journaled."""
    from arbius_tpu.obs import jit_cache_get, use_obs

    obs = _scoped_obs()
    cache: dict = {}
    built = []

    def build():
        built.append(1)
        return lambda x: x + 1  # not jittable via .lower — irrelevant

    def bad_args():
        raise RuntimeError("no operands today")

    with use_obs(obs):
        fn, warm, tag = jit_cache_get(cache, "k", build, tag="t.b1",
                                      aot_args=bad_args)
    assert warm is False and built == [1] and cache["k"] is fn
    assert fn(1) == 2
    assert obs.registry.counter(
        "arbius_perf_capture_skips_total").value() == 1
    assert obs.journal.events(kind="perf_capture_skip")


def test_aot_header_perf_block_and_disk_amortization(tmp_path):
    """Cold life publishes the card's perf block into the entry header;
    a warm life's disk-hit card adopts the ORIGINAL compile cost
    (source=disk) — the cross-life amortization seam."""
    from arbius_tpu.aotcache import AotCache, read_header, scan
    from arbius_tpu.obs import use_obs
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe

    d = str(tmp_path / "cache")
    items = [({"prompt": "amort"}, 3), ({"prompt": "izer"}, 4)]

    def life():
        obs = _scoped_obs()
        obs.aot_cache = AotCache(d)
        with use_obs(obs):
            ShardedImageProbe().dispatch(items)
        return obs

    cold = life()
    (cold_card,) = cold.perfscope.cards()
    assert cold_card.source == "compiled" and \
        cold_card.compile_seconds > 0
    ((_, path, _),) = scan(d)
    perf = read_header(path)["perf"]
    assert perf["flops"] == cold_card.flops
    assert perf["compile_seconds"] == pytest.approx(
        cold_card.compile_seconds, abs=1e-6)
    warm = life()
    (warm_card,) = warm.perfscope.cards()
    assert warm.registry.counter("arbius_aot_cache_loads_total"
                                 ).value() == 1
    assert warm_card.source == "disk"
    assert warm_card.compile_seconds == perf["compile_seconds"]
    assert warm_card.flops == cold_card.flops


# -- chrome trace ------------------------------------------------------------

def _fixture_events():
    with open(os.path.join(FIXTURES, "journal.json")) as f:
        return json.load(f)["events"]


def test_chrome_trace_golden_bytes_and_schema():
    from arbius_tpu.obs.perfscope import chrome_trace, render_chrome_trace

    events = _fixture_events()
    got = render_chrome_trace(events)
    with open(os.path.join(FIXTURES, "trace.golden.json")) as f:
        assert got == f.read()
    doc = json.loads(got)
    assert doc["displayTimeUnit"] == "ms"
    names = set()
    for ev in doc["traceEvents"]:
        assert ev["ph"] in ("M", "X", "i")
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
        assert "name" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 1 and ev["ts"] >= 0
        names.add(ev["name"])
    # one process row per member; lifecycle instants ride task tracks
    members = {e["args"]["name"] for e in doc["traceEvents"]
               if e["ph"] == "M"}
    assert members == {"coord", "w1", "w2"}
    assert {"solve.batch", "lease_hop", "gate_decision",
            "perf_drift"} <= names
    w2 = next(e["pid"] for e in doc["traceEvents"]
              if e["ph"] == "M" and e["args"]["name"] == "w2")
    stage = [e for e in doc["traceEvents"] if e["ph"] == "i"
             and e["pid"] == w2 and e["name"] == "pipeline_stage"]
    root = [e for e in doc["traceEvents"] if e["ph"] == "X"
            and e["pid"] == w2 and e["name"] == "solve.batch"]
    assert stage and root and stage[0]["tid"] == root[0]["tid"]
    # pure: same events, same bytes
    assert render_chrome_trace(list(events)) == got
    assert chrome_trace([]) == {"displayTimeUnit": "ms",
                                "traceEvents": []}


def _tool(argv, capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import perfscope as tool
    finally:
        sys.path.pop(0)
    rc = tool.main(argv)
    out = capsys.readouterr()
    return rc, out.out, out.err


def test_chrome_trace_cli_matches_golden(capsys):
    rc, out, _ = _tool(
        ["--chrome-trace", os.path.join(FIXTURES, "journal.json")],
        capsys)
    assert rc == 0
    with open(os.path.join(FIXTURES, "trace.golden.json")) as f:
        assert out == f.read()


def test_chrome_trace_cli_usage_errors(capsys):
    rc, _, err = _tool(["--chrome-trace"], capsys)
    assert rc == 2 and "--fleet" in err
    rc, _, err = _tool([], capsys)
    assert rc == 2 and "--db" in err


# -- PERF601 auditor ---------------------------------------------------------

def _audit_db(tmp_path, chip_seconds: float, card_overrides=None):
    """A node db with one bound card + one fitted cost row joined on
    the shared (model, bucket, layout, mode) tag."""
    from arbius_tpu.node.db import NodeDB

    card = {"tag": "sd15.2.64.64.2.DDIM", "model": "0xmm", "bucket": "b",
            "layout": "single", "mode": "bf16", "batch": 2,
            "flops": 1e9, "bytes_accessed": 1e8, "arg_bytes": 10,
            "out_bytes": 10, "temp_bytes": 0, "code_bytes": 0,
            "compile_seconds": 0.5, "source": "compiled",
            "roofline_seconds": 0.001, "dispatches": 4, "real_tasks": 8,
            "padded_slots": 0, "padding_waste": 0.0,
            "amortized_compile_seconds": 0.125, "wire_bytes": {},
            "drift_ratio": 1.0, "observed_p50_seconds": 0.001}
    card.update(card_overrides or {})
    path = str(tmp_path / "audit.sqlite")
    db = NodeDB(path)
    try:
        db.upsert_perf_cards([("0xmm", "b", "single", "bf16",
                               json.dumps(card, sort_keys=True), 9)])
        db.upsert_cost_rows([("0xmm", "b", "single", "bf16",
                              chip_seconds, 16, 9)])
    finally:
        db.close()
    return path


def test_perf601_clean_and_fail_closed(tmp_path, capsys):
    # consistent: fitted 2 × 0.0005 s/task = 0.001 s bucket = roofline
    clean = _audit_db(tmp_path, 0.0005)
    rc, out, _ = _tool(["--db", clean], capsys)
    assert rc == 0 and "within the drift band" in out
    # mispriced: the fitted row claims 100× the roofline — PERF601,
    # exit 1, even though the card's own observed window looked fine
    (tmp_path / "m").mkdir()
    bad = _audit_db(tmp_path / "m", 0.05)
    rc, out, _ = _tool(["--db", bad], capsys)
    assert rc == 1 and "PERF601" in out and "fitted-row" in out
    # observed-window drift fails too
    (tmp_path / "w").mkdir()
    wobbly = _audit_db(tmp_path / "w", 0.0005,
                       card_overrides={"drift_ratio": 7.5})
    rc, out, _ = _tool(["--db", wobbly], capsys)
    assert rc == 1 and "observed-window" in out
    # a widened band absolves it; --json is the standard document
    rc, out, _ = _tool(["--db", wobbly, "--drift-max", "10"], capsys)
    assert rc == 0
    rc, out, _ = _tool(["--db", bad, "--json"], capsys)
    assert rc == 1
    doc = json.loads(out)
    assert doc["findings"][0]["rule"] == "PERF601"
    assert doc["findings"][0]["snippet"] == "0xmm|b|single|bf16"


def test_costmodel_dump_joins_cards(tmp_path, capsys):
    """tools/costmodel.py --dump grows the perf columns when the db has
    cards, and renders the historic table byte-for-byte when not."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import costmodel as cm_tool
    finally:
        sys.path.pop(0)
    from arbius_tpu.node.db import NodeDB

    bare = str(tmp_path / "bare.sqlite")
    db = NodeDB(bare)
    db.upsert_cost_rows([("0xmm", "b", "single", "bf16", 0.25, 16, 9)])
    db.close()
    rows = cm_tool.load_db_rows(bare)
    assert "flops" not in rows[0]
    out = cm_tool.render_rows(rows)
    assert "flops" not in out and "chip_seconds" in out
    joined = _audit_db(tmp_path, 0.0005)
    rows = cm_tool.load_db_rows(joined)
    assert rows[0]["flops"] == 1e9
    assert rows[0]["utilization"] == 1.0  # roofline == fitted bucket wall
    table = cm_tool.render_rows(rows)
    assert "flops" in table and "utilization" in table


# -- node integration --------------------------------------------------------

def _mini_node(tmp_path, *, perfscope=True, drift_max=0.0):
    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.node import (
        LocalChain,
        MinerNode,
        MiningConfig,
        ModelConfig,
        ModelRegistry,
        RegisteredModel,
    )
    from arbius_tpu.node.config import PerfscopeConfig
    from arbius_tpu.parallel.meshsolve import ShardedImageProbe
    from arbius_tpu.templates.engine import load_template

    tok = TokenLedger()
    eng = Engine(tok, start_time=10_000)
    tok.mint(Engine.ADDRESS, 600_000 * WAD)
    miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
    for a in (miner, user):
        tok.mint(a, 10**6 * WAD)
        tok.approve(a, Engine.ADDRESS, 10**30)
    mid = "0x" + eng.register_model(user, user, 0, b"{}").hex()
    registry = ModelRegistry()
    registry.register(RegisteredModel(
        id=mid, template=load_template("anythingv3"),
        runner=ShardedImageProbe()))
    chain = LocalChain(eng, miner)
    chain.validator_deposit(100 * WAD)
    node = MinerNode(
        chain,
        MiningConfig(models=(ModelConfig(id=mid, template="anythingv3"),),
                     db_path=str(tmp_path / "node.sqlite"),
                     canonical_batch=2, compile_cache=False,
                     perfscope=PerfscopeConfig(enabled=perfscope,
                                               drift_max=drift_max)),
        registry)
    node.boot(skip_self_test=True)
    return node, eng, user, mid


def test_node_binds_cards_and_persists_in_tick_window(tmp_path):
    node, eng, user, mid = _mini_node(tmp_path)
    try:
        # 3 tasks at canonical_batch 2 → 2 chunks, 1 padded slot
        for i in range(3):
            eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]), 0,
                            json.dumps({"prompt": f"p{i}",
                                        "negative_prompt": ""},
                                       sort_keys=True).encode())
        for _ in range(64):
            if node.tick() == 0:
                break
        assert len(eng.solutions) == 3
        scope = node.obs.perfscope
        (card,) = scope.cards()
        assert card.bound and card.model == mid
        assert card.layout == "single" and card.mode == "bf16"
        assert card.batch == 2
        assert card.real_tasks == 3 and card.padded_slots == 1
        assert card.flops > 0
        rows = node.db.load_perf_cards()
        assert len(rows) == 1 and rows[0][0] == mid
        # the persisted card is the live card's JSON
        assert rows[0][4]["padding_waste"] == pytest.approx(0.25)
    finally:
        node.close()


def test_debug_costmodel_view_joins_perf(tmp_path):
    from arbius_tpu.node.rpc import ControlRPC

    node, eng, user, mid = _mini_node(tmp_path)
    try:
        for i in range(4):
            eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]), 0,
                            json.dumps({"prompt": f"q{i}",
                                        "negative_prompt": ""},
                                       sort_keys=True).encode())
            for _ in range(64):
                if node.tick() == 0:
                    break
        # accrue enough samples for a fitted row, then refit
        node._ingest_costs()
        rpc = ControlRPC.__new__(ControlRPC)
        rpc.node = node
        code, doc = rpc.debug_view("/debug/costmodel")
        assert code == 200
        assert doc["perfscope"]["cards"]
        rows = doc["cost_model"]["rows"]
        assert rows, "no fitted rows accrued"
        perf = rows[0].get("perf")
        assert perf and perf["flops"] > 0
        assert "roofline_seconds" in perf and "utilization" in perf
    finally:
        node.close()


def test_debug_trace_inlines_lifecycle_events_in_seq_order():
    """/debug/trace returns the task's non-span journal events inline,
    ordered — gate/cost decisions and pipeline stages in one view."""
    from arbius_tpu.node.rpc import ControlRPC
    from arbius_tpu.obs import Obs

    obs = Obs(journal_capacity=64)
    obs.event("gate_decision", taskid="0xt", verdict="accept")
    with obs.span("solve.batch", taskids=["0xt"]):
        pass
    obs.event("pipeline_stage", taskid="0xt", stage="solve", rank=0)
    obs.event("pipeline_stage", taskid="0xother", stage="solve", rank=0)
    obs.event("pipeline_stage", taskid="0xt", stage="encode", rank=1)
    obs.event("pipeline_stage", taskid="0xt", stage="reveal", rank=4)

    class _Stub:
        pass

    node = _Stub()
    node.obs = obs
    rpc = ControlRPC.__new__(ControlRPC)
    rpc.node = node
    code, doc = rpc.debug_view("/debug/trace?taskid=0xt")
    assert code == 200
    assert doc["spans"], "span trees still served"
    kinds = [(e["kind"], e.get("stage")) for e in doc["events"]]
    assert kinds == [("gate_decision", None), ("pipeline_stage", "solve"),
                     ("pipeline_stage", "encode"),
                     ("pipeline_stage", "reveal")]
    seqs = [e["seq"] for e in doc["events"]]
    assert seqs == sorted(seqs)
    assert all(e.get("taskid") == "0xt" for e in doc["events"])


def test_perfscope_config_validation():
    from arbius_tpu.node.config import ConfigError, load_config

    with pytest.raises(ConfigError):
        load_config('{"perfscope": {"drift_min": -1}}')
    with pytest.raises(ConfigError):
        load_config('{"perfscope": {"drift_min": 2.0, "drift_max": 1.0}}')
    with pytest.raises(ConfigError):
        load_config('{"perfscope": {"peak_flops": -5}}')
    with pytest.raises(ConfigError):
        load_config('{"perfscope": {"nope": 1}}')
    cfg = load_config('{"perfscope": {"enabled": true, '
                      '"drift_min": 0.5, "drift_max": 2.0}}')
    assert cfg.perfscope.enabled and cfg.perfscope.drift_max == 2.0
    with open(os.path.join(REPO, "MiningConfig.example.json")) as f:
        example = load_config(f.read())
    assert example.perfscope.enabled is False
