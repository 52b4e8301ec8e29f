"""Benchmark: solutions/hour/chip on the anythingv3 task shape.

Metric config (BASELINE.md): SD-1.5 at 512×512, 20 denoise steps,
DPMSolverMultistep, CFG — the anythingv3 queue's shape. Weights are
deterministically random (init_params); FLOPs and memory traffic are
identical to converted weights, so throughput is representative.

`python bench.py` runs the TPU session IN THIS PROCESS — one process
holds the chip — and exits non-zero when the backend is not a TPU or
any stage raises. There is no CPU fallback and an older result is never
re-emitted: a measurement path that finds no chip fails. Stages, one JSON
line each on stdout (and appended to `--out`) the moment it exists:

  tiny          tiny topology, 128×128×4 — proves the chip executes
                end-to-end in ~a minute; no perf claim (vs_baseline 0).
  prod4         full 860M topology at 512×512, measured 4-step,
                extrapolated ×5 to the 20-step metric (conservative:
                fixed text/VAE overhead is re-counted 5×).
  prod20        the real metric — 512×512, 20 steps, measured.
  prod20_bf16   same, bf16 weights (the production configuration).
  sweep_bN      canonical-batch throughput curve, batch ∈ {2,4,8},
                bf16 — the single-chip half of the dp story.
  headline      re-emits the BEST measured solutions/hour LAST.
  goldens       if time remains: record-golden vectors on this chip at
                the production shape, written into goldens/ (the boot
                self-test admission vectors — miner/src/index.ts:984).

The session keeps an internal deadline (BENCH_SESSION_BUDGET_S minus a
margin) and SKIPS stages it has no time left to finish. Param init +
dtype casts each run as one jitted program.

`python bench.py --stage <name>` runs one of the CPU A/B stages
(pipeline_ab, mesh_ab, sched_ab, flood, coldboot, quant_ab, text_ab) on
forced CPU devices: counts and sanity ratios, never device metrics.

`vs_baseline` is measured against ~1800 solutions/hour for the single-A100
cog miner the reference requires (docs/src/pages/mining.mdx:7-19). That
anchor is this repo's ESTIMATE (~2 s/solution end-to-end at 512×512×20);
the reference itself publishes no numbers (BASELINE.md: `published:{}`).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

A100_SOLUTIONS_PER_HOUR_EST = 1800.0  # builder's estimate — see docstring

WIDTH = HEIGHT = 512
STEPS = 20
SCHEDULER = "DPMSolverMultistep"
METRIC = "anythingv3_solutions_per_hour_per_chip"
BASELINE_NOTE = ("anchor 1800 sol/h/A100 is this repo's estimate; "
                 "reference publishes no numbers")

# The session skips a stage it cannot finish before this budget (a chip
# call is time-limited from outside; a stage cut off mid-compile wastes
# the call).
SESSION_BUDGET_S = int(os.environ.get("BENCH_SESSION_BUDGET_S", "3300"))
SESSION_MARGIN_S = int(os.environ.get("BENCH_SESSION_MARGIN_S", "150"))

_T0 = time.perf_counter()
_REPO = os.path.dirname(os.path.abspath(__file__))


def _note(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - _T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# stages
# ---------------------------------------------------------------------------

def _Heartbeat(stage: str):
    """Phase heartbeat (arbius_tpu/utils/session.py), bound to this
    module's stderr note stream."""
    from arbius_tpu.utils.session import Heartbeat

    return Heartbeat(stage, _note)


def _emit(out_path: str, line: dict) -> None:
    with open(out_path, "a") as f:
        f.write(json.dumps(line) + "\n")
        f.flush()
        os.fsync(f.fileno())
    print(json.dumps(line), flush=True)


def _perf_cards(node) -> list | None:
    """PerfCard snapshots for a bench mode block (docs/perfscope.md):
    flops/bytes/padding/roofline context next to the sol/h numbers —
    None when the node ran without perfscope."""
    scope = node.obs.perfscope
    return scope.snapshot()["cards"] if scope is not None else None


def _write_bench_r14(stage: str, platform: str, line: dict) -> None:
    """Merge one stage's perfscope-annotated line into BENCH_r14.json —
    the round-14 record: the same stage lines as their historic round
    files, now carrying PerfCard snapshots per mode/layout."""
    path = os.path.join(_REPO, "BENCH_r14.json")
    doc = {"ok": True, "round": 14, "stages": {}}
    try:
        with open(path) as f:
            prev = json.load(f)
        if isinstance(prev.get("stages"), dict):
            doc["stages"] = prev["stages"]
    except (OSError, ValueError):
        pass
    doc["stages"][stage] = {"platform": platform, "result": line}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    _note(f"{stage}: merged into BENCH_r14.json")


def _timed_solutions(pipe, params, batch: int, *, width: int, height: int,
                     steps: int, rounds: int, hb: _Heartbeat) -> float:
    """Compile + warm up one bucket, then time `rounds` runs.
    Returns seconds per solution."""
    import numpy as np

    kw = dict(width=width, height=height, num_inference_steps=steps,
              scheduler=SCHEDULER, guidance_scale=12.0)
    prompts = [f"arbius bench task {i}" for i in range(batch)]
    negs = [""] * batch
    hb.set(f"compile+warmup {width}x{height} steps={steps} batch={batch}")
    out = pipe.generate(params, prompts, negs, list(range(batch)), **kw)
    assert out.shape == (batch, height, width, 3) and out.dtype == np.uint8
    hb.set(f"timing {rounds} round(s) of {width}x{height} steps={steps} "
           f"batch={batch}")
    t0 = time.perf_counter()
    for r in range(rounds):
        pipe.generate(params, prompts, negs,
                      [(r + 1) * batch + i for i in range(batch)], **kw)
        _note(f"round {r + 1}/{rounds} done")
    return (time.perf_counter() - t0) / (rounds * batch)


def _child_common(cpu: bool, n_devices: int = 1, compile_cache: bool = True):
    if cpu:
        from arbius_tpu.utils import force_cpu_devices

        force_cpu_devices(n_devices)
    import jax

    if compile_cache:
        from arbius_tpu.utils import enable_compile_cache

        enable_compile_cache()
    devs = jax.devices()
    _note(f"platform={devs[0].platform} n_dev={len(devs)}")
    return devs


def _stage_pipeline_ab(out_path: str) -> None:
    """pipeline_ab on the tiny topology, forced CPU (see _pipeline_ab)."""
    hb = _Heartbeat("pipeline_ab")
    devs = _child_common(cpu=True)

    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.node.factory import tiny_byte_tokenizer

    cfg = SD15Config.tiny()
    pipe = SD15Pipeline(cfg, tokenizer=tiny_byte_tokenizer(cfg.text))
    hb.set("init_params (tiny)")
    params = pipe.init_params(seed=0, height=128, width=128)
    _pipeline_ab(out_path, pipe, params, devs[0].platform, hb)
    hb.stop()


def _pipeline_ab(out_path: str, pipe, params, platform: str, hb) -> None:
    """pipeline_ab sub-stage (docs/pipeline.md): the REAL MinerNode tick
    loop drives the same tiny solves with the staged executor OFF then
    ON, reporting chip-idle seconds and solutions/hour per mode plus the
    obs registry snapshot (stage queue depths, chip-idle counter). CPU
    sanity numbers only — clearly labeled, no perf claim."""
    import json as _json

    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.node import (
        LocalChain,
        MinerNode,
        MiningConfig,
        ModelConfig,
        ModelRegistry,
        RegisteredModel,
        SD15Runner,
    )
    from arbius_tpu.node.config import PipelineConfig
    from arbius_tpu.node.solver import solve_cid_batch
    from arbius_tpu.templates.engine import hydrate_input, load_template

    N, BATCH = 8, 2
    tmpl = load_template("anythingv3")
    raw = {"prompt": "pipeline ab warmup", "negative_prompt": "",
           "width": 128, "height": 128, "num_inference_steps": 4}
    hb.set("pipeline_ab: warmup compile (tiny batch=2)")
    warm_model = RegisteredModel(id="0x" + "00" * 32, template=tmpl,
                                 runner=SD15Runner(pipe, params))
    hyd = hydrate_input(dict(raw), tmpl)
    # both modes then run warm executables — the A/B compares schedules,
    # not compile luck
    solve_cid_batch(warm_model, [(hyd, 1), (hyd, 2)], canonical_batch=BATCH)

    def run_mode(pcfg: PipelineConfig, label: str) -> dict:
        tok = TokenLedger()
        eng = Engine(tok, start_time=10_000)
        tok.mint(Engine.ADDRESS, 600_000 * WAD)
        miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
        for a in (miner, user):
            tok.mint(a, 1_000 * WAD)
            tok.approve(a, Engine.ADDRESS, 10**30)
        mid = "0x" + eng.register_model(user, user, 0, b"{}").hex()
        registry = ModelRegistry()
        registry.register(RegisteredModel(
            id=mid, template=tmpl, runner=SD15Runner(pipe, params)))
        chain = LocalChain(eng, miner)
        chain.validator_deposit(100 * WAD)
        node = MinerNode(
            chain,
            MiningConfig(models=(ModelConfig(id=mid,
                                             template="anythingv3"),),
                         canonical_batch=BATCH, compile_cache=False,
                         pipeline=pcfg),
            registry)
        node.boot(skip_self_test=True)
        while node.tick():
            pass
        for i in range(N):
            eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]), 0,
                            _json.dumps(dict(raw, prompt=f"ab task {i}"),
                                        sort_keys=True).encode())
        hb.set(f"pipeline_ab: {label} mode ({N} solves)")
        t0 = time.perf_counter()
        for _ in range(64):
            if node.tick() == 0:
                break
        elapsed = time.perf_counter() - t0
        assert len(eng.solutions) == N, f"{label}: {len(eng.solutions)}/{N}"
        reg = node.obs.registry
        snap = {k: v for k, v in reg.summary().items()
                if k.startswith(("arbius_pipeline_", "arbius_chip_idle",
                                 "arbius_db_commit", "arbius_stage_"))}
        out = {
            "solutions": N,
            "seconds": round(elapsed, 3),
            "solutions_per_hour": round(3600.0 * N / elapsed, 2),
            "chip_idle_seconds": round(
                reg.counter("arbius_chip_idle_seconds_total").value(), 4),
            "obs": snap,
        }
        node.close()
        return out

    on_cfg = PipelineConfig(enabled=True, depth=2, encode_workers=2,
                            max_inflight_pins=2)
    # one discarded pass per mode first: tiny CPU solves are ~50 ms, so
    # cache/allocator warmth would otherwise dominate the comparison
    run_mode(PipelineConfig(), "off-warm")
    run_mode(on_cfg, "on-warm")
    off = run_mode(PipelineConfig(), "off")
    on = run_mode(on_cfg, "on")
    _emit(out_path, {
        "metric": "pipeline_ab_tiny_solutions_per_hour",
        "value": on["solutions_per_hour"],
        "unit": (f"solutions/hour (TINY 128x128x4 through the full node "
                 f"tick loop, canonical_batch={BATCH}, platform="
                 f"{platform} — CPU A/B sanity, no perf claim)"),
        "vs_baseline": 0.0,
        "note": "pipeline_ab: staged executor on vs off, same bytes",
        "stage": "pipeline_ab",
        "modes": {"off": off, "on": on},
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    })


def _stage_mesh_ab(out_path: str) -> None:
    """mesh_ab stage (docs/multichip.md): the REAL node tick loop solves
    the same bucket at mesh-off, dp2, and dp2·tp2 over 8 forced CPU
    devices — config → build_registry (boot_mesh + fused sharded init)
    → MinerNode → staged pipeline — reporting sol/h, chip-idle seconds,
    and per-stage p50/p95 from the obs registry per layout, plus the
    determinism cross-check (off == dp2 CIDs bitwise; dp2·tp2 is its own
    golden-pinned class). CPU sanity numbers only, no perf claim; the
    result also lands in MULTICHIP_r06.json at the repo root."""
    import json as _json

    hb = _Heartbeat("mesh_ab")
    devs = _child_common(cpu=True, n_devices=8)
    platform = devs[0].platform

    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.node import LocalChain, MinerNode, MiningConfig, ModelConfig
    from arbius_tpu.node.config import PipelineConfig
    from arbius_tpu.node.factory import build_registry

    N, BATCH = 8, 2
    raw = {"prompt": "mesh ab warmup", "negative_prompt": "",
           "width": 128, "height": 128, "num_inference_steps": 2}

    def run_mode(mesh_cfg, label: str) -> dict:
        tok = TokenLedger()
        eng = Engine(tok, start_time=10_000)
        tok.mint(Engine.ADDRESS, 600_000 * WAD)
        miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
        for a in (miner, user):
            tok.mint(a, 1_000 * WAD)
            tok.approve(a, Engine.ADDRESS, 10**30)
        mid = "0x" + eng.register_model(user, user, 0, b"{}").hex()
        cfg = MiningConfig(
            models=(ModelConfig(id=mid, template="anythingv3", tiny=True),),
            canonical_batch=BATCH, compile_cache=False, mesh=mesh_cfg,
            pipeline=PipelineConfig(enabled=True, depth=2,
                                    encode_workers=2, max_inflight_pins=2))
        hb.set(f"mesh_ab: {label} boot (registry + sharded init)")
        registry = build_registry(cfg)
        chain = LocalChain(eng, miner)
        chain.validator_deposit(100 * WAD)
        node = MinerNode(chain, cfg, registry)
        node.boot(skip_self_test=True)
        while node.tick():
            pass
        for i in range(N):
            eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]), 0,
                            _json.dumps(dict(raw, prompt=f"mesh task {i}"),
                                        sort_keys=True).encode())
        hb.set(f"mesh_ab: {label} ({N} solves)")
        t0 = time.perf_counter()
        for _ in range(64):
            if node.tick() == 0:
                break
        elapsed = time.perf_counter() - t0
        assert len(eng.solutions) == N, f"{label}: {len(eng.solutions)}/{N}"
        reg = node.obs.registry
        h = reg.get("arbius_stage_seconds")  # node-registered buckets
        stages = h.summary() if h is not None else {}
        out = {
            "mesh": mesh_cfg,
            "mesh_devices": int(
                reg.gauge("arbius_mesh_devices").value()),
            "solutions": N,
            "seconds": round(elapsed, 3),
            "solutions_per_hour": round(3600.0 * N / elapsed, 2),
            "chip_idle_seconds": round(
                reg.counter("arbius_chip_idle_seconds_total").value(), 4),
            "collective_bytes": reg.counter(
                "arbius_collective_bytes_total",
                labelnames=("axis",)).summary(),
            "stage_seconds": stages,
            "cids": {"0x" + t.hex(): "0x" + s.cid.hex()
                     for t, s in eng.solutions.items()},
        }
        node.close()
        return out

    modes = {}
    for label, mesh_cfg in (("off", None), ("dp2", {"dp": 2}),
                            ("dp2tp2", {"dp": 2, "tp": 2})):
        modes[label] = run_mode(mesh_cfg, label)
    # determinism cross-check: dp shards samples — bitwise equal to off;
    # dp·tp moves reduction order — its OWN class, must still be
    # internally consistent (8 distinct tasks ⇒ 8 distinct CIDs)
    assert sorted(modes["off"]["cids"].values()) == \
        sorted(modes["dp2"]["cids"].values()), "dp2 broke byte equality"
    assert len(set(modes["dp2tp2"]["cids"].values())) == N
    line = {
        "metric": "mesh_ab_tiny_solutions_per_hour",
        "value": modes["dp2"]["solutions_per_hour"],
        "unit": (f"solutions/hour (TINY 128x128x2 through the full node "
                 f"tick loop, canonical_batch={BATCH}, platform="
                 f"{platform}, 8 virtual devices — CPU A/B sanity, no "
                 "perf claim)"),
        "vs_baseline": 0.0,
        "note": ("mesh_ab: solve mesh off vs dp2 vs dp2.tp2; off==dp2 "
                 "bytes asserted, dp2.tp2 is its own determinism class "
                 "(docs/multichip.md)"),
        "stage": "mesh_ab",
        "modes": {k: {kk: vv for kk, vv in v.items() if kk != "cids"}
                  for k, v in modes.items()},
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    }
    _emit(out_path, line)
    with open(os.path.join(_REPO, "MULTICHIP_r06.json"), "w") as f:
        json.dump({"n_devices": 8, "ok": True, "stage": "mesh_ab",
                   "platform": platform, "result": line}, f, indent=1)
        f.write("\n")
    _note("mesh_ab: wrote MULTICHIP_r06.json")
    hb.stop()


def _stage_sched_ab(out_path: str) -> None:
    """sched_ab stage (docs/scheduler.md): FIFO vs costsched over a
    mixed two-family synthetic queue on the CPU harness — the REAL node
    tick loop, two registered models sharing one tiny SD-1.5 pipe at
    different shapes (heavy 128²×8 steps, light 128²×2). Each mode primes the
    same warm executables and cost samples, then drives an interleaved
    flood where heavy tasks are priced BELOW their true chip cost but
    ABOVE the static mixture estimate: the static gate accepts them,
    the learned gate rejects them. Reports sol/h, chip-idle seconds,
    and gate precision/recall against measured ground truth; asserts
    commonly-solved tasks' CIDs are identical (deterministic) and
    reports the costsched ≥ FIFO sol/h + ≤ chip-idle ordering as
    `ordering_ok` (wall-clock — CPU sanity, no perf claim). Writes
    BENCH_r07.json."""
    import json as _json

    hb = _Heartbeat("sched_ab")
    devs = _child_common(cpu=True)
    platform = devs[0].platform

    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.node import (
        LocalChain,
        MinerNode,
        MiningConfig,
        ModelConfig,
        ModelRegistry,
        RegisteredModel,
        SD15Runner,
    )
    from arbius_tpu.node.config import PerfscopeConfig, SchedConfig
    from arbius_tpu.node.costmodel import CostModel
    from arbius_tpu.templates.engine import load_template
    from arbius_tpu.node.factory import tiny_byte_tokenizer

    cfg_t = SD15Config.tiny()
    pipe = SD15Pipeline(cfg_t, tokenizer=tiny_byte_tokenizer(cfg_t.text))
    hb.set("init_params (tiny)")
    params = pipe.init_params(seed=0, height=128, width=128)

    HEAVY = {"negative_prompt": "", "width": 128, "height": 128,
             "num_inference_steps": 8}
    LIGHT = {"negative_prompt": "", "width": 128, "height": 128,
             "num_inference_steps": 2}
    RATE = WAD          # 1 wad per predicted chip-second
    N_PRIME_L, N_PRIME_H, N_MIX = 6, 2, 10
    tmpl = load_template("anythingv3")

    def run_mode(sched_cfg, label: str) -> dict:
        tok = TokenLedger()
        eng = Engine(tok, start_time=10_000)
        tok.mint(Engine.ADDRESS, 600_000 * WAD)
        miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
        for a in (miner, user):
            tok.mint(a, 10**9 * WAD)
            tok.approve(a, Engine.ADDRESS, 10**40)
        mid_h = "0x" + eng.register_model(user, user, 0, b'{"f":"H"}').hex()
        mid_l = "0x" + eng.register_model(user, user, 0, b'{"f":"L"}').hex()
        registry = ModelRegistry()
        runner = SD15Runner(pipe, params)
        for mid in (mid_h, mid_l):
            registry.register(RegisteredModel(id=mid, template=tmpl,
                                              runner=runner))
        chain = LocalChain(eng, miner)
        chain.validator_deposit(100 * WAD)
        node = MinerNode(
            chain,
            MiningConfig(models=(ModelConfig(id=mid_h,
                                             template="anythingv3"),
                                 ModelConfig(id=mid_l,
                                             template="anythingv3")),
                         canonical_batch=1, compile_cache=False,
                         min_fee_per_second=RATE, sched=sched_cfg,
                         perfscope=PerfscopeConfig(enabled=True)),
            registry)
        node.boot(skip_self_test=True)
        while node.tick():
            pass

        def submit(mid, shape, i, fee):
            eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]), fee,
                            _json.dumps(dict(shape, prompt=f"sched task {i}"),
                                        sort_keys=True).encode())

        def drain():
            for _ in range(256):
                if node.tick() == 0:
                    break

        # prime: warm both executables AND both buckets' cost samples,
        # fees far above any floor so every prime solves under either
        # gate. One submit per tick ⇒ one bucket observation each.
        hb.set(f"sched_ab {label}: prime ({N_PRIME_L}L+{N_PRIME_H}H)")
        big = 10**6 * WAD
        for i in range(N_PRIME_L):
            submit(mid_l, LIGHT, 1000 + i, big)
            drain()
        for i in range(N_PRIME_H):
            submit(mid_h, HEAVY, 2000 + i, big)
            drain()
        # measured ground truth so far (per-task medians per bucket)
        probe = CostModel(min_samples=1)
        probe.ingest(node._h_stage)
        probe.refit()
        rows = {(r.model, r.bucket): r.chip_seconds
                for r in probe.sorted_rows()}
        l_true = next(v for (m, _), v in sorted(rows.items())
                      if m == mid_l)
        h_true = next(v for (m, _), v in sorted(rows.items())
                      if m == mid_h)
        # heavy fee: above the static mixture floor (≈ light bucket
        # seconds), below heavy's true cost — exactly the mispricing a
        # learned gate exists to catch
        fee_mix = int(2 * l_true * RATE)
        hb.set(f"sched_ab {label}: mixed flood ({N_MIX} tasks)")
        reg = node.obs.registry
        idle0 = reg.counter("arbius_chip_idle_seconds_total").value()
        gate0 = len(node.obs.journal.events(kind="gate_decision"))
        t0 = time.perf_counter()
        for i in range(N_MIX):
            if i % 2 == 0:
                submit(mid_h, HEAVY, 3000 + i, fee_mix)
            else:
                submit(mid_l, LIGHT, 3000 + i, fee_mix)
        drain()
        elapsed = time.perf_counter() - t0
        solved = len(eng.solutions) - N_PRIME_L - N_PRIME_H
        idle = reg.counter("arbius_chip_idle_seconds_total").value() - idle0
        # gate audit vs measured truth: a reject was CORRECT iff the
        # fee really was below the family's measured chip cost × rate
        gates = node.obs.journal.events(kind="gate_decision")[gate0:]
        truth = {mid_h: h_true, mid_l: l_true}
        rejects = [g for g in gates if g["verdict"] == "reject"]
        correct = [g for g in rejects
                   if int(g["fee"]) < truth[g["model"]] * RATE]
        should_reject = sum(1 for i in range(N_MIX)
                            if fee_mix < truth[mid_h if i % 2 == 0
                                               else mid_l] * RATE)
        out = {
            "sched": {"enabled": sched_cfg.enabled,
                      "min_samples": sched_cfg.min_samples},
            "solutions": solved,
            "seconds": round(elapsed, 3),
            "solutions_per_hour": round(3600.0 * solved / elapsed, 2),
            "chip_idle_seconds": round(idle, 4),
            "fee_mix_wad": str(fee_mix),
            "true_seconds": {"heavy": round(h_true, 4),
                             "light": round(l_true, 4)},
            "gate": {
                "decisions": len(gates),
                "rejects": len(rejects),
                "should_reject": should_reject,
                "precision": (round(len(correct) / len(rejects), 3)
                              if rejects else None),
                "recall": (round(len(correct) / should_reject, 3)
                           if should_reject else None),
            },
            "jit_cache": {
                # hits are tiered since the AOT cache landed
                # (docs/compile-cache.md); this stage runs memory-only
                "hits": reg.counter("arbius_jit_cache_hits_total",
                                    labelnames=("tier",)
                                    ).value(tier="memory"),
                "misses": reg.counter(
                    "arbius_jit_cache_misses_total").value(),
            },
            # fleetscope SLO percentiles (docs/fleetscope.md):
            # fixed-bucket estimates over the FULL histograms (never
            # window-truncated), so the bench trajectory carries tail
            # latencies next to sol/h
            "slo": {
                "solve_latency_chain_seconds": {
                    p: node.obs.registry.histogram(
                        "arbius_solve_latency_chain_seconds"
                    ).estimate_percentile(q)
                    for p, q in (("p50", 0.5), ("p95", 0.95),
                                 ("p99", 0.99))},
                "stage_infer_seconds": {
                    p: node._h_stage.estimate_percentile(q,
                                                         stage="infer")
                    for p, q in (("p50", 0.5), ("p95", 0.95),
                                 ("p99", 0.99))},
            },
            # perfscope cards (docs/perfscope.md): flops/bytes/
            # padding/roofline context per bucket, joined on the cost
            # tag — the perf trajectory finally carries the statics
            "perf_cards": _perf_cards(node),
            "cids": {"0x" + t.hex(): "0x" + s.cid.hex()
                     for t, s in eng.solutions.items()},
        }
        node.close()
        return out

    # discarded warm pass per mode, then the measured pair (cache and
    # allocator warmth dominate tiny CPU solves otherwise).
    # enabled=False alone IS the full FIFO/static baseline: it disables
    # the packer AND the learned gate (test-pinned in test_sched.py).
    run_mode(SchedConfig(enabled=False), "fifo-warm")
    run_mode(SchedConfig(enabled=True, min_samples=2), "cost-warm")
    fifo = run_mode(SchedConfig(enabled=False), "fifo")
    cost = run_mode(SchedConfig(enabled=True, min_samples=2), "cost")
    # byte equality on the tasks both modes solved (the packer/gate may
    # only change WHICH tasks run and WHEN — never the bytes): hard
    # asserts, this is deterministic
    common = set(fifo["cids"]) & set(cost["cids"])
    assert common, "modes share no solved tasks"
    for t in sorted(common):
        assert fifo["cids"][t] == cost["cids"][t], f"CID drift on {t}"
    # the throughput/idle ordering is wall-clock on different work sets
    # (the learned gate rejects the mispriced half) — report it rather
    # than hard-fail a loaded host on millisecond noise
    ordering_ok = (cost["solutions_per_hour"] >= fifo["solutions_per_hour"]
                   and cost["chip_idle_seconds"]
                   <= fifo["chip_idle_seconds"])
    if not ordering_ok:
        _note("sched_ab: WARNING costsched did not beat FIFO this run "
              "(wall-clock noise; compare the modes block)")
    line = {
        "metric": "sched_ab_tiny_solutions_per_hour",
        "value": cost["solutions_per_hour"],
        "unit": (f"solutions/hour (TINY two-family mixed queue through "
                 f"the full node tick loop, canonical_batch=1, platform="
                 f"{platform} — CPU A/B sanity, no perf claim)"),
        "vs_baseline": 0.0,
        "note": ("sched_ab: FIFO/static-gate vs costsched/learned-gate "
                 "over an interleaved heavy+light flood with heavy "
                 "mispriced below true cost; common CIDs asserted "
                 "identical, costsched-vs-FIFO sol/h + chip-idle "
                 "ordering reported as ordering_ok "
                 "(docs/scheduler.md)"),
        "stage": "sched_ab",
        "ordering_ok": ordering_ok,
        "modes": {"fifo": {k: v for k, v in fifo.items() if k != "cids"},
                  "costsched": {k: v for k, v in cost.items()
                                if k != "cids"}},
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    }
    _emit(out_path, line)
    with open(os.path.join(_REPO, "BENCH_r07.json"), "w") as f:
        json.dump({"ok": True, "stage": "sched_ab", "platform": platform,
                   "result": line}, f, indent=1)
        f.write("\n")
    _note("sched_ab: wrote BENCH_r07.json")
    _write_bench_r14("sched_ab", platform, line)
    hb.stop()


def _stage_text_ab(out_path: str) -> None:
    """text_ab stage (docs/text-serving.md): the textgen family through
    the REAL node tick loop on CPU — a tiny decoder, real jitted
    prefill + KV-cache decode-scan programs, the canonical encode→CID
    path. Two A/B axes over a mixed-sequence flood (both prompt
    buckets, three decode budgets):

      * greedy vs seeded-top-k: each sampler run TWICE in fresh worlds
        and its CIDs asserted byte-identical (the decode loop is one
        deterministic program per bucket; the samplers are separate
        goldened classes, so cross-sampler bytes are not compared);
      * bucketed (costsched) vs naive (FIFO) packing: the packer may
        permute whole sequence buckets only — commonly solved tasks'
        CIDs asserted identical, sol/h + chip-idle ordering reported
        as `ordering_ok` (wall-clock — CPU sanity, no perf claim).

    Writes BENCH_r16.json."""
    import json as _json

    hb = _Heartbeat("text_ab")
    devs = _child_common(cpu=True)
    platform = devs[0].platform

    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.models.textgen import TextGenConfig, TextGenPipeline
    from arbius_tpu.node import (
        LocalChain,
        MinerNode,
        MiningConfig,
        ModelConfig,
        ModelRegistry,
        RegisteredModel,
    )
    from arbius_tpu.node.config import PerfscopeConfig, SchedConfig
    from arbius_tpu.node.solver import TextGenRunner
    from arbius_tpu.templates.engine import load_template

    cfg_t = TextGenConfig.tiny()
    pipe = TextGenPipeline(cfg_t, prompt_buckets=(32, 64),
                           decode_buckets=(16, 32))
    hb.set("init_params (tiny textgen)")
    params = pipe.init_params(seed=0)
    tmpl = load_template("textgen")
    N_TASKS = 10
    # mixed-sequence flood: short + long prompts (both prompt buckets),
    # three decode budgets (both decode buckets) — several live
    # sequence buckets per run for the packer to permute
    PROMPTS = ["short {i}", "a deliberately longer prompt padding out "
                            "past the first bucket edge {i}"]
    BUDGETS = (8, 16, 24)

    def run_world(sched_cfg, sampler: str, label: str) -> dict:
        tok = TokenLedger()
        eng = Engine(tok, start_time=10_000)
        tok.mint(Engine.ADDRESS, 600_000 * WAD)
        miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
        for a in (miner, user):
            tok.mint(a, 10**9 * WAD)
            tok.approve(a, Engine.ADDRESS, 10**40)
        mid = "0x" + eng.register_model(user, user, 0, b'{"f":"T"}').hex()
        registry = ModelRegistry()
        registry.register(RegisteredModel(
            id=mid, template=tmpl, runner=TextGenRunner(pipe, params)))
        chain = LocalChain(eng, miner)
        chain.validator_deposit(100 * WAD)
        node = MinerNode(
            chain,
            MiningConfig(models=(ModelConfig(id=mid, template="textgen"),),
                         canonical_batch=1, compile_cache=False,
                         sched=sched_cfg,
                         perfscope=PerfscopeConfig(enabled=True)),
            registry)
        node.boot(skip_self_test=True)
        while node.tick():
            pass
        hb.set(f"text_ab {label}: flood ({N_TASKS} tasks)")
        reg = node.obs.registry
        idle0 = reg.counter("arbius_chip_idle_seconds_total").value()
        t0 = time.perf_counter()
        for i in range(N_TASKS):
            obj = {"prompt": PROMPTS[i % 2].format(i=i),
                   "max_new_tokens": BUDGETS[i % 3],
                   "sampler": ("top_k" if i % 2 else "greedy")
                   if sampler == "mix" else sampler}
            eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]),
                            (1 + i % 3) * WAD,
                            _json.dumps(obj, sort_keys=True).encode())
        for _ in range(256):
            if node.tick() == 0:
                break
        elapsed = time.perf_counter() - t0
        solved = len(eng.solutions)
        out = {
            "sampler": sampler,
            "sched": {"enabled": sched_cfg.enabled},
            "solutions": solved,
            "seconds": round(elapsed, 3),
            "solutions_per_hour": round(3600.0 * solved / elapsed, 2),
            "chip_idle_seconds": round(
                reg.counter("arbius_chip_idle_seconds_total").value()
                - idle0, 4),
            "decode_stalls": reg.counter(
                "arbius_decode_stalls_total").value(),
            "jit_cache": {
                "hits": reg.counter("arbius_jit_cache_hits_total",
                                    labelnames=("tier",)
                                    ).value(tier="memory"),
                "misses": reg.counter(
                    "arbius_jit_cache_misses_total").value(),
            },
            "perf_cards": _perf_cards(node),
            "cids": {"0x" + t.hex(): "0x" + s.cid.hex()
                     for t, s in eng.solutions.items()},
        }
        node.close()
        return out

    # axis 1: per-sampler determinism — same world twice, same bytes
    modes = {}
    for samp in ("greedy", "top_k"):
        a = run_world(SchedConfig(enabled=False), samp, f"{samp}-1")
        b = run_world(SchedConfig(enabled=False), samp, f"{samp}-2")
        assert a["cids"] and a["cids"] == b["cids"], \
            f"{samp} CIDs drifted between identical worlds"
        assert a["solutions"] == N_TASKS, \
            f"{samp}: {a['solutions']}/{N_TASKS} solved"
        modes[samp] = {k: v for k, v in a.items() if k != "cids"}
    # axis 2: naive FIFO vs bucketed costsched packing over the mix
    fifo = run_world(SchedConfig(enabled=False), "mix", "fifo-mix")
    cost = run_world(SchedConfig(enabled=True, min_samples=2), "mix",
                     "cost-mix")
    common = set(fifo["cids"]) & set(cost["cids"])
    assert common, "packing modes share no solved tasks"
    for t in sorted(common):
        assert fifo["cids"][t] == cost["cids"][t], f"CID drift on {t}"
    ordering_ok = (cost["solutions_per_hour"]
                   >= fifo["solutions_per_hour"]
                   and cost["chip_idle_seconds"]
                   <= fifo["chip_idle_seconds"])
    if not ordering_ok:
        _note("text_ab: WARNING bucketed packing did not beat naive "
              "this run (wall-clock noise; compare the modes block)")
    modes["fifo_mix"] = {k: v for k, v in fifo.items() if k != "cids"}
    modes["costsched_mix"] = {k: v for k, v in cost.items()
                              if k != "cids"}
    line = {
        "metric": "text_ab_tiny_solutions_per_hour",
        "value": cost["solutions_per_hour"],
        "unit": (f"solutions/hour (TINY textgen mixed-sequence flood "
                 f"through the full node tick loop, canonical_batch=1, "
                 f"platform={platform} — CPU A/B sanity, no perf "
                 "claim)"),
        "vs_baseline": 0.0,
        "note": ("text_ab: greedy and seeded-top-k each byte-identical "
                 "across fresh worlds; bucketed-vs-naive packing common "
                 "CIDs asserted identical, sol/h + chip-idle ordering "
                 "reported as ordering_ok (docs/text-serving.md)"),
        "stage": "text_ab",
        "ordering_ok": ordering_ok,
        "modes": modes,
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    }
    _emit(out_path, line)
    with open(os.path.join(_REPO, "BENCH_r16.json"), "w") as f:
        json.dump({"ok": True, "stage": "text_ab", "platform": platform,
                   "result": line}, f, indent=1)
        f.write("\n")
    _note("text_ab: wrote BENCH_r16.json")
    hb.stop()


def _stage_flood(out_path: str, tasks: int = 10000,
                 workers: int = 4) -> None:
    """flood stage (docs/fleetscope.md): the 10k-lifecycle fleet flood
    through the in-process engine, reported WITH the SLO percentile
    block — queue-wait / time-to-commit / steal-lag p50/p95/p99 over
    chain time (byte-deterministic, same substrate as
    `simsoak --flood`) plus the wall-clock quantities a bench line may
    carry (tasks/hour, chip-idle fraction — wall time stays out of the
    deterministic report and in this line). Writes BENCH_r11.json so
    the bench trajectory restarts with latency percentiles as
    first-class numbers, not just sol/h."""
    import tempfile

    hb = _Heartbeat("flood")
    from arbius_tpu.sim.fleet import FleetFloodHarness

    hb.set(f"flood: {tasks} tasks / {workers} workers")
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="benchflood-") as tmp:
        harness = FleetFloodHarness(tasks, workers, tmp)
        try:
            report = harness.run()
            idle = sum(
                w.obs.registry.counter(
                    "arbius_chip_idle_seconds_total").value()
                for w in harness.workers)
        finally:
            harness.close()
    elapsed = time.perf_counter() - t0
    line = {
        "metric": "flood_tasks_per_hour",
        "value": round(3600.0 * report["claimed"] / elapsed, 1),
        "unit": (f"task lifecycles/hour ({tasks} tasks through a "
                 f"{workers}-worker fleet over the in-process engine, "
                 "CPU wall clock — load sanity, no perf claim)"),
        "vs_baseline": 0.0,
        "note": ("flood: fleet soak with the fleetscope SLO percentile "
                 "report embedded — queue-wait/time-to-commit/steal-lag "
                 "p50/p95/p99 are chain-time and byte-deterministic; "
                 "tasks/hour and chip-idle are wall-clock "
                 "(docs/fleetscope.md)"),
        "stage": "flood",
        "slo": report["slo"],
        "claimed": report["claimed"],
        "rounds": report["rounds"],
        "commit_dedup": report["commit_dedup"],
        "max_backlog": report["max_backlog"],
        "db_commits": report["db_commits"],
        "chip_idle_seconds": round(idle, 4),
        # fraction of the fleet's total worker-seconds (N workers run
        # concurrently, so the denominator is workers × wall) — keeps
        # the number inside SLOConfig's documented [0, 1] range
        "chip_idle_fraction": round(
            idle / max(workers * elapsed, 1e-9), 6),
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    }
    _emit(out_path, line)
    with open(os.path.join(_REPO, "BENCH_r11.json"), "w") as f:
        json.dump({"ok": True, "stage": "flood", "result": line},
                  f, indent=1)
        f.write("\n")
    _note("flood: wrote BENCH_r11.json")
    hb.stop()


def _stage_quant_ab(out_path: str) -> None:
    """quant_ab stage (docs/quantization.md): bf16 vs int8 A/B through
    the FULL node tick loop on the 8-way CPU harness — config (with a
    `precision` block) → build_registry (boot-time weight quantization)
    → MinerNode → staged pipeline. Per mode: sol/h, chip-idle seconds,
    and the collective-byte counters at dp2·tp2 (quantized tp bytes
    must come out STRICTLY below bf16's — the 1-byte wire), plus the
    determinism matrix WITHIN each mode: CIDs byte-identical across
    aot-cache-off / cold / warm lives, pipeline on/off, and mesh-off vs
    dp2. Cross-mode CIDs must differ (a mode is its own class). Also
    runs the simnet clean + crash-restart scenarios at int8 (SIM101-112
    audited). CPU sanity numbers only, no perf claim; writes
    BENCH_r13.json."""
    import json as _json
    import tempfile

    hb = _Heartbeat("quant_ab")
    # XLA persistent cache off: the aot cold/warm lives must measure
    # real compiles (the coldboot-stage rationale)
    devs = _child_common(cpu=True, n_devices=8, compile_cache=False)
    platform = devs[0].platform

    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.node import LocalChain, MinerNode, MiningConfig, ModelConfig
    from arbius_tpu.node.config import (
        AotCacheConfig,
        PerfscopeConfig,
        PipelineConfig,
        PrecisionConfig,
    )
    from arbius_tpu.node.factory import build_registry

    N, BATCH = 8, 2
    raw = {"negative_prompt": "", "width": 128, "height": 128,
           "num_inference_steps": 2}

    def run_node(mode: str, label: str, *, mesh_cfg=None, pipeline=True,
                 aot_dir=None, n=N) -> dict:
        tok = TokenLedger()
        eng = Engine(tok, start_time=10_000)
        tok.mint(Engine.ADDRESS, 600_000 * WAD)
        miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
        for a in (miner, user):
            tok.mint(a, 1_000 * WAD)
            tok.approve(a, Engine.ADDRESS, 10**30)
        mid = "0x" + eng.register_model(user, user, 0, b"{}").hex()
        cfg = MiningConfig(
            models=(ModelConfig(id=mid, template="anythingv3", tiny=True),),
            canonical_batch=BATCH, compile_cache=False, mesh=mesh_cfg,
            precision=PrecisionConfig(default=mode),
            perfscope=PerfscopeConfig(enabled=True),
            aot_cache=AotCacheConfig(enabled=True, dir=aot_dir)
            if aot_dir else AotCacheConfig(),
            pipeline=PipelineConfig(enabled=True, depth=2,
                                    encode_workers=2, max_inflight_pins=2)
            if pipeline else PipelineConfig())
        hb.set(f"quant_ab {mode}/{label}: boot")
        registry = build_registry(cfg)
        chain = LocalChain(eng, miner)
        chain.validator_deposit(100 * WAD)
        node = MinerNode(chain, cfg, registry)
        node.boot(skip_self_test=True)
        while node.tick():
            pass
        for i in range(n):
            eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]), 0,
                            _json.dumps(dict(raw, prompt=f"quant task {i}"),
                                        sort_keys=True).encode())
        hb.set(f"quant_ab {mode}/{label}: {n} solves")
        t0 = time.perf_counter()
        for _ in range(128):
            if node.tick() == 0:
                break
        elapsed = time.perf_counter() - t0
        assert len(eng.solutions) == n, \
            f"{mode}/{label}: {len(eng.solutions)}/{n}"
        reg = node.obs.registry
        out = {
            "mode": mode,
            "mesh": mesh_cfg,
            "solutions": n,
            "seconds": round(elapsed, 3),
            "solutions_per_hour": round(3600.0 * n / elapsed, 2),
            "chip_idle_seconds": round(
                reg.counter("arbius_chip_idle_seconds_total").value(), 4),
            "collective_bytes": reg.counter(
                "arbius_collective_bytes_total",
                labelnames=("axis",)).summary(),
            "jit": {
                "compiles": reg.counter(
                    "arbius_jit_cache_misses_total").value(),
                "disk_hits": reg.counter(
                    "arbius_jit_cache_hits_total",
                    labelnames=("tier",)).value(tier="disk"),
            },
            # per-(mode, layout) perfscope cards (docs/perfscope.md)
            "perf_cards": _perf_cards(node),
            "cids": sorted("0x" + s.cid.hex()
                           for s in eng.solutions.values()),
        }
        node.close()
        return out

    modes: dict[str, dict] = {}
    for mode in ("bf16", "int8"):
        # headline: dp2·tp2 through the staged pipeline — the layout
        # whose tp ring traffic the quantized wire shrinks
        head = run_node(mode, "dp2tp2", mesh_cfg={"dp": 2, "tp": 2})
        # determinism matrix within the mode (4 tasks each)
        base = run_node(mode, "base", pipeline=False, n=4)
        pipe = run_node(mode, "pipe", pipeline=True, n=4)
        dp2 = run_node(mode, "dp2", mesh_cfg={"dp": 2}, n=4)
        with tempfile.TemporaryDirectory() as aot:
            cold = run_node(mode, "aot-cold", pipeline=False, n=4,
                            aot_dir=aot)
            warm = run_node(mode, "aot-warm", pipeline=False, n=4,
                            aot_dir=aot)
        for label, r in (("pipeline-on", pipe), ("dp2", dp2),
                         ("aot-cold", cold), ("aot-warm", warm)):
            assert r["cids"] == base["cids"], \
                f"{mode}: {label} CIDs diverged from cache-off/sync base"
        assert warm["jit"]["compiles"] == 0 and \
            warm["jit"]["disk_hits"] > 0, f"{mode}: warm life compiled"
        modes[mode] = {
            "headline": head,
            "determinism": {"cids_pinned_across":
                            ["aot-off", "aot-cold", "aot-warm",
                             "pipeline-on", "pipeline-off", "mesh-off",
                             "dp2"],
                            "cids": base["cids"]},
        }
    assert modes["bf16"]["determinism"]["cids"] != \
        modes["int8"]["determinism"]["cids"], \
        "int8 must be its own determinism class"
    tp_bf16 = modes["bf16"]["headline"]["collective_bytes"].get(
        "axis=tp", 0)
    tp_int8 = modes["int8"]["headline"]["collective_bytes"].get(
        "axis=tp", 0)
    assert 0 < tp_int8 < tp_bf16, \
        f"quantized tp bytes must be strictly below bf16 " \
        f"({tp_int8} vs {tp_bf16})"

    # simnet at int8: clean + crash-restart under the full invariant
    # catalog (the probe runner carries the quantized program)
    hb.set("quant_ab: simnet int8 (clean + crash-restart)")
    from arbius_tpu.sim.harness import run_scenario
    from arbius_tpu.sim.invariants import check_all
    from arbius_tpu.sim.scenario import get_scenario

    sim = {}
    res = run_scenario(get_scenario("clean"), 0, mesh={},
                       precision="int8")
    sim["clean"] = {"violations": [f.text() for f in check_all(res)]}
    with tempfile.TemporaryDirectory() as d:
        res = run_scenario(get_scenario("crash-restart"), 0, mesh={},
                           precision="int8",
                           db_path=os.path.join(d, "sim.sqlite"))
        sim["crash-restart"] = {
            "violations": [f.text() for f in check_all(res)]}
    assert not sim["clean"]["violations"], sim
    assert not sim["crash-restart"]["violations"], sim

    line = {
        "metric": "quant_ab_int8_tp_bytes_vs_bf16",
        "value": round(tp_int8 / tp_bf16, 4),
        "unit": ("int8/bf16 tp collective-byte ratio at dp2.tp2 (TINY "
                 f"128x128x2, canonical_batch={BATCH}, platform="
                 f"{platform}, 8 virtual devices — CPU A/B sanity, no "
                 "perf claim)"),
        "vs_baseline": 0.0,
        "note": ("quant_ab: bf16 vs int8 through the full node tick "
                 "loop; per-mode CIDs pinned across cache-off/cold/"
                 "warm, pipeline on/off, mesh-off vs dp2; simnet "
                 "clean+crash-restart green at int8 "
                 "(docs/quantization.md)"),
        "stage": "quant_ab",
        "modes": modes,
        "sim_int8": sim,
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    }
    _emit(out_path, line)
    with open(os.path.join(_REPO, "BENCH_r13.json"), "w") as f:
        json.dump({"n_devices": 8, "ok": True, "stage": "quant_ab",
                   "platform": platform, "result": line}, f, indent=1)
        f.write("\n")
    _note("quant_ab: wrote BENCH_r13.json")
    _write_bench_r14("quant_ab", platform, line)
    hb.stop()


def _stage_coldboot(out_path: str) -> None:
    """coldboot stage (docs/compile-cache.md): cold-boot-to-first-
    solution A/B over the AOT executable cache. Three full node lives
    on the CPU harness, each with a FRESH pipeline (so executables
    genuinely re-trace): a discarded pass into a throwaway cache dir
    (process-global warmup — imports and allocator must not masquerade
    as cache wins), then a measured COLD life into an empty cache
    (trace + compile + serialize every bucket) and a measured WARM life
    over the now-populated directory (every bucket a disk hit —
    deserialize, zero XLA compiles). Asserts: warm boot disk-hits every
    bucket with zero bucket compile-seconds and zero rejects, CIDs are
    byte-identical cold vs warm, and warm first-solution wall is
    strictly below cold. Writes BENCH_r12.json."""
    import json as _json
    import tempfile

    hb = _Heartbeat("coldboot")
    # the XLA persistent compilation cache must be OFF here twice over:
    # the cold run must measure REAL compiles, and a cache-served CPU
    # executable re-serializes without its jitted symbols (the AOT
    # write-time self-check would refuse to publish it —
    # docs/compile-cache.md)
    devs = _child_common(cpu=True, compile_cache=False)
    platform = devs[0].platform

    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.node import (
        LocalChain,
        MinerNode,
        MiningConfig,
        ModelConfig,
        ModelRegistry,
        RegisteredModel,
        SD15Runner,
    )
    from arbius_tpu.node.config import AotCacheConfig, PerfscopeConfig
    from arbius_tpu.node.factory import tiny_byte_tokenizer
    from arbius_tpu.templates.engine import load_template

    cfg_t = SD15Config.tiny()
    # params are shared across lives (pure data — same bits whoever
    # computes them); each life builds a FRESH pipeline so bucket
    # executables really re-trace instead of riding python-object caches
    hb.set("init_params (tiny)")
    params = SD15Pipeline(
        cfg_t, tokenizer=tiny_byte_tokenizer(cfg_t.text)).init_params(
        seed=0, height=128, width=128)

    SHAPES = [{"negative_prompt": "", "width": 128, "height": 128,
               "num_inference_steps": 2},
              {"negative_prompt": "", "width": 128, "height": 128,
               "num_inference_steps": 4}]
    TASKS_PER_SHAPE = 2
    tmpl = load_template("anythingv3")

    def boot_and_mine(label: str, cache_dir: str) -> dict:
        hb.set(f"coldboot {label}: boot + mine")
        tok = TokenLedger()
        eng = Engine(tok, start_time=10_000)
        tok.mint(Engine.ADDRESS, 600_000 * WAD)
        miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
        for a in (miner, user):
            tok.mint(a, 10**9 * WAD)
            tok.approve(a, Engine.ADDRESS, 10**40)
        mid = "0x" + eng.register_model(user, user, 0, b'{"f":"C"}').hex()
        pipe = SD15Pipeline(cfg_t,
                            tokenizer=tiny_byte_tokenizer(cfg_t.text))
        registry = ModelRegistry()
        registry.register(RegisteredModel(
            id=mid, template=tmpl, runner=SD15Runner(pipe, params)))
        chain = LocalChain(eng, miner)
        chain.validator_deposit(100 * WAD)
        node = MinerNode(
            chain,
            MiningConfig(models=(ModelConfig(id=mid,
                                             template="anythingv3"),),
                         canonical_batch=1, compile_cache=False,
                         aot_cache=AotCacheConfig(enabled=True,
                                                  dir=cache_dir),
                         perfscope=PerfscopeConfig(enabled=True)),
            registry)
        t0 = time.perf_counter()
        node.boot(skip_self_test=True)
        # all tasks submitted up front: the first-solution wall includes
        # the first bucket's executable acquisition (compile vs load) —
        # the cold-boot cost this stage exists to measure
        total = len(SHAPES) * TASKS_PER_SHAPE
        for i in range(total):
            eng.submit_task(
                user, 0, user, bytes.fromhex(mid[2:]), 0,
                _json.dumps(dict(SHAPES[i % len(SHAPES)],
                                 prompt=f"coldboot task {i}"),
                            sort_keys=True).encode())
        first_wall = None
        for _ in range(1024):
            did = node.tick()
            if first_wall is None and eng.solutions:
                first_wall = time.perf_counter() - t0
            if len(eng.solutions) >= total and not did:
                break
        assert first_wall is not None, \
            f"coldboot {label}: no solution landed in 1024 ticks — " \
            "solve path stalled (check compile/reject journal)"
        wall = time.perf_counter() - t0
        reg = node.obs.registry
        bucket_compiles = [
            (t, v) for t, v in
            reg.histogram("arbius_compile_seconds").recent()
            if t and t.startswith("sd15.")]
        out = {
            "first_solution_wall_s": round(first_wall, 4),
            "total_wall_s": round(wall, 4),
            "solutions": len(eng.solutions),
            "solutions_per_hour": round(
                3600.0 * len(eng.solutions) / wall, 2),
            "bucket_compiles": len(bucket_compiles),
            "bucket_compile_seconds": round(
                sum(v for _, v in bucket_compiles), 4),
            "aot": {
                "loads": reg.counter(
                    "arbius_aot_cache_loads_total").value(),
                "writes": reg.counter(
                    "arbius_aot_cache_writes_total").value(),
                "rejects": reg.counter(
                    "arbius_aot_cache_rejects_total").value(),
                "load_seconds": round(sum(
                    v for _, v in reg.histogram(
                        "arbius_aot_load_seconds").recent()), 4),
                "disk_hits": reg.counter(
                    "arbius_jit_cache_hits_total",
                    labelnames=("tier",)).value(tier="disk"),
                "misses": reg.counter(
                    "arbius_jit_cache_misses_total").value(),
            },
            "disk_warm_at_boot": sorted(node._disk_warm_tags),
            # cards on BOTH lives: the warm one must carry the
            # ORIGINAL compile cost from the aotcache header's perf
            # block (source=disk — docs/perfscope.md amortization)
            "perf_cards": _perf_cards(node),
            "cids": {"0x" + t.hex(): "0x" + s.cid.hex()
                     for t, s in eng.solutions.items()},
        }
        node.close()
        _note(f"coldboot {label}: first_sol={out['first_solution_wall_s']}s "
              f"compiles={out['bucket_compiles']} "
              f"({out['bucket_compile_seconds']}s) "
              f"disk_hits={out['aot']['disk_hits']}")
        return out

    n_buckets = len(SHAPES)
    with tempfile.TemporaryDirectory(prefix="benchaot-") as tmp:
        boot_and_mine("discard", os.path.join(tmp, "discard"))
        cold = boot_and_mine("cold", os.path.join(tmp, "cache"))
        warm = boot_and_mine("warm", os.path.join(tmp, "cache"))
    # hard assertions — this is the acceptance surface, all deterministic
    # except the wall ordering (compile is ~100× a deserialize on this
    # workload; the discarded pass removed interpreter warmup)
    assert cold["aot"]["writes"] == n_buckets and \
        cold["aot"]["disk_hits"] == 0, "cold life must compile + publish"
    assert warm["aot"]["disk_hits"] == n_buckets, \
        "warm boot must disk-hit every bucket"
    assert warm["aot"]["misses"] == 0 and warm["bucket_compiles"] == 0, \
        "warm boot must compile nothing"
    assert warm["aot"]["rejects"] == 0 == cold["aot"]["rejects"]
    assert warm["disk_warm_at_boot"], "boot scan must see disk-warm tags"
    common = sorted(set(cold["cids"]) & set(warm["cids"]))
    assert common, "lives share no solved tasks"
    for t in common:
        assert cold["cids"][t] == warm["cids"][t], f"CID drift on {t}"
    assert warm["first_solution_wall_s"] < cold["first_solution_wall_s"], \
        "warm first-solution wall must beat cold"
    line = {
        "metric": "coldboot_first_solution_seconds",
        "value": warm["first_solution_wall_s"],
        "unit": (f"seconds from boot to first accepted solution (TINY "
                 f"SD-1.5, {n_buckets} buckets, warm AOT cache, "
                 f"platform={platform} — CPU A/B sanity, no perf claim)"),
        "vs_baseline": 0.0,
        "note": ("coldboot: empty-cache vs warm-cache boot through the "
                 "full node tick loop after a discarded warmup pass; "
                 "warm boot deserialized every bucket (zero compiles, "
                 "zero rejects), CIDs byte-identical, first-solution "
                 "wall strictly below cold (docs/compile-cache.md)"),
        "stage": "coldboot",
        "speedup_first_solution": round(
            cold["first_solution_wall_s"] / warm["first_solution_wall_s"],
            2),
        "modes": {"cold": {k: v for k, v in cold.items() if k != "cids"},
                  "warm": {k: v for k, v in warm.items() if k != "cids"}},
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    }
    _emit(out_path, line)
    with open(os.path.join(_REPO, "BENCH_r12.json"), "w") as f:
        json.dump({"ok": True, "stage": "coldboot", "platform": platform,
                   "result": line}, f, indent=1)
        f.write("\n")
    _note("coldboot: wrote BENCH_r12.json")
    _write_bench_r14("coldboot", platform, line)
    hb.stop()


def _prod_line(val: float, unit: str, note: str, stage: str,
               extra: dict | None = None) -> dict:
    line = {
        "metric": METRIC,
        "value": round(val, 2),
        "unit": unit,
        "vs_baseline": round(val / A100_SOLUTIONS_PER_HOUR_EST, 3),
        "baseline_note": BASELINE_NOTE,
        "note": note,
        "stage": stage,
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    }
    if extra:
        line.update(extra)
    return line


def _stage_session(out_path: str) -> None:
    """The whole TPU ladder in this process (see module docstring); a
    failing stage leaves through its exception."""
    deadline = _T0 + SESSION_BUDGET_S - SESSION_MARGIN_S

    def left() -> float:
        return deadline - time.perf_counter()

    hb = _Heartbeat("session")
    hb.set(f"session (budget {SESSION_BUDGET_S}s, margin "
           f"{SESSION_MARGIN_S}s)")
    try:
        _session_body(out_path, hb, left)
    finally:
        hb.stop()


def _session_body(out_path: str, hb: _Heartbeat, left) -> None:
    devs = _child_common(cpu=False)
    platform = devs[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py: the backend is {platform!r}, not a TPU — the "
            "session measures the chip and has no CPU fallback (CPU A/B "
            "stages: --stage <name>)")

    import jax

    from arbius_tpu.models.sd15 import ByteTokenizer, SD15Config, SD15Pipeline
    from arbius_tpu.node.factory import tiny_byte_tokenizer
    from arbius_tpu.utils import cast_floating

    best: tuple[float, str, str] | None = None  # (value, unit, stage)
    sweep: dict[str, float] = {}

    def track(line: dict) -> None:
        nonlocal best
        _emit(out_path, line)
        if line.get("vs_baseline", 0) > 0 and (
                best is None or line["value"] > best[0]):
            best = (line["value"], line["unit"], line["stage"])

    def _headline_note(stage: str) -> str:
        # prod4 is an EXTRAPOLATION — never let the final line claim a
        # measurement it didn't make just because the session ran out of
        # time before the 20-step stages
        kind = "extrapolated" if stage == "prod4" else "measured"
        return f"best_{kind} (from stage {stage})"

    # -- tiny sanity: the chip executes end-to-end, fast ------------------
    cfg = SD15Config.tiny()
    tpipe = SD15Pipeline(cfg, tokenizer=tiny_byte_tokenizer(cfg.text))
    hb.set("init_params (tiny)")
    tparams = tpipe.init_params(seed=0, height=128, width=128)
    sec = _timed_solutions(tpipe, tparams, 1, width=128, height=128,
                           steps=4, rounds=2, hb=hb)
    track({
        "metric": METRIC,
        "value": round(3600.0 / sec, 2),
        "unit": (f"solutions/hour/chip (TINY topology 128x128, 4 steps, "
                 f"platform={platform} — sanity stage, no perf claim)"),
        "vs_baseline": 0.0,
        "note": "stage_tiny_sanity",
        "stage": "tiny",
        "elapsed_s": round(time.perf_counter() - _T0, 1),
    })

    goldens_only = os.environ.get("BENCH_GOLDENS_ONLY", "0") == "1"
    pipe = SD15Pipeline(SD15Config(), tokenizer=ByteTokenizer())
    params = params16 = None
    if goldens_only:
        _note("BENCH_GOLDENS_ONLY=1: skipping measurement stages")
    elif left() > 240:
        hb.set("init_params (full 860M-class, jitted on-device)")
        t_init = time.perf_counter()
        params = pipe.init_params(seed=0, height=HEIGHT, width=WIDTH)
        jax.block_until_ready(params)
        _note(f"init_params done in {time.perf_counter() - t_init:.1f}s")

        # measured 4-step, extrapolated to the 20-step metric shape.
        sec4 = _timed_solutions(pipe, params, 1, width=WIDTH, height=HEIGHT,
                                steps=4, rounds=2, hb=hb)
        est = 3600.0 / (sec4 * (STEPS / 4))
        track(_prod_line(
            est,
            f"solutions/hour/chip (SD-1.5 512x512 FULL topology, "
            f"EXTRAPOLATED 20-step from measured 4-step x5, {SCHEDULER})",
            "stage_prod_extrapolated", "prod4"))
    else:
        _note(f"skipping prod stages: only {left():.0f}s left")

    if params is not None and left() > 180:
        # the real metric — 20 steps measured.
        sec20 = _timed_solutions(pipe, params, 1, width=WIDTH, height=HEIGHT,
                                 steps=STEPS, rounds=2, hb=hb)
        track(_prod_line(
            3600.0 / sec20,
            f"solutions/hour/chip (SD-1.5 512x512, {STEPS} steps, "
            f"{SCHEDULER}, CFG — measured on real TPU)",
            "stage_prod_measured", "prod20"))

    if params is not None and left() > 180:
        # bf16 weights (ModelConfig.weights_dtype="bfloat16") — the
        # production configuration, same trade as the reference's fp16 cog
        # containers. Batch-1 diffusion is weight-bandwidth-bound, so
        # halving weight bytes is the single biggest single-chip lever.
        hb.set("casting weights to bf16 (one jitted program)")
        params16 = jax.jit(lambda p: cast_floating(p, "bfloat16"))(params)
        jax.block_until_ready(params16)
        sec16 = _timed_solutions(pipe, params16, 1, width=WIDTH,
                                 height=HEIGHT, steps=STEPS, rounds=2, hb=hb)
        track(_prod_line(
            3600.0 / sec16,
            f"solutions/hour/chip (SD-1.5 512x512, {STEPS} steps, "
            f"{SCHEDULER}, CFG, bf16 weights — measured on real TPU)",
            "stage_prod_measured_bf16_weights", "prod20_bf16"))

    # -- canonical-batch throughput curve (single-chip dp story) ----------
    if params16 is not None:
        for b in (2, 4, 8):
            if left() < 240:
                _note(f"skipping sweep b={b}: only {left():.0f}s left")
                break
            secb = _timed_solutions(pipe, params16, b, width=WIDTH,
                                    height=HEIGHT, steps=STEPS, rounds=1,
                                    hb=hb)
            vb = 3600.0 / secb
            sweep[str(b)] = round(vb, 2)
            track(_prod_line(
                vb,
                f"solutions/hour/chip (SD-1.5 512x512, {STEPS} steps, "
                f"{SCHEDULER}, CFG, bf16, canonical_batch={b} — measured "
                f"on real TPU)",
                "stage_batch_sweep", f"sweep_b{b}"))

    # -- sustained node-path rate: the REAL solver path (solve_cid_batch:
    # inference + PNG + CID, chunk-pipelined so host codec overlaps chip
    # compute) over a deep queue at canonical_batch 4 — the rate a
    # queue-saturated miner actually sustains. Rides the ladder's warm
    # executables (same pipe + params16 instance).
    if params16 is not None and left() > 240:
        from arbius_tpu.node.solver import (
            RegisteredModel,
            SD15Runner,
            solve_cid_batch,
        )
        from arbius_tpu.obs import Obs, use_obs
        from arbius_tpu.templates.engine import hydrate_input, load_template

        hb.set("sustained node-path rate (pipelined, batch 4)")
        tmpl = load_template("anythingv3")
        model = RegisteredModel(id="0x" + "00" * 32, template=tmpl,
                                runner=SD15Runner(pipe, params16))
        raw = {"prompt": "arbius bench task", "negative_prompt": "",
               "width": WIDTH, "height": HEIGHT,
               "num_inference_steps": STEPS, "scheduler": SCHEDULER}
        hyd = hydrate_input(dict(raw), tmpl)
        n_items = 12  # 3 chunks of 4: enough for the pipeline to fill
        solve_cid_batch(model, [(hyd, 5000)], canonical_batch=1)  # warm
        # per-stage timing rides the obs registry (docs/observability
        # .md): the BENCH line carries infer/encode/cid span stats so
        # perf PRs can show which stage moved, not just the total
        obs = Obs(journal_capacity=256)
        t0 = time.perf_counter()
        with use_obs(obs):
            solve_cid_batch(model,
                            [(hyd, 6000 + i) for i in range(n_items)],
                            canonical_batch=4)
        sec = (time.perf_counter() - t0) / n_items
        track(_prod_line(
            3600.0 / sec,
            f"solutions/hour/chip (SD-1.5 512x512, {STEPS} steps, "
            f"{SCHEDULER}, CFG, bf16, canonical_batch=4, SUSTAINED "
            f"node path incl. PNG+CID, PNG encode chunk-pipelined "
            f"with chip compute — measured on real TPU)",
            "stage_sustained_node_path", "sustained_b4",
            {"obs": obs.registry.summary()}))

    # -- headline: the best number must survive any later-stage overrun,
    # so it is emitted HERE, immediately after the ladder — and RE-emitted
    # after the family stages below so the session's final line is the
    # labeled best (family stages emit their own result lines)
    def _emit_headline() -> None:
        if best is not None:
            track(_prod_line(
                best[0], best[1], _headline_note(best[2]), "headline",
                {"batch_sweep": sweep} if sweep else None))

    _emit_headline()

    # -- other model families: kandinsky2 + zeroscope throughput rows.
    # Cold compiles are expensive, so these only run when a long session
    # budget remains. Their anchors differ from the anythingv3 metric,
    # so they are emitted as their own metric names with vs_baseline 0
    # and never compete for the headline.
    if os.environ.get("BENCH_FAMILIES", "auto") != "0" \
            and not goldens_only and left() > 1200:
        _family_stages(hb, left, lambda l: _emit(out_path, l), platform)
        _emit_headline()  # re-emit so the best number is the LAST line

    # -- goldens: admission vectors on this chip -----------------------
    if left() > 120 and os.environ.get("BENCH_RECORD_GOLDENS", "1") != "0":
        _record_goldens(hb, left, only_missing=goldens_only)
    _note("session complete")


def _family_stages(hb: _Heartbeat, left, emit, platform: str) -> None:
    """Throughput rows for the non-SD families. Each row is an END-TO-END solve rate —
    inference + codec + CID through the node's solver path — at a
    declared shape, measured after a warmup solve (compile excluded, as
    in the SD ladder). kandinsky2 runs its template default (768²×50,
    the reference's only enabled model — miner/src/index.ts:844-877);
    zeroscope first PROBES the template-default production shape
    (1024×576×24f×50 — never executed anywhere before r5) and falls back
    to a declared reduced shape if the 16 GB chip can't fit it, emitting
    the fit result either way."""
    from arbius_tpu.node.config import MiningConfig, ModelConfig
    from arbius_tpu.node.factory import build_registry
    from arbius_tpu.node.solver import solve_cid_batch
    from arbius_tpu.templates.engine import hydrate_input

    def series(template: str, raw: dict, batch: int, need_s: int,
               shape_desc: str, rounds: int = 1) -> bool:
        """Returns True iff a row was emitted (False = budget skip)."""
        if left() < need_s:
            _note(f"family {template}: skipped ({left():.0f}s < {need_s}s)")
            return False
        hb.set(f"family {template} {shape_desc} (compile+warmup)")
        mc = ModelConfig(id="0x" + "00" * 32, template=template,
                         weights_dtype="bfloat16")
        m = build_registry(MiningConfig(models=(mc,))).get(mc.id)
        hyd = hydrate_input(dict(raw), m.template)
        items = [(hyd, 1000 + i) for i in range(batch)]
        t0 = time.perf_counter()
        solve_cid_batch(m, items, canonical_batch=batch)
        warm_s = time.perf_counter() - t0
        _note(f"family {template}: warmup (incl compile) {warm_s:.0f}s")
        if left() < rounds * warm_s * 1.2 + 60:
            # the warmup still proves the shape EXECUTES on this chip
            # (the zeroscope prod-shape fit question) — record that even
            # when there's no budget for a clean post-compile timing
            emit({
                "metric": f"{template}_warmup_only",
                "value": round(warm_s, 1),
                "unit": (f"seconds for first solve INCLUDING compile "
                         f"({template} {shape_desc}, canonical_batch="
                         f"{batch}, bf16, platform={platform}) — shape "
                         "fits+executes; no post-compile timing budget"),
                "vs_baseline": 0.0,
                "note": "family_warmup_only",
                "stage": f"family_{template}_warmup",
                "elapsed_s": round(time.perf_counter() - _T0, 1),
            })
            return True
        hb.set(f"family {template} {shape_desc} (timing)")
        t0 = time.perf_counter()
        for r in range(rounds):
            solve_cid_batch(m, [(h, 2000 + r * batch + i)
                                for i, (h, _) in enumerate(items)],
                            canonical_batch=batch)
        sec = (time.perf_counter() - t0) / (rounds * batch)
        emit({
            "metric": f"{template}_solutions_per_hour_per_chip",
            "value": round(3600.0 / sec, 2),
            "unit": (f"solutions/hour/chip ({template} {shape_desc}, "
                     f"canonical_batch={batch}, bf16, end-to-end "
                     f"solve+codec+CID, platform={platform})"),
            "vs_baseline": 0.0,
            "note": "family_throughput (no cross-family anchor)",
            "stage": f"family_{template}_b{batch}",
            "elapsed_s": round(time.perf_counter() - _T0, 1),
        })
        return True

    # kandinsky2 template default (768², 50 prior+decoder steps)
    series("kandinsky2", {"prompt": "arbius bench task"}, 2, 2100,
           "768x768 template-default steps")

    # zeroscope: template-default production shape fit probe, then row
    prod = {"prompt": "arbius bench task", "negative_prompt": "",
            "width": 1024, "height": 576, "num_frames": 24,
            "num_inference_steps": 50}
    import jax

    ran = False
    try:
        ran = series("zeroscopev2xl", prod, 1, 2100,
                     "1024x576x24f prod-default")
    except jax.errors.JaxRuntimeError as e:
        # the probe's question is "does it fit 16 GB": only the chip
        # running out of memory is an answer, anything else is an error
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        emit({
            "metric": "zeroscopev2xl_prod_shape_fit",
            "value": 0.0,
            "unit": "prod-default 1024x576x24f x50 did NOT fit",
            "vs_baseline": 0.0,
            "note": f"{type(e).__name__}: {e}"[:300],
            "stage": "family_zeroscope_prod_probe",
            "elapsed_s": round(time.perf_counter() - _T0, 1),
        })
    if not ran:
        # declared reduced shape: same step count, half spatial — reached
        # both when the prod probe did not fit and when it was budget-
        # skipped (the cheaper shape may still fit the remaining budget)
        series("zeroscopev2xl",
               {**prod, "width": 576, "height": 320}, 1, 1200,
               "576x320x24f reduced (prod probe failed or skipped)")


def _record_goldens(hb: _Heartbeat, left, only_missing: bool = False) -> None:
    """Record boot-self-test golden CIDs on this chip at template
    default (production) shapes, written straight into goldens/. The
    repo's analogue of the reference's pinned admission CID
    (miner/src/index.ts:984-1001).

    `only_missing` (the BENCH_GOLDENS_ONLY session mode): skip rows whose
    vector file already exists, so a short session spends its whole
    budget on absent rows instead of re-verifying expensive existing
    ones."""
    import jax

    from arbius_tpu.node.config import MiningConfig, ModelConfig
    from arbius_tpu.node.factory import build_registry
    from arbius_tpu.node.solver import solve_cid
    from arbius_tpu.templates.engine import hydrate_input

    platform = jax.devices()[0].platform
    # anythingv3 goldens pin the METRIC shape (512×512×20 — same programs
    # the bench stages just compiled, so the executable cache is warm);
    # kandinsky2 pins its template-default 768².
    metric_shape = {"negative_prompt": "", "width": WIDTH, "height": HEIGHT,
                    "num_inference_steps": STEPS, "scheduler": SCHEDULER}
    PROBE = "8x128x128"  # robust_video_matting file-input probe clip shape
    # need = (post-ladder, goldens-only) min seconds left to attempt.
    # After the ladder the anythingv3 512x512x20 executables are warm
    # in-process; a goldens-only session compiles them cold unless the
    # persistent cache is warm — and a job must never start a compile it
    # has no budget to finish.
    jobs = [
        # (template, dtype, input-overrides, (need_warm, need_cold))
        ("anythingv3", "bfloat16", metric_shape, (420, 1800)),
        ("anythingv3", "float32", metric_shape, (360, 1800)),
        ("kandinsky2", "bfloat16", {}, (900, 900)),
        # video family at the CPU-golden shapes (cross-platform row pairs)
        ("zeroscopev2xl", "bfloat16",
         {"negative_prompt": "", "num_frames": 2, "width": 256,
          "height": 256, "num_inference_steps": 2}, (600, 600)),
        ("damo", "bfloat16",
         {"num_frames": 2, "num_inference_steps": 2}, (400, 400)),
        ("robust_video_matting", "bfloat16", {}, (150, 150)),
    ]
    jobs = [(t, d, o, n[1] if only_missing else n[0])
            for t, d, o, n in jobs]
    if only_missing:
        # cheap rows first: a short session should land the small absent
        # vectors before attempting a long video/kandinsky compile
        jobs.sort(key=lambda j: j[3])
    for template, dtype, overrides, need in jobs:
        resolve_file = None
        if template == "robust_video_matting":
            # file-input template: the shared probe-golden flow
            # (record-golden --probe-video uses the same helper, so CPU-
            # and TPU-recorded rows cannot drift structurally)
            from arbius_tpu.node.factory import probe_golden_input

            resolve_file, raw = probe_golden_input(PROBE)
        else:
            raw = {"prompt": "arbius test cat", **overrides}
        path = os.path.join(_REPO, "goldens",
                            f"{template}.full.{platform}.{dtype}.json")
        if only_missing and os.path.exists(path):
            try:
                with open(path) as f:
                    existing = json.load(f).get("golden", {}).get("input")
            except (OSError, ValueError):
                existing = None
            if existing == raw:
                _note(f"golden {template}/{dtype}: exists, skipped "
                      "(only-missing mode)")
                continue
            _note(f"golden {template}/{dtype}: exists but its input is "
                  "STALE vs the current job spec — re-recording")
        if left() < need:
            _note(f"golden {template}/{dtype}: skipped ({left():.0f}s left)")
            continue
        hb.set(f"golden {template} {dtype}")
        mc = ModelConfig(id="0x" + "00" * 32, template=template,
                         weights_dtype=dtype)
        m = build_registry(MiningConfig(models=(mc,)),
                           resolve_file=resolve_file).get(mc.id)
        hydrated = hydrate_input(dict(raw), m.template)
        t0 = time.perf_counter()
        cid, _files = solve_cid(m, hydrated, 1337)
        golden = {"input": raw, "seed": 1337, "cid": cid}
        if template == "robust_video_matting":
            golden["probe_video"] = PROBE  # regeneration recipe IN the vector
        rec = {
            "template": template, "platform": platform, "tiny": False,
            "weights_dtype": dtype,
            "elapsed_s": round(time.perf_counter() - t0, 1),
            "golden": golden,
        }
        with open(path, "w") as f:
            json.dump(rec, f)
        _note(f"golden recorded: {path} cid={cid}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--stage",
                    choices=["pipeline_ab", "mesh_ab", "sched_ab", "flood",
                             "coldboot", "quant_ab", "text_ab"],
                    help="one CPU A/B stage; omitted = the TPU session")
    ap.add_argument("--out")
    ns = ap.parse_args()
    if not ns.out:
        ns.out = os.path.join(_REPO, f".bench_{ns.stage or 'session'}.jsonl")
    {None: _stage_session, "pipeline_ab": _stage_pipeline_ab,
     "mesh_ab": _stage_mesh_ab, "sched_ab": _stage_sched_ab,
     "flood": _stage_flood, "coldboot": _stage_coldboot,
     "quant_ab": _stage_quant_ab, "text_ab": _stage_text_ab}[ns.stage](ns.out)
