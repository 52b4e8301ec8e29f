"""Chip smoke: the anythingv3 node path, end to end, on the accelerator.

One process, no children. Through the node's normal constructors —
`MiningConfig` (code defaults) → `node.factory.build_registry` →
`MinerNode(LocalChain(Engine))` → `boot()` with the golden self-test →
`ControlRPC(port=0)` → `tick()` — with one model: anythingv3 = full
SD-1.5 (860M), bf16 weights, random init seed 0, 512×512, 20 steps,
DPMSolverMultistep, CFG. It submits 8 tasks with distinct prompts,
ticks until all are solved, advances chain time, ticks until all are
claimed, scrapes `GET /metrics` once, and checks:

  - the backend is a TPU (it neither sets nor clears JAX_PLATFORMS);
  - every task is solved AND claimed, nothing was quarantined, and
    `arbius_solutions_submitted_total` equals the task count;
  - the bucket program the chip ran contains the Mosaic flash kernel
    (`tpu_custom_call` in its lowering) — ops/flash.py falls back to the
    einsum reference off-TPU without a word;
  - the golden input solved at boot (against the vector in goldens/)
    and again after the burst on the warm executables gives one CID.

On success the last two lines of stdout are a JSON summary (versions,
set-up seconds, burst seconds, peak device memory, persistent-cache hits
— smoke output, not benchmark numbers; it ends with `"claim": null`) and
`{"ok": true, "device": {...}}`. Any failed check, or a backend that is
not a TPU, exits non-zero with the summary on stderr and no result on
stdout.

    python chip_smoke.py                   # the chip, one device
    python chip_smoke.py --canonical-batch 4 [--dp 4]   # builder-run
    JAX_PLATFORMS=cpu python chip_smoke.py --preset tiny   # tier-1 body

`--preset tiny` (tiny topology, 128×128×2, golden recorded in-run) is
the only way off the chip.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time
import urllib.request

_T0 = time.perf_counter()
_REPO = os.path.dirname(os.path.abspath(__file__))

N_TASKS = 8
GOLDEN_SEED = 1337
# preset → (tiny topology?, golden vector in the repo, task/golden shape)
PRESETS = {
    "full": (False, "goldens/anythingv3.full.tpu.bfloat16.json",
             {"width": 512, "height": 512, "num_inference_steps": 20,
              "scheduler": "DPMSolverMultistep"}),
    "tiny": (True, None,
             {"width": 128, "height": 128, "num_inference_steps": 2,
              "scheduler": "DDIM"}),
}
EXIT_FAILED, EXIT_NOT_TPU = 1, 4


def _note(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - _T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


def run_burst(node, eng, user: str, mid_b: bytes, n_tasks: int,
              task_input: dict, note=_note) -> dict:
    """Submit `n_tasks` at once and mine them through the full lifecycle:
    tick until none is left to solve, advance chain time past the claim
    delay, tick until none is left to claim. Returns the bookkeeping the
    smoke judges: solved/claimed counts, seconds from first submit to
    last solution on chain, and each task's CID."""
    claimed_before = node.metrics.solutions_claimed
    t0 = time.perf_counter()
    pending = [eng.submit_task(user, 0, user, mid_b, 0, json.dumps({
        "prompt": f"arbius smoke test {i}, a cat mining on a tpu",
        "negative_prompt": "", **task_input}).encode())
        for i in range(n_tasks)]
    tids = list(pending)
    note(f"{n_tasks} tasks submitted")
    last_solution = None
    while node.tick():
        left = [t for t in pending if t not in eng.solutions]
        if len(left) < len(pending):
            last_solution = time.perf_counter() - t0
        pending = left
    solved = n_tasks - len(pending)
    note(f"{solved}/{n_tasks} solved, last solution {last_solution}s "
         "after first submit")
    eng.advance_time(2200)
    while node.tick():
        pass
    return {
        "n_tasks": n_tasks, "solved": solved,
        # delta, not the node-lifetime counter: earlier claims are not
        # this burst's
        "claimed": node.metrics.solutions_claimed - claimed_before,
        "submit_to_last_solution_s":
            None if last_solution is None else round(last_solution, 2),
        "cids": {"0x" + t.hex(): "0x" + eng.solutions[t].cid.hex()
                 for t in tids if t in eng.solutions},
    }


def _metric(text: str, name: str) -> float | None:
    """Value of the unlabelled sample `name` in a Prometheus exposition."""
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return None


def _n_entries(cache_dir: str) -> int:
    """Files in the compile cache; a directory placed from outside may
    not exist until jax first writes to it."""
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def _libtpu_version() -> str | None:
    from importlib import metadata

    for dist in ("libtpu", "libtpu-nightly"):
        try:
            return metadata.version(dist)
        except metadata.PackageNotFoundError:
            continue
    return None


def _bucket_has_mosaic(runner, batch: int, shape: dict) -> bool:
    """Lower the bucket program the node dispatched and look for the
    Mosaic custom call in it."""
    import jax
    import jax.numpy as jnp

    pipe = runner.pipeline
    fn = pipe.compiled_bucket(batch, shape["height"], shape["width"],
                              shape["num_inference_steps"],
                              shape["scheduler"])
    sds = jax.ShapeDtypeStruct
    length = pipe.config.text.max_length
    lowered = fn.lower(
        runner.params, sds((batch, length), jnp.int32),
        sds((batch, length), jnp.int32), sds((batch,), jnp.float32),
        sds((batch,), jnp.uint32), sds((batch,), jnp.uint32))
    return "tpu_custom_call" in lowered.as_text()


def _mesh_facts(runner, batch: int, shape: dict, metrics_text: str) -> dict:
    """What a dp mesh must show on hardware: the image batch sharded
    over distinct devices, every one of them holding bytes."""
    import jax

    images = runner.pipeline.generate(
        runner.params, [f"mesh probe {i}" for i in range(batch)],
        [""] * batch, list(range(batch)), width=shape["width"],
        height=shape["height"],
        num_inference_steps=shape["num_inference_steps"],
        scheduler=shape["scheduler"], as_device=True)
    jax.block_until_ready(images)
    shard_devices = [s.device for s in images.addressable_shards]
    in_use = {}
    for d in shard_devices:
        stats = d.memory_stats()
        in_use[str(d.id)] = None if stats is None \
            else stats.get("bytes_in_use")
    return {
        "arbius_mesh_devices": _metric(metrics_text, "arbius_mesh_devices"),
        "image_shard_devices": sorted(d.id for d in shard_devices),
        "bytes_in_use": in_use,
    }


def smoke(preset: str, canonical_batch: int = 1, dp: int = 0) -> dict:
    """Run every phase; returns the summary with a `failures` list (empty
    = pass). Raises SystemExit(EXIT_NOT_TPU) before any model is built
    when the full preset finds no TPU."""
    tiny, golden_file, shape = PRESETS[preset]
    import jax
    import jaxlib

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    summary: dict = {
        "smoke": "chip_smoke", "preset": preset, "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": _libtpu_version()},
        "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS"),
        "canonical_batch": canonical_batch,
        "mesh": {"dp": dp} if dp else None,
    }
    _note(f"device={device} JAX_PLATFORMS={summary['JAX_PLATFORMS']!r}")
    if not tiny and device["platform"] != "tpu":
        _note("the backend is not a TPU and only --preset tiny may run "
              f"off the chip — refusing (exit {EXIT_NOT_TPU})")
        raise SystemExit(EXIT_NOT_TPU)

    from arbius_tpu.chain import WAD, Engine, TokenLedger
    from arbius_tpu.codecs import _native
    from arbius_tpu.node import LocalChain, MinerNode
    from arbius_tpu.node.config import MiningConfig, ModelConfig
    from arbius_tpu.node.factory import build_registry
    from arbius_tpu.node.rpc import ControlRPC
    from arbius_tpu.node.solver import solve_cid
    from arbius_tpu.templates.engine import hydrate_input
    from arbius_tpu.utils import enable_compile_cache

    # persistent-cache traffic, counted by jax itself; the directory is
    # the one enable_compile_cache decides (node.boot() calls it again)
    cache_events = {"hits": 0, "misses": 0}

    def _on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1

    jax.monitoring.register_event_listener(_on_event)
    cache_dir = enable_compile_cache()
    entries_before = _n_entries(cache_dir)

    miner, user = "0x" + "aa" * 20, "0x" + "01" * 20
    tok = TokenLedger()
    eng = Engine(tok, start_time=0)
    tok.mint(Engine.ADDRESS, 600_000 * WAD)
    for a in (miner, user):
        tok.mint(a, 1000 * WAD)
        tok.approve(a, Engine.ADDRESS, 10**30)
    with open(os.path.join(_REPO, "arbius_tpu", "templates", "data",
                           "anythingv3.json"), "rb") as f:
        mid_b = eng.register_model(user, user, 0, f.read())
    mid = "0x" + mid_b.hex()

    golden = None
    if golden_file is not None:
        with open(os.path.join(_REPO, golden_file)) as f:
            golden = json.load(f)["golden"]
    _note(f"build registry ({'tiny' if tiny else 'full 860M'} topology, "
          "bf16, random init seed 0)")
    t_setup = time.perf_counter()
    cfg = MiningConfig(
        canonical_batch=canonical_batch, mesh={"dp": dp} if dp else None,
        models=(ModelConfig(id=mid, template="anythingv3", tiny=tiny,
                            weights_dtype="bfloat16", golden=golden),))
    registry = build_registry(cfg)
    model = registry.get(mid)
    if golden is None:
        # no vector in the repo for this topology/platform: record one
        # now, so boot's self-test and the after-burst solve still
        # compare three solves of one input
        raw = {"prompt": "arbius test cat", "negative_prompt": "", **shape}
        cid, _ = solve_cid(model, hydrate_input(dict(raw), model.template),
                           GOLDEN_SEED)
        model.golden = (raw, GOLDEN_SEED, cid)
    golden_cid = model.golden[2]
    chain = LocalChain(eng, miner)
    chain.validator_deposit(100 * WAD)
    node = MinerNode(chain, cfg, registry)
    rpc = None
    try:
        _note("boot: golden self-test against "
              f"{golden_file or 'the in-run vector'} (includes compile)")
        node.boot()  # raises BootError on a CID mismatch
        summary["setup_s"] = round(time.perf_counter() - t_setup, 1)
        summary["golden"] = {"source": golden_file or "recorded in-run",
                             "cid": golden_cid, "boot": "passed"}
        _note(f"boot self-test passed, set-up {summary['setup_s']}s")
        rpc = ControlRPC(node, port=0)
        rpc.start()

        failures = _phases(summary, node, eng, model, rpc.port, user, mid_b,
                           shape, tiny)
    finally:
        if rpc is not None:
            rpc.stop()
        node.close()
        jax.monitoring.unregister_event_listener(_on_event)

    stats = devs[0].memory_stats()
    summary["peak_bytes_in_use"] = None if stats is None \
        else stats.get("peak_bytes_in_use")
    summary["deflate_impl"] = _native.deflate_impl()
    summary["compile_cache"] = {
        "dir": cache_dir, "entries_before": entries_before,
        "entries_after": _n_entries(cache_dir), **cache_events}
    summary["elapsed_s"] = round(time.perf_counter() - _T0, 1)
    summary["failures"] = failures
    summary["claim"] = None
    return summary


def _phases(summary: dict, node, eng, model, rpc_port: int, user: str,
            mid_b: bytes, shape: dict, tiny: bool) -> list[str]:
    """The burst and the checks after it, in order; a failed phase ends
    the run (what follows would judge a node that did not mine)."""
    from arbius_tpu.node.solver import solve_cid
    from arbius_tpu.templates.engine import hydrate_input

    batch = node.config.canonical_batch
    dp = (node.config.mesh or {}).get("dp", 0)
    burst = run_burst(node, eng, user, mid_b, N_TASKS, shape)
    summary["burst"] = burst
    with urllib.request.urlopen(
            f"http://127.0.0.1:{rpc_port}/metrics", timeout=30) as r:
        metrics_text = r.read().decode()
    submitted = _metric(metrics_text, "arbius_solutions_submitted_total")
    failed_jobs = [m for m, _ in node.db.failed_jobs()]
    summary["solutions_submitted_total"] = submitted
    summary["failed_jobs"] = failed_jobs
    failures = []
    if burst["solved"] != N_TASKS or burst["claimed"] != N_TASKS:
        failures.append(f"solved {burst['solved']}/{N_TASKS}, claimed "
                        f"{burst['claimed']}/{N_TASKS}")
    if failed_jobs:
        failures.append(f"quarantined jobs: {failed_jobs}")
    if submitted != N_TASKS:
        failures.append("arbius_solutions_submitted_total on /metrics is "
                        f"{submitted}, not {N_TASKS}")
    if failures:
        return failures

    _note("golden input again, on the warm executables")
    golden_input, golden_seed, golden_cid = model.golden
    again, _ = solve_cid(
        model, hydrate_input(dict(golden_input), model.template),
        golden_seed)
    summary["golden"]["after_burst"] = again
    if again.lower() != golden_cid.lower():
        return [f"golden after the burst {again} != boot's {golden_cid}: "
                "not deterministic in-run"]

    mosaic = _bucket_has_mosaic(model.runner, batch, shape)
    summary["mosaic_kernel_in_bucket"] = mosaic
    if not tiny and not mosaic:
        return ["no tpu_custom_call in the bucket program: the chip ran "
                "the einsum reference, not the kernel"]

    if dp:
        facts = _mesh_facts(model.runner, batch, shape, metrics_text)
        summary["mesh_facts"] = facts
        if facts["arbius_mesh_devices"] != dp \
                or len(set(facts["image_shard_devices"])) != dp \
                or not all(facts["bytes_in_use"].values()):
            return [f"dp={dp} mesh not on {dp} devices: {facts}"]
    return []


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--preset", choices=sorted(PRESETS), default="full",
                    help="tiny is the only way off the chip (tier-1)")
    ap.add_argument("--canonical-batch", type=int, default=1)
    ap.add_argument("--dp", type=int, default=0,
                    help="solve mesh {dp: N}; 0 = the single-device path")
    ns = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    summary = smoke(ns.preset, ns.canonical_batch, ns.dp)
    if summary["failures"]:
        print(json.dumps(summary), file=sys.stderr, flush=True)
        _note("FAILED: " + "; ".join(summary["failures"]))
        return EXIT_FAILED
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
