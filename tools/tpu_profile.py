"""TPU profiling session — attribute the anythingv3 solve's wall time.

The r04 sessions put anythingv3 at ~1.0 s per solution with no trace
behind the number (ROADMAP S1). This tool is that profile: ONE process
on the chip (phase heartbeat on stderr, budget gates), and in it:

  device     platform / device_kind / HBM — names the chip so MFU math
             uses the real peak, not a guess.
  matmul     big bf16 matmul microbench — the chip's ACHIEVABLE matmul
             rate through this runtime (the MFU denominator that
             matters; paper peaks are not reachable by real programs).
  attn       flash-vs-einsum A/B at the exact SD-1.5 self-attention
             shapes (S=4096/d=40, S=1024/d=80) — answers the
             question "does flash even beat XLA einsum at SD shapes?"
             (ops/flash.py pads d to 128 lanes; einsum materializes S²).
  conv       the dominant 3x3 conv shape — reference MXU rate for the
             conv-heavy UNet trunk.
  segments   text / single CFG UNet step / VAE decode, each jitted and
             timed alone: 20*unet + vae + text vs the measured full
             generate attributes the gap (dispatch, transfer, sampler).
  trace      jax.profiler trace around warmed generate calls, written to
             bench_runs/traces/ (ROADMAP S1).

Results stream as JSON lines into bench_runs/ (append-only file named by
date) the moment each exists, so a killed session keeps its evidence.
Run:  python tools/tpu_profile.py            (on the chip)
      JAX_PLATFORMS=cpu python tools/tpu_profile.py --cpu   (harness test)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

_T0 = time.perf_counter()
BUDGET_S = int(os.environ.get("PROFILE_BUDGET_S", "3300"))
MARGIN_S = 150
BATCH = int(os.environ.get("PROFILE_BATCH", "4"))
WIDTH = HEIGHT = 512
STEPS = 20
SCHEDULER = "DPMSolverMultistep"


def _note(msg: str) -> None:
    print(f"[profile +{time.perf_counter() - _T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


def _left(deadline: float) -> float:
    return deadline - time.perf_counter()


def _timeit(fn, *args, warmup: int = 2, rounds: int = 5) -> float:
    """Median seconds per call, after warmup (compile + cache)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (harness self-test; tiny shapes)")
    ns = ap.parse_args()

    deadline = _T0 + BUDGET_S - MARGIN_S

    if ns.cpu:
        from arbius_tpu.utils import force_cpu_devices
        force_cpu_devices(1)

    from arbius_tpu.utils import enable_compile_cache
    from arbius_tpu.utils.session import Heartbeat

    enable_compile_cache()
    hb = Heartbeat("profile", _note)
    hb.set(f"starting (budget {BUDGET_S}s)")

    import jax
    import jax.numpy as jnp
    import numpy as np

    devs = jax.devices()
    platform = devs[0].platform
    if not ns.cpu and platform != "tpu":
        # full-shape probes on the host would take hours, and their
        # numbers would not be the chip's
        raise SystemExit(f"tpu_profile: the backend is {platform!r}, not "
                         "a TPU (--cpu runs the tiny harness self-test)")
    out_path = os.path.join(
        _REPO, "bench_runs",
        f"r05_profile_{platform}_{BATCH}b.jsonl")

    def emit(line: dict) -> None:
        line["elapsed_s"] = round(time.perf_counter() - _T0, 1)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
            f.flush()
            os.fsync(f.fileno())
        _note(f"result: {json.dumps(line)}")

    # -- device ----------------------------------------------------------
    d = devs[0]
    mem = {}
    try:
        stats = d.memory_stats() or {}
        mem = {k: stats[k] for k in ("bytes_limit", "bytes_in_use")
               if k in stats}
    except Exception:
        pass
    emit({"probe": "device", "platform": platform,
          "device_kind": getattr(d, "device_kind", "?"),
          "n_devices": len(devs), **mem})

    tiny = ns.cpu  # CPU harness test uses toy shapes throughout

    # -- matmul achievable peak ------------------------------------------
    hb.set("matmul microbench")
    try:
        n = 1024 if tiny else 8192
        key = jax.random.PRNGKey(0)
        a = jax.random.normal(key, (n, n), jnp.bfloat16)
        b = jax.random.normal(jax.random.fold_in(key, 1), (n, n), jnp.bfloat16)
        mm = jax.jit(lambda a, b: a @ b)
        sec = _timeit(mm, a, b)
        tflops = 2 * n ** 3 / sec / 1e12
        emit({"probe": "matmul_bf16", "n": n, "sec": round(sec, 5),
              "achieved_tflops": round(tflops, 1)})
    except Exception as e:
        emit({"probe": "matmul_bf16", "error": f"{type(e).__name__}: {e}"})

    # -- attention A/B at the real SD-1.5 self-attention shapes ----------
    # [B*CFG, H, S, D] with B=BATCH. FLOPs = 2 * 2 * BH * S^2 * D.
    from arbius_tpu.ops.flash import flash_attention
    from arbius_tpu.ops.ring import sp_attention_reference

    shapes = [(2 * BATCH, 8, 64, 16)] if tiny else [
        (2 * BATCH, 8, 4096, 40),   # level-0: 64x64 tokens, ch=320
        (2 * BATCH, 8, 1024, 80),   # level-1: 32x32 tokens, ch=640
        (2 * BATCH, 8, 256, 160),   # level-2: 16x16 tokens, ch=1280
    ]
    for bh, h, s, dd in shapes:
        if _left(deadline) < 300:
            _note("skipping remaining attention probes (budget)")
            break
        hb.set(f"attn A/B S={s} d={dd}")
        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (bh, h, s, dd), jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(key, 1), (bh, h, s, dd),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(key, 2), (bh, h, s, dd),
                              jnp.bfloat16)
        flops = 2 * 2 * bh * h * s * s * dd
        import functools
        for name, fn in (
                ("flash", jax.jit(flash_attention)),
                ("flash_nopad", jax.jit(functools.partial(
                    flash_attention, pad_d=False))),
                ("einsum", jax.jit(sp_attention_reference))):
            try:
                sec = _timeit(fn, q, k, v)
                emit({"probe": "attention", "impl": name, "B": bh, "H": h,
                      "S": s, "D": dd, "sec": round(sec, 6),
                      "achieved_tflops": round(flops / sec / 1e12, 2)})
            except Exception as e:
                emit({"probe": "attention", "impl": name, "S": s, "D": dd,
                      "error": f"{type(e).__name__}: {e}"})

    # -- dominant conv shape ---------------------------------------------
    hb.set("conv microbench")
    try:
        cb, ch, hw = (2, 16, 16) if tiny else (2 * BATCH, 320, 64)
        x = jax.random.normal(jax.random.PRNGKey(9), (cb, hw, hw, ch),
                              jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(10), (3, 3, ch, ch),
                              jnp.bfloat16)
        conv = jax.jit(lambda x, w: jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")))
        sec = _timeit(conv, x, w)
        flops = 2 * cb * hw * hw * 9 * ch * ch
        emit({"probe": "conv3x3", "B": cb, "HW": hw, "C": ch,
              "sec": round(sec, 6),
              "achieved_tflops": round(flops / sec / 1e12, 2)})
    except Exception as e:
        emit({"probe": "conv3x3", "error": f"{type(e).__name__}: {e}"})

    # -- full pipeline: segment attribution ------------------------------
    from arbius_tpu.models.sd15 import ByteTokenizer, SD15Config, SD15Pipeline
    from arbius_tpu.node.factory import tiny_byte_tokenizer

    if tiny:
        cfg = SD15Config.tiny()
        pipe = SD15Pipeline(cfg, tokenizer=tiny_byte_tokenizer(cfg.text))
        w_, h_, steps_ = 128, 128, 4
    else:
        cfg = SD15Config()
        pipe = SD15Pipeline(cfg, tokenizer=ByteTokenizer())
        w_, h_, steps_ = WIDTH, HEIGHT, STEPS

    if _left(deadline) < 600:
        _note("not enough budget for pipeline segments; exiting early")
        hb.stop()
        return

    hb.set("init_params (bf16, jitted on-device)")
    params = pipe.init_params(seed=0, height=h_, width=w_, dtype="bfloat16")
    jax.block_until_ready(params)
    lh, lw = h_ // 8, w_ // 8

    # text encoder alone
    hb.set("segment: text encoder")
    try:
        ids = jnp.zeros((BATCH, cfg.text.max_length), jnp.int32)
        te = jax.jit(lambda p, i: pipe.text_encoder.apply({"params": p}, i))
        sec = _timeit(te, params["text"], ids)
        emit({"probe": "segment", "name": "text_encoder", "batch": BATCH,
              "sec": round(sec, 5)})
    except Exception as e:
        emit({"probe": "segment", "name": "text_encoder",
              "error": f"{type(e).__name__}: {e}"})

    # one CFG UNet step alone (2B batch, the scan body's cost) — under
    # EACH attention impl: the program-level A/B that decides the
    # production dispatch (kernel microbenches above miss fusion effects)
    try:
        xin = jax.random.normal(jax.random.PRNGKey(3),
                                (2 * BATCH, lh, lw, cfg.unet.in_channels),
                                jnp.bfloat16)
        t = jnp.full((2 * BATCH,), 500.0)
        ctx = jax.random.normal(jax.random.PRNGKey(4),
                                (2 * BATCH, cfg.text.max_length,
                                 cfg.unet.context_dim), jnp.bfloat16)
        impls = ("auto",) if tiny else ("auto", "flash_nopad", "einsum")
    except Exception as e:  # input setup failure must not cost the
        # vae/full/trace probes
        emit({"probe": "segment", "name": "unet_step_cfg",
              "error": f"setup: {type(e).__name__}: {e}"})
        impls = ()
    # restore the operator's pinned impl afterwards, not "auto" — the
    # remaining probes (vae/full_generate/trace) must run under the
    # dispatch the operator launched with. The impl is pinned at import
    # (ops/flash.py); the A/B threads each candidate through the explicit
    # setter and re-jits, the one legitimate way to flip it in-process.
    from arbius_tpu.ops.flash import set_attention_impl

    for impl in impls:
        if impl != "auto" and _left(deadline) < 240:
            _note(f"skipping unet A/B impl={impl} (budget)")
            continue
        hb.set(f"segment: unet step (CFG) attn={impl}")
        prior_impl = set_attention_impl(impl)
        try:
            un = jax.jit(lambda p, x, t, c: pipe.unet.apply(
                {"params": p}, x, t, c))
            sec = _timeit(un, params["unet"], xin, t, ctx)
            emit({"probe": "segment", "name": "unet_step_cfg",
                  "attn_impl": impl, "batch": BATCH, "sec": round(sec, 5),
                  "per_solve_x_steps": round(sec * steps_, 4)})
        except Exception as e:
            emit({"probe": "segment", "name": "unet_step_cfg",
                  "attn_impl": impl, "error": f"{type(e).__name__}: {e}"})
        finally:
            set_attention_impl(prior_impl)

    # VAE decode alone
    hb.set("segment: vae decode")
    try:
        from arbius_tpu.models.sd15.vae import decode_to_images
        lat = jax.random.normal(jax.random.PRNGKey(5),
                                (BATCH, lh, lw, cfg.unet.in_channels),
                                jnp.bfloat16)
        va = jax.jit(lambda p, z: decode_to_images(
            pipe.vae.apply({"params": p}, z)))
        sec = _timeit(va, params["vae"], lat)
        emit({"probe": "segment", "name": "vae_decode", "batch": BATCH,
              "sec": round(sec, 5)})
    except Exception as e:
        emit({"probe": "segment", "name": "vae_decode",
              "error": f"{type(e).__name__}: {e}"})

    # full generate (the metric path, host round-trip included)
    hb.set("segment: full generate")
    kw = dict(width=w_, height=h_, num_inference_steps=steps_,
              scheduler=SCHEDULER, guidance_scale=12.0)
    prompts = [f"arbius profile task {i}" for i in range(BATCH)]
    negs = [""] * BATCH
    out = pipe.generate(params, prompts, negs, list(range(BATCH)), **kw)
    assert out.dtype == np.uint8
    t0 = time.perf_counter()
    rounds = 3
    for r in range(rounds):
        pipe.generate(params, prompts, negs,
                      [(r + 1) * BATCH + i for i in range(BATCH)], **kw)
    sec_full = (time.perf_counter() - t0) / rounds
    emit({"probe": "segment", "name": "full_generate", "batch": BATCH,
          "steps": steps_, "sec": round(sec_full, 4),
          "sol_per_hour": round(3600.0 / (sec_full / BATCH), 1)})

    # -- profiler trace (the committed artifact) -------------------------
    if _left(deadline) > 120:
        hb.set("jax.profiler trace around 2 generates")
        trace_dir = os.path.join(
            _REPO, "bench_runs", "traces",
            f"r05_{platform}_prod_b{BATCH}" if not tiny
            else f"r05_{platform}_tiny_b{BATCH}")
        try:
            os.makedirs(trace_dir, exist_ok=True)
            with jax.profiler.trace(trace_dir):
                for r in (7, 8):
                    pipe.generate(params, prompts, negs,
                                  [r * BATCH + i for i in range(BATCH)], **kw)
            emit({"probe": "trace", "dir": os.path.relpath(trace_dir, _REPO),
                  "ok": True})
        except Exception as e:
            emit({"probe": "trace", "error": f"{type(e).__name__}: {e}"})

    hb.stop()
    _note("profile session complete")


if __name__ == "__main__":
    main()
