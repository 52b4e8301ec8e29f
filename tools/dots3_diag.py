#!/usr/bin/env python3
"""Diagnostics of the `dots3_note` family on the chip, outside the
benchmark's harness (PERF.md section 6). One bucket of the cell
`dots3-ep8-8k-1k-backlog` at the configuration's shapes, weights made as
the harness makes them; a JSON line a reading, to standard output and to
`chiprun_out/<--out>`.

  scan    --draws a,b,..  one bucket a weight draw: the routers' share of
                          assignments on the held experts, the bucket's
                          seconds, distinct bytes of the first answers
  kernel                  one sliding layer's prefill attention at the
                          cell's shapes (8,192 positions, 64 heads of
                          192 + 64 | 128, bfloat16): the banded kernel
                          and the walk, milliseconds each and the largest
                          difference between them

`--tiny` runs the same code on the CPU rehearsal's configuration
(tests/perfbench/tiny-dots3), the kernel in interpret mode.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "dots3-ep8-8k-1k-backlog"
TINY = (os.path.join(ROOT, "tests", "perfbench", "tiny-dots3",
                     "manifest.json"), "tiny-dots3-backlog")


class Bench:
    """The cell's pipeline, one bucket of its traffic, weights by draw."""

    def __init__(self, tiny: bool, seed: int):
        import jax

        from perfbench import manifest, traffic
        from perfbench.reference.trinity import decode_bucket, prompt_bucket

        from arbius_tpu.utils import enable_compile_cache

        enable_compile_cache()        # the harness's runs after it hit
        path, name = TINY if tiny else (manifest.DEFAULT_MANIFEST, CELL)
        self.cell = manifest.Cell(path, name)
        self.config = self.cell.config
        self.entry = self.config["models"][0]
        self.arch = self.entry["arch"]
        self.family = self.cell.family(self.entry["family"])
        self.pipe, _ = self.family.build(self.arch, "bf16")
        self.batch = self.config["node"]["canonical_batch"]
        gen = traffic.Traffic(self.cell.traffic, seed)
        self.tasks = [{**self.entry["defaults"], **gen.task()[1]}
                      for _ in range(self.batch)]
        self.p = prompt_bucket(self.arch, self.tasks[0]["prompt"])
        self.t = decode_bucket(self.arch,
                               int(self.tasks[0]["max_new_tokens"]))
        self.seeds = [seed * 1000 + i for i in range(self.batch)]
        self.shapes = jax.eval_shape(lambda: self.pipe.init_params(
            seed=0, dtype=self.config["weights"]["dtype"]))
        self.device = {"platform": jax.devices()[0].platform,
                       "kind": jax.devices()[0].device_kind}

    def weights(self, draw: int):
        import jax

        from perfbench import weights

        params = weights.make(self.shapes, int(draw) * 16,
                              self.config["weights"]["init"])
        return jax.block_until_ready(params)

    def generate(self, params):
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(self.pipe.generate(
            params, [x["prompt"] for x in self.tasks], self.seeds,
            prompt_bucket=self.p, decode_bucket=self.t, as_device=True))
        return out, time.perf_counter() - t0


def scan(b: Bench, draws: list[int], emit) -> None:
    import numpy as np

    for i, draw in enumerate(draws):
        params = b.weights(draw)
        (tokens, routed), secs = b.generate(params)    # the first compiles
        made, held = (int(x) for x in np.asarray(routed))
        emit({"what": "scan", "draw": draw, "bucket_s": secs,
              "compiled_in_it": i == 0, "assignments": made, "held": held,
              "held_pct": 100.0 * held / made,
              "distinct_bytes": [int(len(set(r.tolist())))
                                 for r in np.asarray(tokens)[:4]]})
        del params, tokens, routed


def kernel(b: Bench, emit) -> None:
    import jax
    import jax.numpy as jnp

    from arbius_tpu.ops import selected_flash

    a = b.pipe.config.attn("sliding")
    p = b.p
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k = (jax.random.normal(x, (p, a.heads, a.qk_head_dim), jnp.bfloat16)
            for x in ks[:2])
    v = jax.random.normal(ks[2], (p, a.heads, a.v_head_dim), jnp.bfloat16)
    kw = dict(window=a.window, scale=a.softmax_scale)
    walk = jax.jit(lambda q, k, v: selected_flash.window_walk(
        q, k, v, block=min(512, p), **kw))
    interpret = jax.default_backend() != "tpu"        # --tiny
    band = jax.jit(lambda q, k, v: selected_flash.window_flash_attention(
        q, k, v, interpret=interpret, **kw))
    out = {}
    for name, fn in (("kernel", band), ("walk", walk)):
        res = jax.block_until_ready(fn(q, k, v))
        secs = []
        for _ in range(5):
            t0 = time.perf_counter()
            res = jax.block_until_ready(fn(q, k, v))
            secs.append(time.perf_counter() - t0)
        out[name] = (res, min(secs))
    diff = float(jnp.abs(out["kernel"][0].astype(jnp.float32)
                         - out["walk"][0].astype(jnp.float32)).max())
    emit({"what": "kernel", "positions": p, "heads": a.heads,
          "dk": a.qk_head_dim, "dv": a.v_head_dim, "window": a.window,
          "kernel_ms": 1e3 * out["kernel"][1],
          "walk_ms": 1e3 * out["walk"][1], "max_abs_diff": diff,
          "walked_blocks": selected_flash.walk_blocks(p, p, a.heads,
                                                      a.window)})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("scan", "kernel"))
    ap.add_argument("--seed", type=int, default=2147540001)
    ap.add_argument("--draws", default="")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="dots3_diag.jsonl")
    args = ap.parse_args(argv)
    b = Bench(args.tiny, args.seed)
    if b.device["platform"] != "tpu" and not args.tiny:
        print(f"no accelerator ({b.device}); --tiny rehearses on the CPU",
              file=sys.stderr)
        return 4
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", args.out)

    def emit(rec: dict) -> None:
        line = json.dumps({**rec, "seed": args.seed, "device": b.device})
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    if args.mode == "scan":
        scan(b, [int(x) for x in args.draws.split(",") if x], emit)
    else:
        kernel(b, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
