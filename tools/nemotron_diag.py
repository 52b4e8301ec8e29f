#!/usr/bin/env python3
"""Diagnostics of the `nemotron_h` family on the chip, outside the
benchmark's harness (PERF.md section 6). One bucket of the cell
`nemotron3-ep4-2k-1k-backlog` at the configuration's shapes, weights made
as the harness makes them; a JSON line a reading, to standard output and
to `chiprun_out/<--out>`.

  scan    --draws a,b,..  one bucket a weight draw: the routers' share of
                          assignments on the held experts, the loop's
                          steps and accepted drafts, the bucket's
                          seconds, distinct bytes of the first answers

`--decode N` runs the bucket at another decode edge than the cell's (a
scan's held share is nearly all prefill's: 2,048 prompt positions in
five expert layers against two positions in six a step). `--tiny` runs
the same code on the CPU rehearsal's configuration
(tests/perfbench/tiny-nemotron).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "nemotron3-ep4-2k-1k-backlog"
TINY = (os.path.join(ROOT, "tests", "perfbench", "tiny-nemotron",
                     "manifest.json"), "tiny-nemotron-backlog")


def scan(args, emit) -> None:
    import jax
    import numpy as np

    from arbius_tpu.utils import enable_compile_cache
    from perfbench import manifest, traffic, weights
    from perfbench.reference.trinity import prompt_bucket

    enable_compile_cache()        # the harness's runs after it hit
    path, name = TINY if args.tiny else (manifest.DEFAULT_MANIFEST, CELL)
    cell = manifest.Cell(path, name)
    config = cell.config
    entry = config["models"][0]
    arch = dict(entry["arch"])
    if args.decode:
        arch["decode_buckets"] = [args.decode]
    pipe, _ = cell.family(entry["family"]).build(arch, "bf16")
    batch = config["node"]["canonical_batch"]
    gen = traffic.Traffic(cell.traffic, args.seed)
    tasks = [{**entry["defaults"], **gen.task()[1]} for _ in range(batch)]
    p = prompt_bucket(arch, tasks[0]["prompt"])
    t = arch["decode_buckets"][0]
    shapes = jax.eval_shape(lambda: pipe.init_params(
        seed=0, dtype=config["weights"]["dtype"]))
    for i, draw in enumerate(int(x) for x in args.draws.split(",") if x):
        params = jax.block_until_ready(weights.make(
            shapes, draw * 16, config["weights"]["init"]))
        t0 = time.perf_counter()
        tokens, routed, spec = jax.block_until_ready(pipe.generate(
            params, [x["prompt"] for x in tasks],
            [args.seed * 1000 + j for j in range(batch)], prompt_bucket=p,
            decode_bucket=t, as_device=True))
        secs = time.perf_counter() - t0           # the first compiles
        made, held = (int(x) for x in np.asarray(routed))
        steps, drafts, accepted, idle = (int(x) for x in np.asarray(spec))
        emit({"what": "scan", "draw": draw, "bucket_s": secs,
              "compiled_in_it": i == 0, "prompt_bucket": p,
              "decode_bucket": t, "assignments": made, "held": held,
              "held_pct": 100.0 * held / made, "steps": steps,
              "drafts": drafts, "accepted": accepted,
              "distinct_bytes": [int(len(set(r.tolist())))
                                 for r in np.asarray(tokens)[:4]]})
        del params, tokens


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("scan",))
    ap.add_argument("--seed", type=int, default=2147542301)
    ap.add_argument("--draws", default="")
    ap.add_argument("--decode", type=int, default=0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="nemotron_diag.jsonl")
    args = ap.parse_args(argv)
    device = {"platform": jax.devices()[0].platform,
              "kind": jax.devices()[0].device_kind}
    if device["platform"] != "tpu" and not args.tiny:
        print(f"no accelerator ({device}); --tiny rehearses on the CPU",
              file=sys.stderr)
        return 4
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out_path = os.path.join(ROOT, "chiprun_out", args.out)

    def emit(rec: dict) -> None:
        line = json.dumps({**rec, "seed": args.seed, "device": device})
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    scan(args, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
