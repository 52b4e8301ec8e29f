#!/usr/bin/env python3
"""The builder's diagnostics of the `joyai_llm_flash` family on the chip,
outside the benchmark's harness (PERF.md section 6, PR 36). One bucket
of the cell `joyai-ep1-2k-512-backlog` at the configuration's shapes,
weights made as the harness makes them; a JSON line a reading, to
standard output and to `chiprun_out/<--out>`.

  scan    --draws a,b,..  one bucket a weight draw: the loop's counts
                          (steps, drafts, accepted), the bucket's seconds
  steps                   at the stated draw: prefill | the speculative
                          loop | a ONE-token loop of the main model alone
                          (this family serves no such program: it exists
                          here and in tests/test_joyai_flash.py), seconds
                          each, seconds a step, and how many served ids
                          the two loops disagree on in bfloat16
  module  --tasks n       the module against the plain reference: the
                          drafts the program's module makes teacher-forced
                          on the served ids (through its latent cache, one
                          position a step), read against the reference's
                          module logits by the family's `gaps`; the main
                          model's ids beside them

  experts --cells a,b,..  one `route` + `routed_experts` call a walk —
                          the tile loop, the grouped product
                          (`ops.grouped`), and at this family's decode
                          shapes `lax.ragged_dot` in gmm's place — at
                          each `expert_calls` shape of each text cell
                          (decode steps, drafts, prefill blocks or
                          chunks), one expert layer of random weights:
                          microseconds a call, the touched experts'
                          kernel bytes, GB/s against 819

`--tiny` runs the same code on the CPU rehearsal's configuration
(tests/perfbench/tiny-joyai); `experts --tiny` runs the four tiny
rehearsal cells, the grouped walks in Pallas's interpreter.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "joyai-ep1-2k-512-backlog"
TINY = (os.path.join(ROOT, "tests", "perfbench", "tiny-joyai",
                     "manifest.json"), "tiny-joyai-backlog")
# the text cells `experts` reads by default, and their CPU rehearsals
TEXT_CELLS = ("joyai-ep1-2k-512-backlog", "trinity-ep8-8k-backlog",
              "dsv32-ep16-16k-backlog", "dots3-ep8-8k-1k-backlog")
TINY_CELLS = {name: (os.path.join(ROOT, "tests", "perfbench", tiny,
                                  "manifest.json"), f"{tiny}-backlog")
              for name, tiny in zip(TEXT_CELLS, ("tiny-joyai",
                                                 "tiny-trinity",
                                                 "tiny-dsv32",
                                                 "tiny-dots3"))}
HBM_GBPS = 819.0          # v5e (perfbench/peaks.py)


class Bench:
    """The cell's pipeline, one bucket of its traffic, weights by draw."""

    def __init__(self, tiny: bool, seed: int):
        import jax

        from perfbench import manifest, traffic
        from perfbench.reference.trinity import decode_bucket, prompt_bucket

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

    def weights(self, draw: int | None = None):
        import jax

        from perfbench import weights

        draw = self.config["weights"]["seed"] if draw is None else draw
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


def _timed(fn, *args, runs: int = 2):
    """(result, [seconds of each run after the first, which compiles])."""
    import jax

    out = jax.block_until_ready(fn(*args))
    secs = []
    for _ in range(runs):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        secs.append(time.perf_counter() - t0)
    return out, secs


def scan(b: Bench, draws: list[int], emit) -> None:
    import numpy as np

    for draw in draws:
        params = b.weights(draw)
        b.generate(params)                      # compiles once a process
        (tokens, routed, spec), secs = b.generate(params)
        steps, drafts, accepted, idle = (int(x) for x in np.asarray(spec))
        emit({"what": "scan", "draw": draw, "bucket_s": secs,
              "steps": steps, "drafts": drafts, "accepted": accepted,
              "idle_row_steps": idle,
              "accept_pct": 100.0 * accepted / max(drafts, 1),
              "distinct_bytes": [int(len(set(r.tolist())))
                                 for r in np.asarray(tokens)[:4]]})
        del params, tokens, routed, spec


def _one_token_loop(b: Bench, with_module: bool):
    """(params, prefill's carry, served[B, T]) → (the main model's greedy
    ids [B, T-1] for tokens 1 .. T-1, the module's drafts [B, T-1] of the
    same tokens): one position a step through the caches, teacher-forced
    on `served`; the module's half only where asked for."""
    import jax
    import jax.numpy as jnp

    from arbius_tpu.models.joyai_flash import model as joyai

    cfg, p = b.pipe.config, b.p
    greedy = b.pipe._sampler_fn("greedy")

    def loop(params, carry, served):
        caches, mtp_cache, h_last, _ = carry
        bsz = served.shape[0]

        def guess(tok, h, cache, at):
            lg, cache, _ = joyai.draft(params, tok[:, None], h[:, None],
                                       cache, at, cfg)
            return greedy(lg[:, 0], None, 0), cache

        first = jnp.zeros((bsz,), jnp.int32)
        if with_module:
            first, mtp_cache = guess(served[:, 0], h_last, mtp_cache,
                                     jnp.full((bsz,), p - 1, jnp.int32))

        def body(state, i):
            caches, mtp_cache = state
            at = jnp.full((bsz,), p + i - 1, jnp.int32)
            tok = jax.lax.dynamic_index_in_dim(served, i - 1, 1, False)
            lg, h, caches, _ = joyai.step(params, tok[:, None], caches, at,
                                          cfg)
            mine = greedy(lg[:, 0], None, 0)
            nxt = jnp.zeros((bsz,), jnp.int32)
            if with_module:
                tok = jax.lax.dynamic_index_in_dim(served, i, 1, False)
                nxt, mtp_cache = guess(tok, h[:, 0], mtp_cache, at)
            return (caches, mtp_cache), (mine, nxt)

        _, (mine, nxt) = jax.lax.scan(
            body, (caches, mtp_cache), jnp.arange(1, served.shape[1]))
        drafts = jnp.concatenate([first[None], nxt[:-1]])
        return jnp.moveaxis(mine, 0, 1), jnp.moveaxis(drafts, 0, 1)

    return jax.jit(loop)


def _inputs(b: Bench):
    import jax.numpy as jnp
    import numpy as np

    ids = jnp.asarray(b.pipe._tokenizer(b.p).encode_batch(
        [x["prompt"] for x in b.tasks]))
    seeds = np.asarray(b.seeds, dtype=np.uint64)
    return (ids, jnp.asarray(seeds & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray(seeds >> np.uint64(32), jnp.uint32))


def steps(b: Bench, emit) -> None:
    import jax
    import numpy as np

    params = b.weights()
    ids, lo, hi = _inputs(b)
    pre = b.pipe.prefill_program(b.batch, b.p, b.t)
    (logits0, carry), pre_s = _timed(pre, params, ids)
    t0 = b.pipe._sampler_fn("greedy")(logits0, None, 0)
    spec = b.pipe.decode_program(b.batch, b.p, b.t, "greedy")
    (tokens, _, counts), spec_s = _timed(spec, params, carry, t0, lo, hi)
    n_steps, drafts, accepted, idle = (int(x) for x in np.asarray(counts))
    one = _one_token_loop(b, with_module=False)
    (mine, _), one_s = _timed(one, params, carry, tokens)
    served = np.asarray(tokens)
    differ = int((np.asarray(mine) != served[:, 1:]).sum())
    emit({"what": "steps", "draw": b.config["weights"]["seed"],
          "batch": b.batch, "prefill_s": pre_s, "spec_loop_s": spec_s,
          "one_token_loop_s": one_s, "spec_steps": n_steps,
          "drafts": drafts, "accepted": accepted, "idle_row_steps": idle,
          "spec_step_ms": 1e3 * min(spec_s) / max(n_steps, 1),
          "one_token_step_ms": 1e3 * min(one_s) / max(b.t - 1, 1),
          "served_ids_the_one_token_loop_reads_otherwise": differ,
          "of": int(served[:, 1:].size),
          "memory_peak_bytes": int((jax.devices()[0].memory_stats() or {})
                                   .get("peak_bytes_in_use", 0))})


def module(b: Bench, n_tasks: int, emit) -> None:
    import numpy as np

    params = b.weights()
    ids, _, _ = _inputs(b)
    (tokens, _, counts), _ = b.generate(params)
    served = np.asarray(tokens)
    _, carry = b.pipe.prefill_program(b.batch, b.p, b.t)(params, ids)
    mine, drafts = _one_token_loop(b, with_module=True)(params, carry,
                                                        tokens)
    mine, drafts = np.asarray(mine), np.asarray(drafts)
    del carry
    ref = b.family.reference
    for i in range(min(n_tasks, b.batch)):
        main, guess = ref.both_logits(params, b.arch, b.tasks[i], served[i])
        emit({"what": "module", "task": i,
              "draw": b.config["weights"]["seed"],
              "module_drafts": b.family.gaps(guess, drafts[i]),
              "main_served": b.family.gaps(main, served[i]),
              "main_one_token": b.family.gaps(main[1:], mine[i]),
              "drafts_equal_reference_first": int(
                  (guess.argmax(axis=-1) == drafts[i]).sum()),
              "drafts_right": int((drafts[i] == served[i, 1:]).sum()),
              "of": int(drafts.shape[1]),
              "loop_counts": [int(x) for x in np.asarray(counts)]})


def _walk(path: str):
    """A context in which `routed_experts` traces the walk `path`:
    "loop", "grouped" (gmm), or "ragged" (`lax.ragged_dot` in gmm's
    place, its products rounded as `_dot` rounds them)."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from arbius_tpu.ops import grouped

    def ragged(x, w, sizes, tile):
        return jax.lax.ragged_dot(
            x, w, sizes, preferred_element_type=jnp.float32).astype(x.dtype)

    @contextlib.contextmanager
    def walk():
        serves, dot = grouped.kernel_serves, grouped.grouped_dot
        grouped.kernel_serves = lambda *_: path != "loop"
        if path == "ragged":
            grouped.grouped_dot = ragged
        try:
            yield
        finally:
            grouped.kernel_serves, grouped.grouped_dot = serves, dot

    return walk()


def experts(cells: list[str], tiny: bool, seed: int, emit) -> None:
    """One expert layer of each cell's configuration, random weights
    (kernels N(0, 1/fan_in), the router's too, so the load is near
    uniform), `reps` inputs of a shape in one jitted `fori_loop` (the
    dispatch paid once): a call's seconds = the loop's ÷ reps, the best
    of five runs after the compile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arbius_tpu.models.deepseek_v32 import model as dsv32
    from arbius_tpu.models.trinity import model as trinity
    from perfbench import manifest

    reps = 8
    for name in cells:
        path, cell_name = TINY_CELLS[name] if tiny \
            else (manifest.DEFAULT_MANIFEST, name)
        cell = manifest.Cell(path, cell_name)
        entry = cell.config["models"][0]
        pipe, _ = cell.family(entry["family"]).build(entry["arch"], "bf16")
        cfg = pipe.config
        route = trinity.route if isinstance(cfg, trinity.TrinityConfig) \
            else dsv32.route
        batch = cell.config["node"]["canonical_batch"]
        pb, db = pipe.prompt_buckets[-1], pipe.decode_buckets[-1]
        d, f, h = cfg.hidden, cfg.expert_ff, cfg.n_held
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 5)
        bf = jnp.bfloat16
        layer = {
            "router": {"kernel": (jax.random.normal(
                ks[0], (d, cfg.num_experts)) / d ** 0.5).astype(bf)},
            "expert_bias": jnp.zeros((cfg.num_experts,), jnp.float32),
            "experts": {
                n: {"kernel": (jax.random.normal(k, shape, bf)
                               / shape[1] ** 0.5).astype(bf)}
                for n, k, shape in (("gate", ks[1], (h, d, f)),
                                    ("up", ks[2], (h, d, f)),
                                    ("down", ks[3], (h, f, d)))}}
        kernel_bytes = 3 * d * f * 2
        for rows, calls in pipe.expert_calls(batch, pb, db):
            tile = trinity.expert_tile(rows, cfg)
            xs = jax.random.normal(jax.random.fold_in(ks[4], rows),
                                   (reps, rows, d), bf)
            chosen = np.asarray(jax.jit(jax.vmap(
                lambda x: route(x, layer, cfg)[0]))(xs))
            lo, hi = cfg.experts_held
            touched = [len({int(e) for e in c.ravel() if lo <= e < hi})
                       for c in chosen]
            walks = ["loop", "grouped"] + (
                ["ragged"] if tile == 8 and entry["family"]
                == "joyai_llm_flash" else [])
            for walk in walks:
                def call_all(xs, layer):
                    def body(i, acc):
                        x = jax.lax.dynamic_index_in_dim(xs, i, 0, False)
                        c, w = route(x, layer, cfg)
                        y, n = trinity.routed_experts(
                            x, c, w, layer["experts"], cfg)
                        return acc[0] + y.astype(jnp.float32), acc[1] + n
                    return jax.lax.fori_loop(
                        0, reps, body, (jnp.zeros((rows, d), jnp.float32),
                                        jnp.zeros((), jnp.int32)))

                with _walk(walk):
                    fn = jax.jit(call_all)
                    out, secs = _timed(fn, xs, layer, runs=5)
                s = min(secs) / reps
                gb = kernel_bytes * float(np.mean(touched)) / 1e9
                emit({"what": "experts", "cell": name, "rows": rows,
                      "tile": tile, "calls_a_bucket": calls, "walk": walk,
                      "us_a_call": 1e6 * s, "runs_us": [
                          1e6 * x / reps for x in secs],
                      "touched_experts": float(np.mean(touched)),
                      "held": h, "touched_gb": gb,
                      "gb_per_s": gb / s, "of_hbm_pct":
                      100 * gb / s / HBM_GBPS,
                      "held_assignments": int(out[1]) / reps,
                      "checksum": float(jnp.abs(out[0]).sum())})
        del layer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("scan", "steps", "module", "experts"))
    ap.add_argument("--seed", type=int, default=2147536001)
    ap.add_argument("--draws", default="")
    ap.add_argument("--tasks", type=int, default=4)
    ap.add_argument("--cells", default=",".join(TEXT_CELLS))
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default="pr36_joyai_diag.jsonl")
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

    if args.mode == "experts":
        experts([c for c in args.cells.split(",") if c], args.tiny,
                args.seed, emit)
    elif args.mode == "scan":
        scan(b, [int(x) for x in args.draws.split(",") if x], emit)
    elif args.mode == "steps":
        steps(b, emit)
    else:
        module(b, args.tasks, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
