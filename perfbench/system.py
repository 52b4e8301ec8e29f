"""The system under test, assembled and driven the way a miner runs it.

`MiningConfig` (from the configuration file's `node` block, through the
program's own `load_config`) -> `ModelRegistry` of the program's runners
over weights the benchmark made from the configuration's `weights.seed`
(`--seed` where the file states none) -> `MinerNode(LocalChain(
Engine))` -> `boot()` -> `tick()`. Tasks enter by `Engine.submit_task`
and leave as commitments and revealed solutions on the engine. Copied in
pattern from `chip_smoke.py` (which later PRs may change); imports the
program's public constructors only.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

from perfbench import manifest, traffic, weights

MINER = "0x" + "aa" * 20
USER = "0x" + "01" * 20


class Model:
    """One registered model: its family, files and weights."""

    def __init__(self, entry: dict, family=manifest.family):
        self.entry = entry
        self.template = entry["template"]
        self.family = family(entry["family"])
        if set(entry["limits"]) != set(self.family.COMPARED):
            raise manifest.ManifestError(
                f"model {self.template!r}: its limits are for "
                f"{sorted(entry['limits'])}, its family "
                f"{entry['family']!r} compares "
                f"{sorted(self.family.COMPARED)}")
        self.arch = entry["arch"]
        self.defaults = entry["defaults"]
        self.id_bytes: bytes = b""
        self.params = None          # the benchmark's tree (bf16), kept
        self.n_params = 0

    def hydrated(self, task_input: dict) -> dict:
        """The fields a task runs with: the model's defaults under its
        input (what the template and the runner fill in)."""
        return {**self.defaults, **task_input}


class System:
    def __init__(self, config: dict, seed: int, *, note=lambda m: None,
                 config_dir: str = ".", family=manifest.family):
        """`family` finds a family's file by its name (`Cell.family`)."""
        self.config = config
        self.config_dir = config_dir
        self.seed = seed
        self.note = note
        self.models = [Model(m, family) for m in config["models"]]
        self.workdir = tempfile.mkdtemp(prefix="perfbench-")
        self.cache_events = {"hits": 0, "misses": 0}
        self.timings: dict[str, float] = {}
        self.node = None
        self.submitted: list[dict] = []   # every task, in submit order

    # -- set-up ----------------------------------------------------------
    def build(self) -> None:
        import jax

        from arbius_tpu.chain import WAD, Engine, TokenLedger
        from arbius_tpu.node import LocalChain, MinerNode
        from arbius_tpu.node.config import load_config
        from arbius_tpu.node.solver import ModelRegistry, RegisteredModel
        from arbius_tpu.utils import enable_compile_cache

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_events["misses"] += 1

        self._listener = on_event
        jax.monitoring.register_event_listener(on_event)
        # No size cap on the cache the benchmark keeps: one cell's programs
        # are 0.3-0.9 GB, and under a cap (the chip machines export
        # JAX_COMPILATION_CACHE_MAX_SIZE of 160 MiB) jax evicts or refuses
        # them, so that every run compiles (my chip runs, PR 24).
        jax.config.update("jax_compilation_cache_max_size", -1)
        self.cache_dir = enable_compile_cache()

        tok = TokenLedger()
        eng = Engine(tok, start_time=0)
        tok.mint(Engine.ADDRESS, 600_000 * WAD)
        for a in (MINER, USER):
            tok.mint(a, 1000 * WAD)
            tok.approve(a, Engine.ADDRESS, 10**30)
        self.engine = eng

        from arbius_tpu.templates.engine import Template, load_template_bytes

        t0 = time.perf_counter()
        registry = ModelRegistry()
        node_models = []
        for i, m in enumerate(self.models):
            # the program's template, unless the configuration brings its
            # own (the tests' rehearsal: sizes a CPU can run)
            if m.entry.get("template_file"):
                with open(os.path.join(self.config_dir,
                                       m.entry["template_file"]), "rb") as f:
                    raw = f.read()
            else:
                raw = load_template_bytes(m.template)
            m.id_bytes = eng.register_model(USER, USER, 0, raw)
            mid = "0x" + m.id_bytes.hex()
            pipe, runner_cls = m.family.build(m.arch, "bf16")
            dtype = self.config["weights"]["dtype"]
            shapes = jax.eval_shape(
                lambda p=pipe: p.init_params(seed=0, dtype=dtype))
            m.n_params = weights.count(shapes)
            # one stated draw where the file gives `weights.seed` (a text
            # model's expert load is drawn with its weights: PERF.md
            # section 4), else a draw a run; `--seed` drives the traffic
            # and the sample compared either way
            draw = self.config["weights"].get("seed", self.seed)
            m.params = weights.make(shapes, int(draw) * 16 + i,
                                    self.config["weights"]["init"])
            jax.block_until_ready(m.params)
            registry.register(RegisteredModel(
                id=mid, template=Template.from_dict(json.loads(raw)),
                runner=runner_cls(pipe, m.params)))
            node_models.append({"id": mid, "template": m.template,
                                "weights_dtype": dtype})
            self.note(f"{m.template}: {m.n_params:,} parameters on the "
                      f"device ({dtype})")
        self.timings["param_init_s"] = time.perf_counter() - t0

        node_cfg = dict(self.config["node"])
        node_cfg.update(
            models=node_models,
            db_path=os.path.join(self.workdir, "node.sqlite"),
            store_dir=os.path.join(self.workdir, "store"))
        cfg = load_config(node_cfg)
        chain = LocalChain(eng, MINER)
        chain.validator_deposit(100 * WAD)
        self.registry = registry
        self.node = MinerNode(chain, cfg, registry)
        self.node.boot()
        self.canonical_batch = cfg.canonical_batch

    def model(self, template: str) -> Model:
        return next(m for m in self.models if m.template == template)

    # -- driving ---------------------------------------------------------
    def submit(self, gen: traffic.Traffic, model: str | None = None,
               tag: str = "t") -> dict:
        template, task_input = gen.task(model, tag)
        m = self.model(template)
        tid = self.engine.submit_task(USER, 0, USER, m.id_bytes, 0,
                                      traffic.encode(task_input))
        rec = {"taskid": tid, "model": template, "input": task_input,
               "submitted": time.perf_counter(), "solved": None, "tag": tag,
               "tick": None}
        self.submitted.append(rec)
        return rec

    def drain(self, pending: list[dict]) -> list[dict]:
        """Tick until every task of `pending` has its solution on the
        engine (or the node has nothing left to do). Returns those still
        pending."""
        while pending:
            done = self.node.tick()
            now = time.perf_counter()
            left = []
            for rec in pending:
                if rec["taskid"] in self.engine.solutions:
                    rec["solved"] = now
                else:
                    left.append(rec)
            if len(left) == len(pending) and not done:
                return left
            pending = left
        return pending

    def warm_up(self, gen: traffic.Traffic) -> None:
        """One canonical batch of every model the traffic holds, so every
        bucket program the window uses is compiled (or loaded from the
        persistent cache) before it opens."""
        t0 = time.perf_counter()
        for template in gen.models():
            recs = [self.submit(gen, template, tag="warm")
                    for _ in range(self.canonical_batch)]
            left = self.drain(recs)
            if left:
                raise RuntimeError(
                    f"warm-up: {len(left)} {template} task(s) never solved; "
                    f"failed jobs {self.failed_jobs()}")
            self.note(f"warm-up bucket of {template} done, cache "
                      f"{self.cache_events}")
        self.timings["bucket_warm_s"] = time.perf_counter() - t0

    def window(self, gen: traffic.Traffic, seconds: float,
               on_first_tick=None) -> dict:
        """The measured window: the backlog stands at `t0`; the window
        closes at the first moment at or after `seconds`, and after the
        traffic's `min_ticks` ticks, at which no dispatched bucket is in
        flight (a tick has returned). A traced window (`on_first_tick`)
        is not held open for `min_ticks`: its readers see the first tick
        alone. `tick_s`: the seconds from each tick's backlog standing to
        its last solution landed."""
        least = gen.min_ticks if on_first_tick is None else 1
        pending = [self.submit(gen) for _ in range(gen.outstanding)]
        first = len(self.submitted) - len(pending)
        t0 = time.perf_counter()
        tick_s = []
        while True:
            for rec in pending:
                rec["tick"] = len(tick_s)
            began = time.perf_counter()
            pending = self.drain(pending)
            t1 = time.perf_counter()
            tick_s.append(t1 - began)
            if len(tick_s) == 1 and on_first_tick is not None:
                on_first_tick()
            if pending or (t1 - t0 >= seconds and len(tick_s) >= least):
                break
            pending = [self.submit(gen) for _ in range(gen.outstanding)]
        tasks = self.submitted[first:]
        return {"t0": t0, "t1": t1, "ticks": len(tick_s), "tick_s": tick_s,
                "tasks": tasks, "unsolved": pending}

    def failed_jobs(self) -> list:
        return [m for m, _ in self.node.db.failed_jobs()]

    def solution_files(self, rec: dict) -> dict | None:
        """The bytes the node pinned for a task's revealed CID."""
        sol = self.engine.solutions.get(rec["taskid"])
        if sol is None:
            return None
        m = self.model(rec["model"])
        data = self.node.store.resolve(sol.cid, m.family.OUT_NAME)
        return None if data is None else {m.family.OUT_NAME: data}

    def close(self) -> None:
        import jax

        if self.node is not None:
            self.node.close()
            self.node = None
        jax.monitoring.unregister_event_listener(self._listener)
        shutil.rmtree(self.workdir, ignore_errors=True)

    def free_program(self) -> None:
        """Drop the program's state (runners, compiled buckets) so the
        reference has the chip; the benchmark's own weights stay."""
        import gc

        import jax

        self.registry = None
        if self.node is not None:
            self.node.close()
            self.node = None
        gc.collect()
        jax.clear_caches()
