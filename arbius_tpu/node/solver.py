"""Model registry + the deterministic solve path (inference → bytes → CID).

The reference's `EnabledModels` maps a model id to a template, filters, and
a `getfiles` that HTTP-POSTs a cog container (`miner/src/index.ts:781-877`).
Here `getfiles` IS the framework: an in-process runner produces the output
arrays, the codec layer fixes their bytes, and the L0 DAG fixes the CID —
no sidecars (`models.ts:34-54` default__getcid equivalent).

A `Runner` is `(hydrated_input: dict, seed: int) -> dict[filename, bytes]`.
`SD15Runner` adapts the SD-1.5 pipeline; tests plug in fakes. Runners must
be deterministic in (input, seed) — `solve_cid` is what gets keccak'd into
the on-chain commitment.

This module IS the solve→encode→CID path, so the determinism rules below
are enforced: findings here can never be pragma'd or baselined away
(docs/static-analysis.md), and tests/test_analysis.py proves an injected
wall-clock call fails the tier-1 gate. The JIT2xx rules stay
pragma-able here on purpose — jit-target detection is heuristic, and an
un-waivable false positive would block correct code.
"""
# detlint: enforce[DET101,DET102,DET103,DET104,DET105]
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Protocol

import numpy as np

from arbius_tpu.codecs import encode_png
from arbius_tpu.l0.cid import cid_hex, cid_of_solution_files
from arbius_tpu.obs import span, under
from arbius_tpu.templates.engine import Template, load_template

Runner = Callable[[dict, int], dict]


@dataclass
class RegisteredModel:
    id: str                       # 0x hash
    template: Template
    runner: Runner
    min_fee: int = 0
    allowed_owners: tuple[str, ...] = ()
    golden: tuple[dict, int, str] | None = None  # (input, seed, cid_hex)


class ModelRegistry:
    def __init__(self):
        self._models: dict[str, RegisteredModel] = {}

    def register(self, model: RegisteredModel) -> None:
        # detlint: allow[CONC401] boot-time only: build_registry fills
        # the registry before node.boot() returns, which happens-before
        # ControlRPC.start() — the map is frozen while request threads
        # read it (mining never registers models mid-life)
        self._models[model.id.lower()] = model

    def get(self, model_id: str) -> RegisteredModel | None:
        return self._models.get(model_id.lower())

    def ids(self) -> list[str]:
        return list(self._models)


def bucket_key(model_id: str, hydrated: dict, mode: str = "bf16") -> tuple:
    """The shape-bucket identity of one task: every field that is part
    of the compiled XLA program (w/h/steps/scheduler, and num_frames
    for video templates — image templates simply carry None there),
    plus the PRECISION MODE (docs/quantization.md) — a quantized bucket
    and its bf16 twin are different XLA programs, so they are different
    buckets exactly like different shapes. Tasks sharing a key run as
    ONE batched dispatch; the key is also the cost model's bucket
    feature and the packer's unit of reordering (node/sched.py,
    docs/scheduler.md), so it lives here — next to the chunking it must
    agree with — not in the node.

    Text templates (docs/text-serving.md) fill the scheduler slot with
    their `sampler` and EXTEND the key with the sequence-bucket fields
    the runner's `prepare_hydrated` injected (`_prompt_bucket`,
    `_decode_bucket`) — a 9-tuple. Tasks without those fields keep
    producing the historic 7-tuple byte for byte, so persisted cost
    rows and legacy keys keep meaning what they meant."""
    sched = hydrated.get("scheduler")
    if sched is None:
        sched = hydrated.get("sampler")
    key = (model_id, hydrated.get("width"), hydrated.get("height"),
           hydrated.get("num_inference_steps"), sched,
           hydrated.get("num_frames"), mode)
    pb = hydrated.get("_prompt_bucket")
    db = hydrated.get("_decode_bucket")
    if pb is None and db is None:
        return key
    return key + (pb, db)


def bucket_mode(key: tuple) -> str:
    """The precision mode a bucket key carries (pre-quant 6-tuples read
    as bf16, so persisted/legacy keys keep meaning what they meant)."""
    return key[6] if len(key) > 6 else "bf16"


def _check_declared(model: RegisteredModel, files: dict) -> dict:
    declared = {o.filename for o in model.template.outputs}
    if set(files) != declared:
        raise ValueError(
            f"runner produced {sorted(files)} but template declares "
            f"{sorted(declared)}")
    return files


def solve_files(model: RegisteredModel, hydrated: dict, seed: int) -> dict:
    """Run inference, return {filename: bytes} per the template outputs."""
    return _check_declared(model, model.runner(hydrated, seed))


def chunk_items(items: list[tuple[dict, int]],
                canonical_batch: int) -> list[tuple[list, int]]:
    """Split a bucket's items into canonical_batch-sized chunks, padding
    the last chunk by repeating its final real item — every dispatch runs
    the exact fleet-wide batch size (one bucket ⇒ one XLA program ⇒ one
    determinism class). Returns [(padded_items, n_real)]. Shared by the
    serial path below and the staged executor (node/pipeline.py) so the
    two schedules can never chunk differently."""
    chunks = []
    for start in range(0, len(items), canonical_batch):
        chunk = items[start:start + canonical_batch]
        real = len(chunk)
        chunks.append((chunk + [chunk[-1]] * (canonical_batch - real), real))
    return chunks


# -- one chunk's span chain (docs/observability.md) -------------------------
#
# Both schedules journal the same chain per dispatched chunk:
# solve.dispatch, then solve.device_wait, solve.encode (opened by the
# runner's finalize) and solve.cid as its children, on whichever thread
# finalizes. This file reads no clock (DET101 is enforced here): the
# stamps the schedules reckon chip idle from are the spans' own.

def program_attrs(runner, items: list) -> dict:
    """`solve.dispatch`'s `program`: the executable-cache tag of the
    bucket a chunk of `items` runs, from the runner's `cache_tag` (the
    key `Obs.blocks` reads the program's block map under); nothing for
    a runner without one, or whose derivation fails (the node's
    `_bucket_exec_tag` rule: a tag never fails a solve)."""
    tag = getattr(runner, "cache_tag", None)
    if tag is None:
        return {}
    try:
        return {"program": tag(items[0][0], len(items))}
    except Exception:  # noqa: BLE001 — a tag is advisory metadata
        return {}


def device_wait(value, *, chunk, parent: int | None) -> float | None:
    """Block until a dispatched chunk's device result is ready — the
    ONE place either schedule waits on the chip, so `solve.encode`
    times transfer + codec only. Returns the moment it was ready
    (`time.perf_counter`, the span's end), None with no ambient obs."""
    import jax

    with span("solve.device_wait", parent=parent, chunk=chunk) as sp:
        jax.block_until_ready(value)
    return sp.t1 if sp is not None else None


def encode_chunk(model: RegisteredModel, payload: tuple, real: int, *,
                 chunk, parent: int | None) -> list[tuple[str, dict]]:
    """Ready device result → [(cid_hex, files)] per real item. Pure in
    (model, payload) — safe on any worker thread, and the same
    finalize→CID sequence on both schedules."""
    kind, value = payload
    if kind == "dev":
        # the runner opens solve.encode itself, from the ambient stack
        with under(parent):
            files_list = model.runner.finalize(value, real)
    else:
        files_list = value[:real]
    with span("solve.cid", parent=parent, n=real, chunk=chunk):
        return [(cid_hex(cid_of_solution_files(_check_declared(model, f))),
                 f) for f in files_list]


def _solve_chunked(model: RegisteredModel, chunks: list, *, gen: int,
                   taskids, busy) -> list[tuple[str, dict]]:
    """One-deep pipeline: queue chunk i+1's XLA dispatch BEFORE
    transferring/encoding chunk i, so the host PNG encode (~64 ms/
    image, the dominant host cost) overlaps the chip's compute (JAX
    async dispatch). Output order and bytes are those of
    finalize(dispatch(chunk)) chunk by chunk — only the schedule
    changes. `busy` collects (dispatch start, ready, chunk index)."""
    runner = model.runner
    b = len(chunks[0][0])
    out: list[tuple[str, dict]] = []

    def finish(idx, dev, real, dsp):
        parent = dsp.span_id if dsp is not None else None
        ready = device_wait(dev, chunk=[gen, idx], parent=parent)
        if busy is not None and ready is not None:
            busy.append((dsp.t0, ready, idx))
        out.extend(encode_chunk(model, ("dev", dev), real,
                                chunk=[gen, idx], parent=parent))

    pending = None
    for idx, (chunk, real) in enumerate(chunks):
        with span("solve.dispatch", n=real, batch=len(chunk),
                  chunk=[gen, idx], model=model.id,
                  taskids=(taskids or [])[idx * b:idx * b + real],
                  **program_attrs(runner, chunk)) as dsp:
            dev = runner.dispatch(chunk)
        if pending is not None:
            finish(*pending)
        pending = (idx, dev, real, dsp)
    finish(*pending)
    return out


EVIL_CID = ("0x1220000000000000000000000000000000000000000000000000000000000"
            "0000666")


def solve_cid(model: RegisteredModel, hydrated: dict, seed: int,
              *, evilmode: bool = False) -> tuple[str, dict]:
    """The commitment-bound CID for a task: dir-wrapped root of the output
    files (ipfs.ts:28-76 path). evilmode emits a deliberately wrong CID
    for contestation drills (models.ts:40-42)."""
    if evilmode:
        return EVIL_CID, {}
    files = solve_files(model, hydrated, seed)
    with span("solve.cid", n=1):
        return cid_hex(cid_of_solution_files(files)), files


def solve_cid_batch(model: RegisteredModel, items: list[tuple[dict, int]],
                    *, evilmode: bool = False, canonical_batch: int = 1,
                    taskids: list[str] | None = None,
                    busy: list | None = None) -> list[tuple[str, dict]]:
    """Batched solve_cid over one shape bucket, ALWAYS at the canonical
    batch size.

    Batch size is part of the compiled XLA program, and different programs
    are different determinism classes — if miners ran whatever batch their
    queue happened to hold, two honest nodes could emit different bytes
    for the same task and contest each other. So every dispatch is padded
    to exactly `canonical_batch` samples (repeating the last real item)
    and one bucket ⇒ one program ⇒ one determinism class. Runners without
    `run_batch` are the canonical_batch=1 case by construction.

    Runners with the dispatch/finalize pair are chunk-pipelined
    (`_solve_chunked`; `run_batch` IS finalize(dispatch(...)) in every
    one of them, so a single chunk takes that branch too) and journal
    the per-chunk span chain; `taskids` (item order) names the tasks on
    it, and `busy`, when given, collects each chunk's (dispatch start,
    ready, index) stamps for the caller's idle accounting."""
    if evilmode:
        return [(EVIL_CID, {})] * len(items)
    runner = model.runner
    run_batch = getattr(runner, "run_batch", None)
    with span("solve.infer", n=len(items), batch=canonical_batch) as infer:
        if run_batch is None or canonical_batch <= 1:
            files_list = [solve_files(model, h, s) for h, s in items]
        elif getattr(runner, "dispatch", None) is not None \
                and getattr(runner, "finalize", None) is not None:
            # chunk ids are (generation, index): the staged executor's
            # generation is its run counter, here it is this span's id
            return _solve_chunked(
                model, chunk_items(items, canonical_batch),
                gen=infer.span_id if infer is not None else 0,
                taskids=taskids, busy=busy)
        else:
            files_list = []
            for chunk, real in chunk_items(items, canonical_batch):
                files_list.extend(_check_declared(model, f)
                                  for f in run_batch(chunk)[:real])
    with span("solve.cid", n=len(files_list)):
        return [(cid_hex(cid_of_solution_files(files)), files)
                for files in files_list]


class Kandinsky2Runner:
    """kandinsky2-template runner: prior+decoder+MOVQ → deterministic PNG.

    Template variables (templates/kandinsky2.json): prompt,
    width/height ∈ {768, 1024}; output out-1.png. The reference's only
    enabled + boot-self-test model (miner/src/index.ts:844-877).
    """

    def __init__(self, pipeline, params, out_name: str = "out-1.png"):
        self.pipeline = pipeline
        self.params = params
        self.out_name = out_name

    def __call__(self, hydrated: dict, seed: int) -> dict:
        return self.run_batch([(hydrated, seed)])[0]

    def run_batch(self, items: list[tuple[dict, int]]) -> list[dict]:
        return self.finalize(self.dispatch(items), len(items))

    def dispatch(self, items: list[tuple[dict, int]]):
        """Async-dispatch the bucket (chunk pipelining — see SD15Runner:
        768² PNG encode is ~145 ms/image of host time to overlap)."""
        first = items[0][0]
        return self.pipeline.generate(
            self.params,
            prompts=[h["prompt"] for h, _ in items],
            negative_prompts=None,
            seeds=[s for _, s in items],
            width=int(first.get("width", 768)),
            height=int(first.get("height", 768)),
            num_inference_steps=int(first.get("num_inference_steps", 50)),
            guidance_scale=[float(h.get("guidance_scale", 4.0))
                            for h, _ in items],
            as_device=True,
        )

    def finalize(self, images, n_real: int) -> list[dict]:
        from arbius_tpu.parallel.meshsolve import gather_canonical

        with span("solve.encode", n=n_real, codec="png"):
            # fully-replicated gather in canonical order: sample i is
            # task i on every mesh layout (meshsolve.gather_canonical)
            images = gather_canonical(images)
            return [{self.out_name: encode_png(images[i])}
                    for i in range(n_real)]

    def cache_tag(self, hydrated: dict, batch: int) -> str:
        """The executable-cache tag a dispatch of this task's bucket
        would use — defaults mirror `dispatch` exactly, and the string
        comes from the pipeline's one `bucket_tag` definition, so the
        scheduler's cross-life disk-warm lookup (docs/compile-cache.md)
        can never drift from what the dispatch actually caches."""
        return self.pipeline.bucket_tag(
            batch, int(hydrated.get("height", 768)),
            int(hydrated.get("width", 768)),
            int(hydrated.get("num_inference_steps", 50)), "DDIM")


class Text2VideoRunner:
    """zeroscope/damo-template runner: UNet3D → deterministic H.264 MP4.

    Template variables (templates/zeroscopev2xl.json / damo.json): prompt,
    negative_prompt (zeroscope), num_frames, num_inference_steps,
    width/height enums, guidance_scale, fps; output out-1.mp4.
    """

    def __init__(self, pipeline, params, out_name: str = "out-1.mp4",
                 defaults: dict | None = None):
        self.pipeline = pipeline
        self.params = params
        self.out_name = out_name
        self.defaults = {"num_frames": 16, "width": 256, "height": 256,
                         "num_inference_steps": 20, "guidance_scale": 9.0,
                         "fps": 8, **(defaults or {})}

    def __call__(self, hydrated: dict, seed: int) -> dict:
        return self.finalize(self.dispatch([(hydrated, seed)]), 1)[0]

    def run_batch(self, items: list[tuple[dict, int]]) -> list[dict]:
        """One dp×sp-batched dispatch for a whole shape bucket: the
        node's bucket key includes num_frames (plus w/h/steps/scheduler),
        so every item shares the compiled program; prompts, negatives,
        seeds, guidance — and the container-only fps — vary per item."""
        return self.finalize(self.dispatch(items), len(items))

    def _get(self, hydrated: dict, key: str):
        v = hydrated.get(key)
        return v if v is not None else self.defaults[key]

    def dispatch(self, items: list[tuple[dict, int]]):
        """Queue the bucket's XLA dispatch and return WITHOUT waiting
        (see SD15Runner.dispatch): the staged pipeline muxes chunk i's
        MP4s while the chip crunches chunk i+1. fps is mp4-container
        metadata, not part of the compiled program, so the per-item
        values ride along to finalize instead of the bucket key."""
        first = items[0][0]
        g = lambda k: self._get(first, k)
        frames = self.pipeline.generate(
            self.params,
            prompts=[h["prompt"] for h, _ in items],
            negative_prompts=[h.get("negative_prompt", "") for h, _ in items],
            seeds=[s for _, s in items],
            num_frames=int(g("num_frames")),
            width=int(g("width")), height=int(g("height")),
            num_inference_steps=int(g("num_inference_steps")),
            guidance_scale=[float(self._get(h, "guidance_scale"))
                            for h, _ in items],
            as_device=True,
        )
        return frames, [int(self._get(h, "fps")) for h, _ in items]

    def finalize(self, dev, n_real: int) -> list[dict]:
        # H.264 (all-intra I_PCM, codecs/h264.py) — the artifact class
        # the reference's cog/ffmpeg outputs belong to, so the dapp's
        # <video> tag (website/src/pages/task/[taskid].tsx:214-224
        # analogue) can actually play it; MJPEG-MP4 was deterministic
        # but not browser-decodable (round-4 verdict, missing #1)
        from arbius_tpu.codecs import encode_mp4_h264
        from arbius_tpu.parallel.meshsolve import gather_canonical

        frames, fps = dev
        with span("solve.encode", n=n_real, codec="h264"):
            frames = gather_canonical(frames)
            return [{self.out_name: encode_mp4_h264(frames[i], fps=fps[i])}
                    for i in range(n_real)]

    def cache_tag(self, hydrated: dict, batch: int) -> str:
        """Scheduler's cross-life disk-warm join key — defaults mirror
        `dispatch` exactly (docs/compile-cache.md, see
        SD15Runner.cache_tag)."""
        g = lambda k: self._get(hydrated, k)  # noqa: E731
        return self.pipeline.bucket_tag(
            batch, int(g("num_frames")), int(g("height")),
            int(g("width")), int(g("num_inference_steps")), "DDIM")


class RVMRunner:
    """robust_video_matting-template runner: ConvGRU matting stream.

    The template's `input_video` is a file reference; `resolve_file`
    (cid/url → bytes) is injected — the reference fetched from IPFS, a
    local deployment may read a content store. Output composition follows
    the output_type enum. Seed-independent, like the reference model.
    """

    def __init__(self, pipeline, params, resolve_file,
                 out_name: str = "out-1.mp4", fps: int = 8):
        self.pipeline = pipeline
        self.params = params
        self.resolve_file = resolve_file
        self.out_name = out_name
        self.fps = fps

    def __call__(self, hydrated: dict, seed: int) -> dict:
        # output: H.264 I_PCM (browser-playable artifact class — see
        # Text2VideoRunner); input: MJPEG or avc1, auto-detected
        from arbius_tpu.codecs import encode_mp4_h264
        from arbius_tpu.codecs.mp4_demux import decode_video_mp4

        video = decode_video_mp4(self.resolve_file(hydrated["input_video"]))
        # the template's output_type enum includes "" as its default
        # choice (templates/robust_video_matting.json) — the published
        # model treats empty as green-screen
        out = self.pipeline.matte(
            self.params, video,
            output_type=hydrated.get("output_type") or "green-screen")
        with span("solve.encode", n=1, codec="h264"):
            return {self.out_name: encode_mp4_h264(out, fps=self.fps)}


class SD15Runner:
    """anythingv3-class runner: SD-1.5 pipeline → deterministic PNG.

    Template variables (templates/anythingv3.json): prompt,
    negative_prompt, width, height, num_inference_steps, guidance_scale,
    scheduler (enum), seed (injected from taskid).
    """

    def __init__(self, pipeline, params, out_name: str = "out-1.png"):
        self.pipeline = pipeline
        self.params = params
        self.out_name = out_name

    def __call__(self, hydrated: dict, seed: int) -> dict:
        return self.run_batch([(hydrated, seed)])[0]

    def run_batch(self, items: list[tuple[dict, int]]) -> list[dict]:
        """One dp-batched XLA dispatch for a whole shape bucket: every item
        shares (width, height, steps, scheduler) — the node's bucket key —
        while prompts, guidance, and seeds vary per sample."""
        return self.finalize(self.dispatch(items), len(items))

    def dispatch(self, items: list[tuple[dict, int]]):
        """Queue the bucket's XLA dispatch and return WITHOUT waiting
        (JAX async dispatch): the chunk-pipelining in solve_cid_batch
        encodes chunk i's PNGs on the host while the chip crunches chunk
        i+1 — the host codec work disappears from the critical path."""
        first = items[0][0]
        return self.pipeline.generate(
            self.params,
            prompts=[h["prompt"] for h, _ in items],
            negative_prompts=[h.get("negative_prompt", "") for h, _ in items],
            seeds=[s for _, s in items],
            width=int(first.get("width", 512)),
            height=int(first.get("height", 512)),
            num_inference_steps=int(first.get("num_inference_steps", 20)),
            guidance_scale=[float(h.get("guidance_scale", 7.5))
                            for h, _ in items],
            scheduler=first.get("scheduler", "DDIM"),
            as_device=True,
        )

    def finalize(self, images, n_real: int) -> list[dict]:
        """Device result → per-item encoded files (blocks on the
        transfer, then host-side codec). Bytes identical to the
        unpipelined path: encode order and inputs are unchanged. On a
        mesh the result arrives dp-sharded; gather_canonical is the
        fully-replicated gather in canonical sample order."""
        from arbius_tpu.parallel.meshsolve import gather_canonical

        with span("solve.encode", n=n_real, codec="png"):
            images = gather_canonical(images)
            return [{self.out_name: encode_png(images[i])}
                    for i in range(n_real)]

    def cache_tag(self, hydrated: dict, batch: int) -> str:
        """Scheduler's cross-life disk-warm join key — defaults mirror
        `dispatch` exactly (docs/compile-cache.md, see
        Kandinsky2Runner.cache_tag)."""
        return self.pipeline.bucket_tag(
            batch, int(hydrated.get("height", 512)),
            int(hydrated.get("width", 512)),
            int(hydrated.get("num_inference_steps", 20)),
            hydrated.get("scheduler", "DDIM"))


def _counter(name: str, help_text: str, labelnames: tuple = ()):
    """The ambient obs' counter of that name, or None with no obs active
    (library code stays node-free, like `span`)."""
    from arbius_tpu.obs import current_obs

    obs = current_obs()
    if obs is None:
        return None
    return obs.registry.counter(name, help_text, labelnames=labelnames)


def count_decode_stall(n: int = 1) -> None:
    """Bump `arbius_decode_stalls_total` — a text solve whose decode
    produced ZERO output bytes (immediate eos / nothing representable).
    Observation only: the empty artifact is still the committed bytes,
    never retried or mutated. One registration site shared by the
    production finalize path and the simnet fault plane so the metric
    carries one help string (docs/observability.md; the healthwatch
    `decode_stall` rule watches this counter)."""
    c = _counter("arbius_decode_stalls_total",
                 "text solves whose decode produced zero output bytes")
    if c is not None:
        c.inc(n)


def count_text_tokens(prefill: int, decode: int) -> None:
    """Bump `arbius_text_tokens_total{phase}` by the positions a text
    bucket's program processes: batch × prompt edge of prefill, batch ×
    decode edge of decode steps — counted at dispatch from the bucket's
    shape (padding slots and padded positions are program work too)."""
    c = _counter("arbius_text_tokens_total",
                 "positions text bucket programs processed, by phase",
                 labelnames=("phase",))
    if c is not None:
        c.inc(prefill, phase="prefill")
        c.inc(decode, phase="decode")


def count_moe_assignments(made: int, held: int) -> None:
    """Bump `arbius_moe_assignments_total{held}`: the (token, choice)
    assignments a bucket program's routers made, split by whether the
    chosen expert is held on this chip — the program's own int32 sums
    (docs/text-serving.md)."""
    c = _counter("arbius_moe_assignments_total",
                 "router (token, choice) assignments, by whether the "
                 "chosen expert is held here", labelnames=("held",))
    if c is not None:
        c.inc(held, held="yes")
        c.inc(made - held, held="no")


def count_latent_cache(held: int, per_head: int) -> None:
    """Bump `arbius_text_cache_bytes_total{form}`: the bytes a latent-
    attention family's bucket carries as cache (`form="latent"`) and
    what per-head K and V rows of every head would take for the same
    positions (`form="per_head"`) — counted at dispatch from the
    bucket's shape (docs/text-serving.md)."""
    c = _counter("arbius_text_cache_bytes_total",
                 "cache bytes of dispatched text buckets, as held and as "
                 "per-head K/V rows would be", labelnames=("form",))
    if c is not None:
        c.inc(held, form="latent")
        c.inc(per_head, form="per_head")


def count_window_cache(window: int, full: int) -> None:
    """Bump `arbius_text_cache_bytes_total{form}` for a family whose
    sliding-window layers keep a ring of latent rows: `form=
    "window_latent"` the bytes those rings hold, `form="latent"` what its
    full layers' latent and indexer caches hold — counted at dispatch
    from the bucket's shape (docs/text-serving.md)."""
    c = _counter("arbius_text_cache_bytes_total",
                 "cache bytes of dispatched text buckets, as held and as "
                 "per-head K/V rows would be", labelnames=("form",))
    if c is not None:
        c.inc(window, form="window_latent")
        c.inc(full, form="latent")


def count_ssm_state(state: int) -> None:
    """Bump `arbius_text_cache_bytes_total{form="ssm_state"}` for a family
    whose state-space layers carry a recurrent state beside K/V rows: the
    bytes of those states and conv windows a bucket holds — counted at
    dispatch from the bucket's shape (docs/text-serving.md)."""
    c = _counter("arbius_text_cache_bytes_total",
                 "cache bytes of dispatched text buckets, as held and as "
                 "per-head K/V rows would be", labelnames=("form",))
    if c is not None:
        c.inc(state, form="ssm_state")


def count_attn_pairs(kept: int, causal: int) -> None:
    """Bump `arbius_attn_pairs_total{mask}`: the (query, key) pairs a
    sparse-attention family's bucket leaves to attention's softmax
    (`mask="selected"`) and the pairs the causal mask alone leaves
    (`mask="causal"`), prompt rows and decode steps over every layer —
    counted at dispatch from the bucket's shape."""
    c = _counter("arbius_attn_pairs_total",
                 "attention (query, key) pairs of dispatched text "
                 "buckets, selected and causal", labelnames=("mask",))
    if c is not None:
        c.inc(kept, mask="selected")
        c.inc(causal, mask="causal")


def count_expert_calls(grouped: int, loop: int) -> None:
    """Bump `arbius_text_expert_calls_total{path}`: the `routed_experts`
    calls an expert family's bucket makes on the grouped product
    (`path="grouped"`) and on the tile loop (`path="loop"`) — counted at
    dispatch from the bucket's shape (docs/text-serving.md)."""
    c = _counter("arbius_text_expert_calls_total",
                 "routed-expert calls of dispatched text buckets, by the "
                 "walk over their tiles", labelnames=("path",))
    if c is not None:
        c.inc(grouped, path="grouped")
        c.inc(loop, path="loop")


def count_speculation(steps: int, drafts: int, accepted: int) -> None:
    """Bump `arbius_text_decode_steps_total` by the steps a speculative
    bucket program's loop ran, and `arbius_text_spec_drafts_total
    {outcome}` by the drafts it verified, split by whether the draft was
    the sampler's own choice — the program's int32 counts
    (docs/text-serving.md "Speculative decoding")."""
    c = _counter("arbius_text_decode_steps_total",
                 "steps speculative text bucket programs' decode loops ran")
    if c is not None:
        c.inc(steps)
    c = _counter("arbius_text_spec_drafts_total",
                 "drafts speculative text bucket programs verified, by "
                 "outcome", labelnames=("outcome",))
    if c is not None:
        c.inc(accepted, outcome="accepted")
        c.inc(drafts - accepted, outcome="rejected")


class TextGenRunner:
    """text-template runner (textgen, trinity, deepseek_v32, joyai_llm_flash, dots3_note, nemotron_h): decoder-only LM → deterministic UTF-8.

    Template variables (templates/textgen.json): prompt,
    max_new_tokens, sampler (enum); output out-1.txt. The sequence
    buckets (docs/text-serving.md) ride the hydrated input as
    `_prompt_bucket`/`_decode_bucket` — injected by `prepare_hydrated`
    at intake so the node's bucket_key, cost tags, and the packer all
    see them without re-deriving the policy.
    """

    def __init__(self, pipeline, params, out_name: str = "out-1.txt"):
        self.pipeline = pipeline
        self.params = params
        self.out_name = out_name

    def prepare_hydrated(self, hydrated: dict) -> dict:
        """Stamp the family's sequence-bucket fields onto the hydrated
        input (node/_process_task calls this right after hydration).
        Pure function of (input, pipeline config): every honest node
        with the same fleet-wide bucket edges stamps the same fields."""
        h = dict(hydrated)
        h["_prompt_bucket"] = self.pipeline.prompt_bucket_for(
            h.get("prompt", ""))
        h["_decode_bucket"] = self.pipeline.decode_bucket_for(
            int(h.get("max_new_tokens") or 16))
        return h

    def _buckets_of(self, hydrated: dict) -> tuple[int, int]:
        pb = hydrated.get("_prompt_bucket")
        db = hydrated.get("_decode_bucket")
        if pb is None:
            pb = self.pipeline.prompt_bucket_for(hydrated.get("prompt", ""))
        if db is None:
            db = self.pipeline.decode_bucket_for(
                int(hydrated.get("max_new_tokens") or 16))
        return int(pb), int(db)

    def __call__(self, hydrated: dict, seed: int) -> dict:
        return self.run_batch([(hydrated, seed)])[0]

    def run_batch(self, items: list[tuple[dict, int]]) -> list[dict]:
        return self.finalize(self.dispatch(items), len(items))

    def dispatch(self, items: list[tuple[dict, int]]):
        """Queue the bucket's decode loop and return WITHOUT waiting
        (JAX async dispatch — see SD15Runner.dispatch). The per-item
        requested budgets ride along to finalize: the program always
        runs the full decode bucket and the host truncates, which is
        byte-sound because generation is causally prefix-stable
        (docs/text-serving.md)."""
        first = items[0][0]
        pb, db = self._buckets_of(first)
        batch = len(items)
        attrs = self.pipeline.bucket_attrs(batch, pb, db)
        count_text_tokens(prefill=batch * pb, decode=batch * db)
        if "cache_bytes" in attrs:      # a latent, sparse family's own
            count_latent_cache(batch * attrs["cache_bytes"],
                               batch * attrs["cache_bytes_per_head"])
            count_attn_pairs(batch * attrs["attn_pairs"],
                             batch * attrs["attn_pairs_causal"])
        if "cache_bytes_window" in attrs:   # rings beside full caches
            count_window_cache(batch * attrs["cache_bytes_window"],
                               batch * attrs["cache_bytes_full"])
        if "ssm_state_bytes" in attrs:      # recurrent state beside K/V
            count_ssm_state(batch * attrs["ssm_state_bytes"])
        if "expert_calls_grouped" in attrs:     # expert layers
            count_expert_calls(attrs["expert_calls_grouped"],
                               attrs["expert_calls_loop"])
        with span("text.bucket", model=self.pipeline.FAMILY,
                  prompt_bucket=pb, decode_bucket=db, batch=batch,
                  **attrs):
            out = self.pipeline.generate(
                self.params,
                prompts=[str(h.get("prompt", "")) for h, _ in items],
                seeds=[s for _, s in items],
                prompt_bucket=pb, decode_bucket=db,
                sampler=first.get("sampler") or "greedy",
                as_device=True,
            )
        return out, [int(h.get("max_new_tokens") or 16)
                     for h, _ in items]

    def finalize(self, dev, n_real: int) -> list[dict]:
        from arbius_tpu.models.textgen import tokens_to_bytes
        from arbius_tpu.parallel.meshsolve import gather_canonical

        out, budgets = dev
        # a family with expert layers returns its routers' counts
        # beside the tokens (models/trinity, models/deepseek_v32), one
        # whose loop speculates its loop's counts after them
        # (models/joyai_flash, models/nemotron_h)
        tokens, routed, spec = (*out, None)[:3] \
            if isinstance(out, tuple) else (out, None, None)
        with span("solve.encode", n=n_real, codec="text"):
            tokens = gather_canonical(tokens)
            if routed is not None:
                made, held = (int(x) for x in np.asarray(routed))
                count_moe_assignments(made, held)
                with span("text.routed", model=self.pipeline.FAMILY,
                          assignments=made, held=held):
                    pass
            if spec is not None:
                steps, drafts, accepted, idle = (
                    int(x) for x in np.asarray(spec))
                count_speculation(steps, drafts, accepted)
                with span("text.speculate", model=self.pipeline.FAMILY,
                          batch=int(tokens.shape[0]), steps=steps,
                          drafts=drafts, accepted=accepted,
                          idle_row_steps=idle, tokens=int(tokens.size)):
                    pass
            out = []
            stalls = 0
            for i in range(n_real):
                text = tokens_to_bytes(tokens[i], budgets[i],
                                       self.pipeline.EOS_ID)
                if not text:
                    stalls += 1
                out.append({self.out_name: text})
            if stalls:
                count_decode_stall(stalls)
            return out

    def cache_tag(self, hydrated: dict, batch: int) -> str:
        """Scheduler's cross-life disk-warm join key — bucket policy
        identical to `dispatch` (docs/compile-cache.md)."""
        pb, db = self._buckets_of(hydrated)
        return self.pipeline.bucket_tag(
            batch, pb, db, hydrated.get("sampler") or "greedy")
