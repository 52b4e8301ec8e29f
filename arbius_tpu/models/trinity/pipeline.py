"""trinity pipeline — TextGenPipeline's bucket policy, decode loop,
samplers and seed chain over the Trinity (`afmoe`) model.

Nothing of the serving discipline is copied: a bucket is still (batch,
prompt edge, decode edge, sampler), ONE jitted program of prefill then
the `lax.scan` decode loop with the caches as carry, prompts padded to
the edge with eos and no padding mask, the host truncating to each
task's budget. What this family brings is the model behind the loop
(models/trinity/model.py): two kinds of cache in the carry, routed
experts told which they hold, and a bucket program that returns, beside
the tokens, its routers' int32 assignment counts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from arbius_tpu.models.textgen.pipeline import TextGenPipeline
from arbius_tpu.models.trinity import model as trinity
from arbius_tpu.models.trinity.model import TrinityConfig
from arbius_tpu.ops import causal_flash


class SharePipeline(TextGenPipeline):
    """What the families that serve one chip's share of a model have in
    common (trinity here, models/deepseek_v32, models/joyai_flash,
    models/dots3, models/nemotron_h): no
    mesh layout, bf16 only, the byte tokenizer's ids inside the
    vocabulary rows held, the samplers over those ids alone, the model
    a set of pure functions of the param tree, and the routers' counts
    beside the tokens. A family names itself, its default config and
    edges, `_prefill`, `_decode` and `_init_fn`; those whose step yields
    more than one token (joyai_llm_flash, nemotron_h) share
    `models.joyai_flash.pipeline.SpeculativePipeline`'s loop in the
    scan's place."""

    # ids the byte tokenizer turns into text, one byte each. No
    # vocabulary file of the model's is in the tree, so an id past them
    # is no text: the samplers see the byte ids' logits alone, and a
    # task always spends its whole budget (no eos is ever sampled).
    BYTE_IDS = 256

    def __init__(self, config, mesh=None, precision: str = "bf16",
                 prompt_buckets: tuple = (8192,),
                 decode_buckets: tuple = (256,), top_k: int = 8):
        if mesh is not None:
            raise ValueError(
                f"{self.FAMILY} ships no mesh layout: its expert axis "
                "across chips is a determinism class of its own "
                "(ROADMAP R4)")
        if precision != "bf16":
            raise ValueError(
                f"precision mode {precision!r} is not shipped for the "
                f"{self.FAMILY} family — it serves bf16 only")
        super().__init__(config, mesh=None, precision=precision,
                         prompt_buckets=prompt_buckets,
                         decode_buckets=decode_buckets, top_k=top_k)
        lo, hi = self.config.vocab_rows
        if lo != 0 or hi <= self.EOS_ID:
            raise ValueError(
                f"vocab_rows {self.config.vocab_rows} must hold the byte "
                f"tokenizer's ids 0..{self.EOS_ID}: the chip that samples "
                "holds the slice the text lives in")
        if self.top_k > self.BYTE_IDS:
            raise ValueError(f"top_k ({self.top_k}) exceeds the "
                             f"{self.BYTE_IDS} byte ids sampled over")

    def _make_model(self):
        return None     # pure functions of the param tree, no module

    def _sampler_fn(self, sampler: str):
        """The shared samplers over the byte ids alone: every other
        logit of the slice is computed (the head is this chip's share
        of the deployment's) and masked to the least float32."""
        sample = super()._sampler_fn(sampler)
        n = self.BYTE_IDS

        def over_bytes(logits, keys, step):
            text = jnp.arange(logits.shape[-1]) < n
            return sample(jnp.where(text, logits,
                                    jnp.finfo(logits.dtype).min),
                          keys, step)

        return over_bytes

    def expert_paths(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> dict:
        """The `routed_experts` calls a bucket makes on each walk
        (`expert_calls_grouped`, `expert_calls_loop` on `text.bucket`):
        static, from the family's `expert_calls` and the rule that
        function reads off the same shapes (`trinity.grouped_walk`)."""
        paths = {"expert_calls_grouped": 0, "expert_calls_loop": 0}
        for rows, calls in self.expert_calls(batch, prompt_bucket,
                                             decode_bucket):
            paths["expert_calls_grouped"
                  if trinity.grouped_walk(rows, self.config)
                  else "expert_calls_loop"] += calls
        return paths

    def _outputs(self, tokens, carry):
        """(tokens[B, T], routed int32 [assignments made, on held
        experts]) — the counts are part of the goldened program."""
        return tokens, carry[1]


class TrinityPipeline(SharePipeline):
    FAMILY = "trinity"

    def __init__(self, config: TrinityConfig | None = None, mesh=None,
                 precision: str = "bf16",
                 prompt_buckets: tuple = (8192,),
                 decode_buckets: tuple = (256,), top_k: int = 8):
        super().__init__(config or TrinityConfig.published(), mesh=mesh,
                         precision=precision,
                         prompt_buckets=prompt_buckets,
                         decode_buckets=decode_buckets, top_k=top_k)

    # -- the model behind the loop -----------------------------------------
    def _prefill(self, params, ids, total: int):
        return trinity.prefill(params, ids, total, self.config)

    def _decode(self, params, tok, carry, pos):
        return trinity.decode(params, tok, carry, pos, self.config)

    def kv_rows(self, prompt_bucket: int, decode_bucket: int) -> tuple:
        return self.config.kv_rows(prompt_bucket + decode_bucket)

    def attn_kernel(self, batch: int, prompt_bucket: int) -> tuple:
        """Static, from the rule `ops.causal_flash.causal_attention`
        reads off the same shapes: one call a layer a sequence (prefill
        walks the batch a sequence at a time), each over every KV head."""
        cfg = self.config
        if not causal_flash.kernel_serves(prompt_bucket):
            return 0, 0, 0
        walked = dense = 0
        for _, attn in cfg.layers:
            w, n = causal_flash.walk_blocks(
                prompt_bucket, cfg.window if attn == "sliding" else None,
                cfg.group)
            walked, dense = walked + w, dense + n
        heads = batch * cfg.kv_heads
        return batch * len(cfg.layers), heads * walked, heads * dense

    def expert_calls(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> tuple:
        """((rows routed at once, calls a bucket makes), ...): an expert
        layer's call a sequence over the whole prompt, and one a decode
        step over the batch."""
        n = trinity.n_moe(self.config)
        return ((prompt_bucket, batch * n),
                (batch, (decode_bucket - 1) * n))

    def bucket_attrs(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> dict:
        """TextGenPipeline's cache rows and prefill kernel's counts, and
        the routed experts' calls by walk (`expert_paths`)."""
        return {**super().bucket_attrs(batch, prompt_bucket,
                                       decode_bucket),
                **self.expert_paths(batch, prompt_bucket, decode_bucket)}

    def _init_fn(self):
        return lambda key: trinity.init_params(self.config, key)


MESH_LAYOUTS: tuple[tuple[str, ...], ...] = ()


def share_trace_specs(model: str, make_pipe, p: int, t: int):
    """The four graphlint trace specs of a `SharePipeline` family at its
    tiny whole-model config: prefill, the decode loop (greedy and seeded
    top-k) and the composed bucket program, batch 2, edges (p, t)."""
    from arbius_tpu.models.trace_specs import TraceSpec

    def abstract(pipe, batch):
        shapes = jax.eval_shape(
            lambda: pipe.init_params(seed=0, dtype="bfloat16"))
        sds = jax.ShapeDtypeStruct
        return (shapes, sds((batch, p), jnp.int32),
                sds((batch,), jnp.uint32), sds((batch,), jnp.uint32))

    def build_prefill():
        pipe = make_pipe()
        shapes, ids, _, _ = abstract(pipe, 2)
        return pipe.prefill_program(2, p, t), (shapes, ids)

    def build_decode(sampler):
        def build():
            pipe = make_pipe()
            shapes, ids, lo, hi = abstract(pipe, 2)
            _, carry = jax.eval_shape(pipe.prefill_program(2, p, t),
                                      shapes, ids)
            t0 = jax.ShapeDtypeStruct((2,), jnp.int32)
            return (pipe.decode_program(2, p, t, sampler),
                    (shapes, carry, t0, lo, hi))

        return build

    def build_generate():
        pipe = make_pipe()
        return (pipe.compiled_bucket(2, p, t, "greedy"),
                abstract(pipe, 2))

    bucket = f"b2.p{p}.t{t}"
    return [
        TraceSpec(model=model, entry="prefill", bucket=bucket,
                  mesh="single", dtype="bfloat16", build=build_prefill),
        TraceSpec(model=model, entry="decode",
                  bucket=f"{bucket}.greedy", mesh="single",
                  dtype="bfloat16", build=build_decode("greedy")),
        TraceSpec(model=model, entry="decode",
                  bucket=f"{bucket}.top_k", mesh="single",
                  dtype="bfloat16", build=build_decode("top_k")),
        TraceSpec(model=model, entry="generate",
                  bucket=f"{bucket}.greedy", mesh="single",
                  dtype="bfloat16", build=build_generate),
    ]


def trace_specs():
    """graphlint trace specs at the tiny whole-model config: prefill,
    the decode loop (greedy and seeded top-k) and the composed bucket
    program — the prompt edge longer than the tiny window, so the ring
    fill and the ring's write rule are in the goldened graphs."""
    return share_trace_specs(
        "trinity", lambda: TrinityPipeline(
            TrinityConfig.tiny(), prompt_buckets=(12,),
            decode_buckets=(4,), top_k=4), 12, 4)
