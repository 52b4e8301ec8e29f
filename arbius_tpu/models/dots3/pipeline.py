"""dots3_note pipeline — TextGenPipeline's bucket policy, decode loop,
samplers and seed chain over dots3-note-prev.

As with the other share families, nothing of the serving discipline is
copied: a bucket is (batch, prompt edge, decode edge, sampler), ONE
jitted program of prefill then the `lax.scan` decode loop with the
caches as carry, prompts padded to the edge with eos and no padding
mask, samplers over the byte ids alone. What this family brings is the
model behind the loop (models/dots3/model.py): two latent attentions of
their own shapes, so two forms of cache in one carry — a full layer's
latent and indexer caches, a sliding layer's ring of latent rows — and a
banded prefill attention for the sliding layers.
"""
from __future__ import annotations

from arbius_tpu.models.deepseek_v32.model import _ffn_rows
from arbius_tpu.models.deepseek_v32.pipeline import (
    ffn_expert_calls,
    selected_kernel_counts,
)
from arbius_tpu.models.dots3 import model as dots3
from arbius_tpu.models.dots3.model import Dots3NoteConfig
from arbius_tpu.models.trinity.pipeline import (
    SharePipeline,
    share_trace_specs,
)
from arbius_tpu.ops import selected_flash


def window_kernel_counts(a, batch: int, prompt_bucket: int,
                         layers: int) -> tuple:
    """The banded kernel's share of `attn_kernel` for `layers` sliding
    layers of shape `a`: static, from the rule
    `ops.selected_flash.window_attention` reads off the same shapes (the
    joined nope | rope width for the query's): one call a layer a
    sequence, all its rows, each over every group of heads."""
    if not selected_flash.kernel_serves(prompt_bucket, a.qk_head_dim,
                                        a.v_head_dim):
        return 0, 0, 0
    walked, dense = selected_flash.walk_blocks(
        prompt_bucket, prompt_bucket, a.heads, a.window)
    each = batch * layers
    return each, each * walked, each * dense


class Dots3NotePipeline(SharePipeline):
    FAMILY = "dots3_note"

    def __init__(self, config: Dots3NoteConfig | None = None, mesh=None,
                 precision: str = "bf16",
                 prompt_buckets: tuple = (8192,),
                 decode_buckets: tuple = (1024,), top_k: int = 8):
        super().__init__(config or Dots3NoteConfig.published(),
                         mesh=mesh, precision=precision,
                         prompt_buckets=prompt_buckets,
                         decode_buckets=decode_buckets, top_k=top_k)

    def _prefill(self, params, ids, total: int):
        return dots3.prefill(params, ids, total, self.config)

    def _decode(self, params, tok, carry, pos):
        return dots3.decode(params, tok, carry, pos, self.config)

    def attn_kernel(self, batch: int, prompt_bucket: int) -> tuple:
        """Both prefill kernels' counts summed: deepseek_v32's selected
        kernel over the full layers, the banded one over the sliding
        layers."""
        cfg = self.config
        full = selected_kernel_counts(cfg.attn("full"), batch,
                                      prompt_bucket, cfg.count("full"))
        window = window_kernel_counts(cfg.attn("sliding"), batch,
                                      prompt_bucket, cfg.count("sliding"))
        return tuple(f + w for f, w in zip(full, window))

    def bucket_attrs(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> dict:
        """One sequence's cache bytes by form — the sliding layers' rings,
        the full layers' latent and indexer caches, and the rings' layers
        at full length —, both prefill kernels' counts summed, and the
        rows of a prefill FFN chunk with the chunks a bucket runs —
        static, from the config."""
        cfg = self.config
        window, full, window_full = cfg.cache_bytes(prompt_bucket
                                                    + decode_bucket)
        calls, blocks, dense = self.attn_kernel(batch, prompt_bucket)
        ffn_rows = _ffn_rows(prompt_bucket, cfg)
        return {"cache_bytes_window": window, "cache_bytes_full": full,
                "cache_bytes_window_full": window_full,
                "attn_kernel_calls": calls, "attn_blocks": blocks,
                "attn_blocks_dense": dense,
                "ffn_rows": ffn_rows,
                "ffn_calls": batch * len(cfg.layers)
                * (prompt_bucket // ffn_rows),
                **self.expert_paths(batch, prompt_bucket, decode_bucket)}

    def expert_calls(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> tuple:
        """deepseek_v32's: a call an expert layer a prefill FFN chunk,
        and one a decode step over the batch."""
        return ffn_expert_calls(self.config, dots3.n_moe(self.config),
                                batch, prompt_bucket, decode_bucket)

    def _init_fn(self):
        return lambda key: dots3.init_params(self.config, key)


MESH_LAYOUTS: tuple[tuple[str, ...], ...] = ()


def trace_specs():
    """graphlint trace specs at the tiny whole-model config: prefill,
    the decode loop (greedy and seeded top-k) and the composed bucket
    program — the prompt edge longer than the tiny window and the tiny
    `index_topk`, so the ring fill, the ring's write rule and the
    selection are in the goldened graphs of both phases."""
    return share_trace_specs(
        "dots3_note", lambda: Dots3NotePipeline(
            Dots3NoteConfig.tiny(), prompt_buckets=(12,),
            decode_buckets=(4,), top_k=4), 12, 4)
