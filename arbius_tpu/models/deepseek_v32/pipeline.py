"""deepseek_v32 pipeline — TextGenPipeline's bucket policy, decode loop,
samplers and seed chain over the DeepSeek-V3.2-Exp model.

As with trinity, nothing of the serving discipline is copied: a bucket
is (batch, prompt edge, decode edge, sampler), ONE jitted program of
prefill then the `lax.scan` decode loop with the caches as carry,
prompts padded to the edge with eos and no padding mask, samplers over
the byte ids alone. What this family brings is the model behind the loop
(models/deepseek_v32/model.py): a latent cache and an indexer's key
cache in the carry, a selection of keys in prefill and in every step,
routed experts under a group limit; the bucket program returns its
routers' int32 assignment counts beside the tokens, as trinity's does.
"""
from __future__ import annotations

from arbius_tpu.models.deepseek_v32 import model as dsv32
from arbius_tpu.models.deepseek_v32.model import DeepSeekV32Config
from arbius_tpu.models.trinity.pipeline import (
    SharePipeline,
    share_trace_specs,
)
from arbius_tpu.ops import selected_flash


def selected_kernel_counts(cfg, batch: int, prompt_bucket: int,
                           layers: int | None = None) -> tuple:
    """`attn_kernel` of a family whose prefill attention is
    `ops.selected_flash.selected_attention` (this one, models/joyai_flash
    under an all-ones selection, and models/dots3's `layers` full layers
    at their shape `cfg`). Static, from the rule that function reads off
    the same shapes: one call a block of query rows, a layer, a sequence
    (prefill walks the batch a sequence at a time and a sequence a block
    at a time), each over every group of heads."""
    if not selected_flash.kernel_serves(
            prompt_bucket, cfg.qk_nope_head_dim, cfg.v_head_dim):
        return 0, 0, 0
    rows = dsv32._block(prompt_bucket, cfg.heads)
    walked, dense = selected_flash.walk_blocks(prompt_bucket, rows,
                                               cfg.heads)
    each = batch * (len(cfg.layers) if layers is None else layers)
    return each * (prompt_bucket // rows), each * walked, each * dense


def ffn_expert_calls(cfg, n_moe: int, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> tuple:
    """`expert_calls` of a family whose prefill FFN runs in
    `_ffn_rows` chunks (this one, models/dots3) and whose decode loop is
    the scan: a call an expert layer a chunk, and one a step."""
    rows = dsv32._ffn_rows(prompt_bucket, cfg)
    return ((rows, batch * n_moe * (prompt_bucket // rows)),
            (batch, (decode_bucket - 1) * n_moe))


class DeepSeekV32Pipeline(SharePipeline):
    FAMILY = "deepseek_v32"

    def __init__(self, config: DeepSeekV32Config | None = None, mesh=None,
                 precision: str = "bf16",
                 prompt_buckets: tuple = (16384,),
                 decode_buckets: tuple = (256,), top_k: int = 8):
        super().__init__(config or DeepSeekV32Config.published(),
                         mesh=mesh, precision=precision,
                         prompt_buckets=prompt_buckets,
                         decode_buckets=decode_buckets, top_k=top_k)

    def _prefill(self, params, ids, total: int):
        return dsv32.prefill(params, ids, total, self.config)

    def _decode(self, params, tok, carry, pos):
        return dsv32.decode(params, tok, carry, pos, self.config)

    def attn_kernel(self, batch: int, prompt_bucket: int) -> tuple:
        return selected_kernel_counts(self.config, batch, prompt_bucket)

    def bucket_attrs(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> dict:
        """One sequence's cache bytes beside what per-head K and V rows
        would take, the pairs the selection leaves to attention beside
        the causal mask's, the prefill kernel's counts under the names
        trinity gives its own, and the rows of a prefill FFN chunk with
        the chunks a bucket runs (each routes its rows to the held
        experts in one call) — static, from the config."""
        cfg = self.config
        held, per_head = cfg.cache_bytes(prompt_bucket + decode_bucket)
        pairs, causal = cfg.attn_pairs(prompt_bucket, decode_bucket)
        calls, blocks, dense = self.attn_kernel(batch, prompt_bucket)
        ffn_rows = dsv32._ffn_rows(prompt_bucket, cfg)
        return {"cache_bytes": held, "cache_bytes_per_head": per_head,
                "attn_pairs": pairs, "attn_pairs_causal": causal,
                "attn_kernel_calls": calls, "attn_blocks": blocks,
                "attn_blocks_dense": dense, "ffn_rows": ffn_rows,
                "ffn_calls": batch * len(cfg.layers)
                * (prompt_bucket // ffn_rows),
                **self.expert_paths(batch, prompt_bucket, decode_bucket)}

    def expert_calls(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> tuple:
        """((rows routed at once, calls a bucket makes), ...): an expert
        layer's call a prefill FFN chunk, and one a decode step over the
        batch."""
        return ffn_expert_calls(self.config, dsv32.n_moe(self.config),
                                batch, prompt_bucket, decode_bucket)

    def _init_fn(self):
        return lambda key: dsv32.init_params(self.config, key)


MESH_LAYOUTS: tuple[tuple[str, ...], ...] = ()


def trace_specs():
    """graphlint trace specs at the tiny whole-model config: prefill,
    the decode loop (greedy and seeded top-k) and the composed bucket
    program — the prompt edge longer than the tiny `index_topk`, so the
    selection is in the goldened graphs of both phases."""
    return share_trace_specs(
        "deepseek_v32", lambda: DeepSeekV32Pipeline(
            DeepSeekV32Config.tiny(), prompt_buckets=(12,),
            decode_buckets=(4,), top_k=4), 12, 4)
