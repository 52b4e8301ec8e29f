"""nemotron_h pipeline — TextGenPipeline's bucket policy, samplers and
seed chain over Nemotron 3 Super, behind the speculative loop that
joyai_llm_flash serves with (models/joyai_flash/pipeline.py
`SpeculativePipeline`: imported, not copied).

A bucket is (batch, prompt edge, decode edge, sampler) and ONE jitted
program of prefill then the loop: two positions a row a step, the
module's draft verified, one or two tokens out. What this family brings
to the loop is recurrent state: a Mamba layer's state and conv window
cannot be rewound by overwriting a row, so a step stores them after its
first position with the second position's increment beside them, and
`_commit` records, per row, whether the row keeps the state after q
(draft rejected) or after q + 1 (taken) — `model.commit`; the next step
folds the taken increment into its own first update. As for
joyai_llm_flash there is no one-token program: the two-position step
defines the bytes (docs/determinism.md), and prefill's chunked scan is a
determinism class of its own.
"""
from __future__ import annotations

from arbius_tpu.models.joyai_flash.pipeline import SpeculativePipeline
from arbius_tpu.models.nemotron_h import model as nemotron
from arbius_tpu.models.nemotron_h.model import NemotronHConfig
from arbius_tpu.models.trinity.pipeline import share_trace_specs
from arbius_tpu.ops import causal_flash


class NemotronHPipeline(SpeculativePipeline):
    FAMILY = "nemotron_h"

    def __init__(self, config: NemotronHConfig | None = None, mesh=None,
                 precision: str = "bf16",
                 prompt_buckets: tuple = (2048,),
                 decode_buckets: tuple = (1024,), top_k: int = 8):
        super().__init__(config or NemotronHConfig.published(),
                         mesh=mesh, precision=precision,
                         prompt_buckets=prompt_buckets,
                         decode_buckets=decode_buckets, top_k=top_k)

    def _prefill(self, params, ids, total: int):
        return nemotron.prefill(params, ids, total, self.config)

    def _step(self, params, toks, caches, q):
        return nemotron.step(params, toks, caches, q, self.config)

    def _draft(self, params, toks, h, cache, q):
        return nemotron.draft(params, toks, h, cache, q, self.config)

    def _commit(self, caches, took):
        return nemotron.commit(caches, took, self.config)

    def _n_moe(self) -> int:
        return nemotron.n_moe(self.config)

    def attn_kernel(self, batch: int, prompt_bucket: int) -> tuple:
        """Static, from the rule `ops.causal_flash.causal_attention` reads
        off the same shapes: one call an attention layer a sequence
        (prefill walks the batch a sequence at a time), each over every KV
        head; the module's prompt rows take no attention."""
        cfg = self.config
        if not causal_flash.kernel_serves(prompt_bucket):
            return 0, 0, 0
        walked, dense = causal_flash.walk_blocks(prompt_bucket, None,
                                                 cfg.group)
        calls = batch * cfg.count("attention")
        heads = calls * cfg.kv_heads
        return calls, heads * walked, heads * dense

    def bucket_attrs(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> dict:
        """One sequence's state-space bytes (a float32 state and a conv
        window a Mamba layer, whatever the length) and K/V bytes (the
        attention layers' and the module's), the prefill kernel's counts
        and the routed experts' calls by walk — static, from the
        config."""
        cfg = self.config
        calls, blocks, dense = self.attn_kernel(batch, prompt_bucket)
        return {"ssm_state_bytes": cfg.ssm_state_bytes(),
                "kv_bytes": cfg.kv_bytes(prompt_bucket + decode_bucket),
                "attn_kernel_calls": calls, "attn_blocks": blocks,
                "attn_blocks_dense": dense,
                **self.expert_paths(batch, prompt_bucket, decode_bucket)}

    def expert_calls(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> tuple:
        """((rows routed at once, calls a bucket makes), ...): an expert
        layer's call a sequence over the whole prompt; the module's first
        draft over the batch; then, a step, each expert layer's and the
        module's call over its two positions a row — counted at one token
        a step, the loop's most steps (`text.speculate` says how many
        ran)."""
        n = nemotron.n_moe(self.config)
        return ((prompt_bucket, batch * n), (batch, 1),
                (2 * batch, (decode_bucket - 1) * (n + 1)))

    def _init_fn(self):
        return lambda key: nemotron.init_params(self.config, key)


MESH_LAYOUTS: tuple[tuple[str, ...], ...] = ()


def trace_specs():
    """graphlint trace specs at the tiny whole-model config: prefill
    (three whole chunks of the scan, the module's K/V rows), the
    speculative loop with its per-row fold of state (greedy and seeded
    top-k) and the composed bucket program."""
    return share_trace_specs(
        "nemotron_h", lambda: NemotronHPipeline(
            NemotronHConfig.tiny(), prompt_buckets=(12,),
            decode_buckets=(4,), top_k=4), 12, 4)
