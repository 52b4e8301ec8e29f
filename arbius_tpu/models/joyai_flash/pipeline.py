"""joyai_llm_flash pipeline — TextGenPipeline's bucket policy, samplers
and seed chain over JoyAI-LLM-Flash, behind a SPECULATIVE decode loop.

A bucket is still (batch, prompt edge, decode edge, sampler) and ONE
jitted program of prefill then a decode loop, prompts padded to the edge
with eos and no padding mask, samplers over the byte ids alone. The loop
is the second beside TextGenPipeline's `lax.scan`, shared by the
families whose model drafts (`SpeculativePipeline`: this one and
models/nemotron_h): the model's multi-token prediction module drafts one
token a step and the model verifies it, so a step yields one or two
tokens a row.

A row holds n emitted tokens, the last t_{n-1} at position q = P + n - 1,
and a draft d_n. A step runs the main model on positions q, q + 1 over
(t_{n-1}, d_n) in one pass (`model.step`, S = 2) → L0, L1;
t_n = sample(L0, key, n) — the shared samplers, the key folded by the
TOKEN'S index, as the scan folds it. If t_n = d_n the row also takes
t_{n+1} = sample(L1, key, n + 1); else cache row q + 1 is overwritten by
the next step. A carry that holds recurrent state (models/nemotron_h's
Mamba-2 layers) cannot be rewound so: its step stores the state after q
with the increment of q + 1 beside it, the family's `_commit` records
which rows took the draft, and the next step folds their increment in
(the identity here). The module then runs the same two positions on
(h_q, t_n), (h_{q+1}, t_{n+1}) and the next draft is read at q +
accepted. A token is taken only when it IS the sampler's choice at its
index, so greedy and seeded top-k are both exact: in exact arithmetic
the bytes are those of one-token-a-step decoding and do not depend on
the module's weights. Rows that have their tokens stop emitting; the
`lax.while_loop` ends when every row has them.

There is no one-token program for this family and no knob that picks
one: in bfloat16 a two-position step may round differently from a
one-position step, so the template's bytes are DEFINED by this program
(docs/text-serving.md).

Beside the tokens and the routers' pair the program returns int32
[steps run, drafts verified, drafts accepted, row-steps on finished
rows]: a draft counts as verified where its row still had room for the
token after it, so a row's tokens after the first = steps run - its idle
steps + its accepted drafts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from arbius_tpu.models.deepseek_v32.pipeline import selected_kernel_counts
from arbius_tpu.models.joyai_flash import model as joyai
from arbius_tpu.models.joyai_flash.model import JoyAIFlashConfig
from arbius_tpu.models.trinity.pipeline import (
    SharePipeline,
    share_trace_specs,
)


def accept(t_n, drafted, room):
    """Which rows take the token after t_n from the second position's
    logits: those whose draft WAS the sampler's choice, and that have
    room for one more."""
    return (t_n == drafted) & room


class SpeculativePipeline(SharePipeline):
    """The speculative loop of the module docstring, shared by the
    families whose model drafts with a multi-token prediction module
    (joyai_llm_flash here, models/nemotron_h). A family names `_step`
    (the main model on S positions a row), `_draft` (the module on the
    same positions), `_n_moe` (its main model's expert layers) and, where
    its carry holds state that a next step cannot overwrite, `_commit`.
    Prefill's carry is (caches, the module's cache, the last prompt
    position's hidden state, int32 [assignments, held])."""

    def _decode(self, params, tok, carry, pos):
        raise NotImplementedError(
            f"{self.FAMILY} has no one-token decode program: its bytes "
            "are defined by the two-position speculative step")

    def _step(self, params, toks, caches, q):
        """(logits [B, S, V'], hidden [B, S, d], caches, held) for toks
        [B, S] at a row's positions q[B] .. q[B] + S - 1."""
        raise NotImplementedError

    def _draft(self, params, toks, h, cache, q):
        """(the module's logits [B, S, V'], its cache, held)."""
        raise NotImplementedError

    def _n_moe(self) -> int:
        raise NotImplementedError

    def _commit(self, caches, took):
        """The main model's caches once a step's draft is decided: `took`
        [B] bool, the rows that took both positions. The identity for a
        carry of cache rows, which the next step overwrites from the
        first position it runs (row q + 1 of a rejected draft); a family
        whose carry holds recurrent state records `took`, so that its next
        step starts from the state after q + 1 where `took`, after q
        elsewhere."""
        return caches

    # -- the speculative loop ----------------------------------------------
    def _decode_loop(self, prompt_bucket: int, decode_bucket: int,
                     sampler: str):
        """(params, prefill's carry, t0, keys) → (tokens[B, T], routed
        int32 [2], speculation int32 [4]); the body is the module
        docstring's step."""
        p, t = prompt_bucket, decode_bucket
        cfg = self.config
        sample = self._sampler_fn(sampler)
        greedy = self._sampler_fn("greedy")
        i32 = jnp.int32

        def sample_rows(logits, keys, index):
            """The bucket's sampler, each row's key folded by the row's
            own token index."""
            return jax.vmap(lambda row, key, i: sample(
                row[None], key[None], i)[0])(logits, keys, index)

        def loop(params, carry, t0, keys):
            caches, mtp_cache, h_last, routed = carry
            b = t0.shape[0]
            at = jnp.arange(t, dtype=i32)[None]
            # the module's position P-1 had to wait for t0: its row, and
            # the draft of token 1
            guess, mtp_cache, held = self._draft(
                params, t0[:, None], h_last[:, None], mtp_cache,
                jnp.full((b,), p - 1, i32))
            routed = routed + jnp.stack(
                [i32(b * cfg.experts_per_token), held])
            made = i32(2 * b * cfg.experts_per_token
                       * (self._n_moe() + 1))
            state = (caches, mtp_cache,
                     jnp.where(at == 0, t0[:, None], 0).astype(i32),
                     jnp.ones((b,), i32), t0, greedy(guess[:, 0], keys, 0),
                     routed, jnp.zeros((4,), i32))

            def live_rows(state):
                return (state[3] < t).any()

            def body(state):
                caches, mtp_cache, tokens, n, last, drafted, routed, \
                    spec = state
                live = n < t
                room = live & (n + 1 < t)
                # a finished row keeps running its last step's positions
                q = p - 1 + jnp.minimum(n, t - 1)
                logits, h, caches, held = self._step(
                    params, jnp.stack([last, drafted], axis=1), caches, q)
                t_n = sample_rows(logits[:, 0], keys, n)
                t_n1 = sample_rows(logits[:, 1], keys, n + 1)
                took = accept(t_n, drafted, room)
                caches = self._commit(caches, took)
                tokens = jnp.where((at == n[:, None]) & live[:, None],
                                   t_n[:, None], tokens)
                tokens = jnp.where((at == n[:, None] + 1) & took[:, None],
                                   t_n1[:, None], tokens)
                guess, mtp_cache, held_m = self._draft(
                    params, jnp.stack([t_n, t_n1], axis=1), h, mtp_cache,
                    q)
                guess = greedy(guess, keys, 0)               # [B, 2]
                drafted = jnp.where(took, guess[:, 1], guess[:, 0])
                last = jnp.where(took, t_n1, t_n)
                n = n + live.astype(i32) + took.astype(i32)
                routed = routed + jnp.stack([made, held + held_m])
                spec = spec + jnp.stack(
                    [i32(1), room.sum(dtype=i32), took.sum(dtype=i32),
                     (~live).sum(dtype=i32)])
                return (caches, mtp_cache, tokens, n, last, drafted,
                        routed, spec)

            state = jax.lax.while_loop(live_rows, body, state)
            return state[2], state[6], state[7]

        return loop


class JoyAIFlashPipeline(SpeculativePipeline):
    FAMILY = "joyai_llm_flash"

    def __init__(self, config: JoyAIFlashConfig | None = None, mesh=None,
                 precision: str = "bf16",
                 prompt_buckets: tuple = (2048,),
                 decode_buckets: tuple = (512,), top_k: int = 8):
        super().__init__(config or JoyAIFlashConfig.published(),
                         mesh=mesh, precision=precision,
                         prompt_buckets=prompt_buckets,
                         decode_buckets=decode_buckets, top_k=top_k)

    def _prefill(self, params, ids, total: int):
        return joyai.prefill(params, ids, total, self.config)

    def _step(self, params, toks, caches, q):
        return joyai.step(params, toks, caches, q, self.config)

    def _draft(self, params, toks, h, cache, q):
        return joyai.draft(params, toks, h, cache, q, self.config)

    def _n_moe(self) -> int:
        return joyai.n_moe(self.config)

    def attn_kernel(self, batch: int, prompt_bucket: int) -> tuple:
        """deepseek_v32's rule and counts over the main layers (the
        module's prompt rows take no attention)."""
        return selected_kernel_counts(self.config, batch, prompt_bucket)

    def bucket_attrs(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> dict:
        """One sequence's cache bytes (a latent row a position, the main
        layers and the module's) and the prefill kernel's counts."""
        calls, blocks, dense = self.attn_kernel(batch, prompt_bucket)
        return {"latent_bytes": self.config.cache_bytes(
                    prompt_bucket + decode_bucket),
                "attn_kernel_calls": calls, "attn_blocks": blocks,
                "attn_blocks_dense": dense,
                **self.expert_paths(batch, prompt_bucket, decode_bucket)}

    def expert_calls(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> tuple:
        """((rows routed at once, calls a bucket makes), ...): an expert
        layer's call a prefill block of query rows; the module's first
        draft over the batch; then, a step, each expert layer's and the
        module's call over its two positions a row — counted at one token
        a step, the loop's most steps (`text.speculate` says how many
        ran)."""
        cfg = self.config
        n = joyai.n_moe(cfg)
        rows = joyai._block(prompt_bucket, cfg.heads)
        return ((rows, batch * n * (prompt_bucket // rows)),
                (batch, 1),
                (2 * batch, (decode_bucket - 1) * (n + 1)))

    def _init_fn(self):
        return lambda key: joyai.init_params(self.config, key)


MESH_LAYOUTS: tuple[tuple[str, ...], ...] = ()


def trace_specs():
    """graphlint trace specs at the tiny whole-model config: prefill
    with the module's cache rows, the speculative loop (greedy and
    seeded top-k) and the composed bucket program."""
    return share_trace_specs(
        "joyai_llm_flash", lambda: JoyAIFlashPipeline(
            JoyAIFlashConfig.tiny(), prompt_buckets=(12,),
            decode_buckets=(4,), top_k=4), 12, 4)
