"""Plain float32 reference of the program's decoder-only text model
(`arbius_tpu/models/textgen`): token and learned position embeddings,
pre-LayerNorm blocks (causal attention, GELU MLP), a final LayerNorm and
the logits head. One full forward pass over prompt and served ids
together, teacher-forced: no cache, no prefill/decode split, no scan.
Imports nothing of the program; the weight tree is the layout the node
loads. Lives with the tests' tiny rehearsal: the benchmark has no text
configuration yet.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import ops

BYTES = 256   # ids under it are the byte of the same value (the id ->
              # bytes table is total and invertible on this slice alone)


def forward(p, ids, cfg):
    """ids [B,S] -> float32 logits [B,S,V]: row s predicts id s+1."""
    nh = cfg["heads"]
    s = ids.shape[1]
    x = ops.f32(p["token_embed"]["embedding"])[ids] \
        + ops.f32(p["pos_embed"])[None, :s]
    causal = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0,
                       -jnp.inf)[None, None]
    for i in range(cfg["layers"]):
        lp = p[f"layer_{i}"]
        h = ops.layer_norm(x, lp["ln1"])
        q, k, v = (ops.heads(ops.dense(h, lp[n]), nh)
                   for n in ("wq", "wk", "wv"))
        o = ops.attend(q, k, v, mask=causal, pairs=s * (s + 1) // 2)
        x = x + ops.dense(ops.unheads(o), lp["wo"])
        h = ops.layer_norm(x, lp["ln2"])
        x = x + ops.dense(ops.gelu(ops.dense(h, lp["mlp_up"])),
                          lp["mlp_down"])
    return ops.dense(ops.layer_norm(x, p["final_norm"]), p["lm_head"])


def prompt_bucket(arch: dict, prompt: str) -> int:
    """The smallest edge that holds bos + bytes + eos; the top one
    truncates (the fleet-wide rule the program's pipeline states)."""
    need = len(prompt.encode("utf-8")) + 2
    edges = sorted(arch["prompt_buckets"])
    return next((e for e in edges if need <= e), edges[-1])


def decode_bucket(arch: dict, max_new_tokens: int) -> int:
    edges = sorted(arch["decode_buckets"])
    return next((e for e in edges if max_new_tokens <= e), edges[-1])


def parts(arch):
    return {"forward": lambda p, ids: forward(p, ids, arch["model"])}


@functools.lru_cache(maxsize=8)
def _jitted(arch_key, weights):
    return {k: jax.jit(ops.traced_with(v, weights))
            for k, v in parts(json.loads(arch_key)).items()}


def logits(params, arch: dict, task: dict, served,
           weights: str | None = None) -> np.ndarray:
    """[T, BYTES] float32: for each of the T served ids, the logits over
    the byte slice at the position that produced it, given the prompt
    (padded to its bucket as the tokenizer pads it) and the served ids
    before it. `weights` ("fp8") computes the control instead."""
    t = arch["tokenizer"]
    p = prompt_bucket(arch, task["prompt"])
    prompt = ops.byte_tokens(task["prompt"], p, t["bos_id"], t["eos_id"])
    ids = np.concatenate([prompt, np.asarray(served, np.int32)[:-1]])[None]
    fn = _jitted(json.dumps(arch, sort_keys=True), weights)["forward"]
    return np.asarray(fn(params, jnp.asarray(ids)))[0, p - 1:, :BYTES]


def forward_shapes(arch: dict, task: dict, batch: int = 1):
    """One solution is one pass over its prompt bucket and its decode
    bucket's positions (the work of prefill plus every decode step, each
    counted once: the causal pairs)."""
    s = prompt_bucket(arch, task.get("prompt", "")) \
        + decode_bucket(arch, int(task["max_new_tokens"])) - 1
    return [("forward", (jax.ShapeDtypeStruct((batch, s), jnp.int32),), 1)]
