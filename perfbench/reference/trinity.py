"""Plain float32 reference of Trinity-Large-Preview (`afmoe`), the share
of it that a configuration states (`experts_held`, `vocab_rows`).

One teacher-forced forward pass over prompt and served ids together:
no cache, no ring, no kernel, no prefill/decode split. `jax.numpy` at
float32 with matmul precision "highest" (perfbench/reference/ops.py);
imports nothing of the program — the weight tree is the layout a node
loads. The equations, as `transformers`' `modeling_afmoe.py` computes
them (the configuration file lists each point under `assumed`):

  h = embed[ids] * sqrt(hidden)                              (mup_enabled)
  h = h + post_attn_norm(attn(input_norm(h)))      (all RMSNorm, eps 1e-5)
  h = h + post_mlp_norm(mlp(pre_mlp_norm(h)))
  attn: q, k, v, g = x·Wq, x·Wk, x·Wv, x·Wg; q and k RMS-normed over each
        head; rotary (theta 10000, the two halves rotated) on q and k in
        SLIDING layers only; causal softmax attention, scale D^-1/2, each
        KV head serving heads/kv_heads query heads, keys limited to the
        last `window` positions (the query's own included) in sliding
        layers; out = (attention * sigmoid(g))·Wo
  dense mlp: (silu(x·Wgate) * (x·Wup))·Wdown
  expert mlp: s = sigmoid(x·Wr) over ALL experts; the chosen are the
        top-k of s + expert_bias (ties to the lower index); w =
        s[chosen] / (sum s[chosen] + 1e-20) * route_scale; mlp(x) =
        shared(x) + sum over the chosen experts HELD HERE of w_i·expert_i(x)
        — what the absent experts would add is left out, as in the program
  logits = final_norm(h)·Whead over the vocabulary rows held here (untied,
        no bias)

The numbers are computed layer by layer on the served bfloat16 arrays
(one jitted function a layer kind), attention in blocks of query rows
with the keys sliced to each block's reach, so float32 never holds more
than one layer's weights or one block's scores.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import ops

BYTES = 256       # ids under it are the byte of the same value
ATTN_BLOCK = 512  # query rows an attention block


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * ops.f32(p["scale"])


def rope(x, pos, theta):
    """x[B,H,S,D], pos[S]: the two halves rotated."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mask_pairs(q0: int, q1: int, k0: int, window):
    """How many (query, key) pairs the mask leaves to query rows
    [q0, q1) over key rows [k0, q1)."""
    q = np.arange(q0, q1)[:, None]
    k = np.arange(k0, q1)[None, :]
    ok = k <= q
    if window is not None:
        ok &= k > q - window
    return int(ok.sum())


def attention(q, k, v, window):
    """Causal (and windowed) attention on [B,H,S,D], a block of query
    rows at a time over the keys its mask can reach."""
    s = q.shape[2]
    outs = []
    for q0 in range(0, s, ATTN_BLOCK):
        q1 = min(q0 + ATTN_BLOCK, s)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        qp = jnp.arange(q0, q1)[:, None]
        kp = jnp.arange(k0, q1)[None, :]
        ok = kp <= qp
        if window is not None:
            ok &= kp > qp - window
        mask = jnp.where(ok, 0.0, -jnp.inf)[None, None]
        outs.append(ops.attend(q[:, :, q0:q1], k[:, :, k0:q1],
                               v[:, :, k0:q1], mask=mask,
                               pairs=mask_pairs(q0, q1, k0, window)))
    return jnp.concatenate(outs, axis=2)


def swiglu(x, p):
    return ops.dense(ops.silu(ops.dense(x, p["gate"])) * ops.dense(x, p["up"]),
                     p["down"])


def moe(x, p, cfg):
    """x[B,S,d]: shared expert + the held experts' part of the routed sum."""
    e, k = cfg["num_experts"], cfg["experts_per_token"]
    lo, hi = cfg["experts_held"]
    scores = jax.nn.sigmoid(ops.dense(x, p["router"]))
    _, chosen = jax.lax.top_k(scores + ops.f32(p["expert_bias"]), k)
    sc = jnp.take_along_axis(scores, chosen, axis=-1)
    w = sc / (sc.sum(axis=-1, keepdims=True) + 1e-20) * cfg["route_scale"]
    # [B,S,E]: the weight of every expert for every token, 0 if not chosen
    dense_w = (jax.nn.one_hot(chosen, e, dtype=jnp.float32)
               * w[..., None]).sum(axis=-2)
    y = swiglu(x, p["shared"])
    xo = ops.operand(x)
    for j in range(hi - lo):
        kern = {n: ops.kernel({"kernel": p["experts"][n]["kernel"][j]})
                for n in ("gate", "up", "down")}
        h = ops.silu(jnp.matmul(xo, kern["gate"], precision=ops.HIGHEST)) \
            * jnp.matmul(xo, kern["up"], precision=ops.HIGHEST)
        y = y + dense_w[..., lo + j, None] * jnp.matmul(
            ops.operand(h), kern["down"], precision=ops.HIGHEST)
    # the work of the algorithm: only the tokens sent to experts held
    # here, at the expected load (tokens * k * held / experts)
    tokens = x.shape[0] * x.shape[1]
    ops.count("experts", tokens * k * (hi - lo) / e
              * 3 * ops.dense_flops(1, cfg["hidden"], cfg["expert_ff"]))
    return y


def layer(lp, x, kind, cfg):
    """One block on x[B,S,d]; kind = (mlp kind, attention kind)."""
    mlp_kind, attn_kind = kind
    nh, nkv = cfg["heads"], cfg["kv_heads"]
    eps = cfg["eps"]
    a = lp["attn"]
    h = rms_norm(x, lp["input_norm"], eps)
    q = ops.heads(ops.dense(h, a["q"]), nh)
    k = ops.heads(ops.dense(h, a["k"]), nkv)
    v = ops.heads(ops.dense(h, a["v"]), nkv)
    g = ops.dense(h, a["gate"])
    q, k = rms_norm(q, a["q_norm"], eps), rms_norm(k, a["k_norm"], eps)
    if attn_kind == "sliding":
        pos = jnp.arange(x.shape[1])
        q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos,
                                                      cfg["rope_theta"])
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    o = attention(q, k, v, cfg["window"] if attn_kind == "sliding" else None)
    o = ops.dense(ops.unheads(o) * jax.nn.sigmoid(g), a["o"])
    x = x + rms_norm(o, lp["post_attn_norm"], eps)
    h = rms_norm(x, lp["pre_mlp_norm"], eps)
    y = swiglu(h, lp["mlp"]) if mlp_kind == "dense" else moe(h, lp["moe"],
                                                             cfg)
    return x + rms_norm(y, lp["post_mlp_norm"], eps)


def embed(p, ids, cfg):
    lo, hi = cfg["vocab_rows"]
    local = ids - lo
    mine = (local >= 0) & (local < hi - lo)
    x = ops.f32(p["embedding"])[jnp.clip(local, 0, hi - lo - 1)]
    return jnp.where(mine[..., None], x, 0.0) * np.sqrt(cfg["hidden"])


def head(p, x, cfg):
    return ops.dense(rms_norm(x, p["final_norm"], cfg["eps"]), p["head"])


def forward(p, ids, out_rows, cfg):
    """ids [B,S] -> float32 logits [B,T,V'] of the last T = len(out_rows)
    rows: row s predicts id s+1. (`out_rows` is read for its length
    alone: the head is computed where an id was served.)"""
    x = embed(p["embed"], ids, cfg)
    for i, kind in enumerate(cfg["layers"]):
        x = layer(p[f"layer_{i}"], x, tuple(kind), cfg)
    return head(p, x[:, x.shape[1] - out_rows.shape[0]:], cfg)


def prompt_bucket(arch: dict, prompt: str) -> int:
    need = len(prompt.encode("utf-8")) + 2
    edges = sorted(arch["prompt_buckets"])
    return next((e for e in edges if need <= e), edges[-1])


def decode_bucket(arch: dict, max_new_tokens: int) -> int:
    edges = sorted(arch["decode_buckets"])
    return next((e for e in edges if max_new_tokens <= e), edges[-1])


def parts(arch):
    return {"forward": lambda p, ids, out_rows: forward(p, ids, out_rows,
                                                        arch["model"])}


@functools.lru_cache(maxsize=8)
def _jitted(arch_key, weights):
    cfg = json.loads(arch_key)["model"]
    fns = {"embed": lambda p, ids: embed(p, ids, cfg),
           "head": lambda p, x: head(p, x, cfg)}
    for kind in {tuple(k) for k in cfg["layers"]}:
        fns["layer." + ".".join(kind)] = functools.partial(
            lambda lp, x, kind: layer(lp, x, kind, cfg), kind=kind)
    return {k: jax.jit(ops.traced_with(v, weights)) for k, v in fns.items()}


def logits(params, arch: dict, task: dict, served,
           weights: str | None = None) -> np.ndarray:
    """[T, BYTES] float32: for each of the T served ids, the logits over
    the byte slice at the position that produced it, given the prompt
    (padded to its bucket as the tokenizer pads it) and the served ids
    before it. `weights` ("fp8") computes the control instead."""
    t = arch["tokenizer"]
    cfg = arch["model"]
    p = prompt_bucket(arch, task["prompt"])
    prompt = ops.byte_tokens(task["prompt"], p, t["bos_id"], t["eos_id"])
    served = np.asarray(served, np.int32)
    ids = jnp.asarray(np.concatenate([prompt, served[:-1]])[None])
    fns = _jitted(json.dumps(arch, sort_keys=True), weights)
    x = fns["embed"](params["embed"], ids)
    for i, kind in enumerate(cfg["layers"]):
        x = fns["layer." + ".".join(kind)](params[f"layer_{i}"], x)
    out = fns["head"]({"final_norm": params["final_norm"],
                       "head": params["head"]}, x[:, p - 1:])
    return np.asarray(out)[0, :, :BYTES]


def forward_shapes(arch: dict, task: dict, batch: int = 1):
    """One solution is one pass over its prompt bucket and its decode
    bucket's positions (the work of prefill plus every decode step, each
    counted once: the causal and windowed pairs), the head at the
    positions that serve an id."""
    t = decode_bucket(arch, int(task["max_new_tokens"]))
    s = prompt_bucket(arch, task.get("prompt", "")) + t - 1
    return [("forward", (jax.ShapeDtypeStruct((batch, s), jnp.int32),
                         jax.ShapeDtypeStruct((t,), jnp.int32)), 1)]
