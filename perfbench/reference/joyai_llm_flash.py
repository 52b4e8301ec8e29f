"""Plain float32 reference of JoyAI-LLM-Flash (`joyai_llm_flash`) with
its multi-token prediction module, the share of it that a configuration
states (`experts_held`, `vocab_rows`).

One teacher-forced forward pass over prompt and served ids together: no
cache of any kind, no latent form, no prefill/decode split, no
speculation, no kernel. `jax.numpy` at float32 with matmul precision
"highest" (perfbench/reference/ops.py); imports nothing of the program —
the weight tree is the layout a node loads. Its layers are
DeepSeek-V3.2's without the indexer, so the pieces that are the same to
the letter (RMSNorm, SwiGLU, blocked softmax attention under a mask, the
embedding and the head) are imported from `reference/deepseek_v32.py`;
the rotary positions, the router and the module are written here. The
equations (the configuration file restates them under `assumed`):

  h = embed[ids]
  h = h + attn(attn_norm(h));  h = h + ffn(ffn_norm(h))   (RMSNorm, eps 1e-6)
  query:   c_q = q_norm(x·Wqa); q = c_q·Wqb -> 32 heads x (nope 128 | pe
           64); q_pe rotated: adjacent pairs (2i, 2i+1), angle pos·f_i,
           f_i = 32,000,000^(-2i/64), no scaling
  latent:  x·Wkva -> 512 | 64: c_kv = kv_norm(first 512), k_pe = rope(last
           64), one rotary key for all heads
  keys:    c_kv·Wkvb -> heads x (k_nope | v); k = [k_nope | k_pe]
  attn:    softmax over EVERY key s <= t of (q[t]·k[s])·192^-1/2;
           out = concat(o)·Wo
  dense:   (silu(x·W1) * (x·W3))·W2
  experts: s = sigmoid(x·Wr) over all 256; the 8 chosen = top-8 of s +
           bias (one group: no group limit; ties to the lower id);
           w = s[chosen] / sum s[chosen]·2.5; ffn(x) = shared(x) + sum
           over the chosen experts held here of w_i·expert_i(x)
  logits = final_norm(h)·Whead over the vocabulary rows held here
  module:  at position i, with h_i the main model's state BEFORE
           final_norm and t_{i+1} the next id:
           x_i = Weh·[enorm(embed[t_{i+1}]) ; hnorm(h_i)]; one expert
           layer (the equations above, rotary position i, causal over
           the module's own x); logits_i = norm(x_i')·Whead (the main
           model's head): a guess at id i+2

Computed a sequence at a time and layer by layer on the served bfloat16
arrays (one jitted function a layer kind). A float32 expert layer is
4.96 GB and does not fit beside the served weights, so the experts are
walked in groups of EXPERT_GROUP: each group's kernels are widened,
every token goes through every expert of the group, and the router's
weight (0 where not chosen) picks what counts.

The FLOP count (perfbench/flops.py walks the parts under
`jax.eval_shape`): `forward` is what a solution needs — the main model
over prompt + served ids, once; projections and MLPs through
`ops.dense`, the held experts at the expected load under `other`
"experts", attention's pairs under `other` "attention" (2·heads·(192 +
128) a causal pair). `mtp` is the module over the same positions, all of
it under `other` "mtp", and `forward_shapes` gives it 0 calls: a draft
is work the program chooses to spend, not work a solution needs.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import ops
from perfbench.reference.deepseek_v32 import (
    BYTES,
    HEAD_GROUP,
    _after,
    _coarse,
    _heads_of,
    attention,
    embed,
    head,
    rms_norm,
    swiglu,
)
from perfbench.reference.trinity import decode_bucket, prompt_bucket

EXPERT_GROUP = 16   # experts whose kernels are float32 at once


def rope(x, pos, theta: float):
    """x[S, ..., D] at pos[S]: adjacent pairs (2i, 2i+1) rotated by
    pos·theta^(-2i/D)."""
    dim = x.shape[-1]
    f = theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)
    ang = pos.astype(jnp.float32)[:, None] * f.astype(np.float32)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attend(a, x, cfg):
    """The attention half of a block on one sequence x[S, d] (already
    normed), `a` the layer's attention weights."""
    eps = cfg["eps"]
    s = x.shape[0]
    nh, dn, dr, dv = (cfg["heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    c = cfg["kv_lora_rank"]
    pos = jnp.arange(s)
    c_q = rms_norm(ops.dense(x, a["wq_a"]), a["q_norm"], eps)
    kv = ops.dense(x, a["wkv_a"])
    c_kv = rms_norm(kv[:, :c], a["kv_norm"], eps)
    k_pe = rope(kv[:, c:], pos, cfg["rope_theta"])
    keep = jnp.tril(jnp.ones((s, s), bool))
    ops.count("attention", 2.0 * nh * (dn + dr + dv) * (s * (s + 1) // 2))
    scale = (dn + dr) ** -0.5
    wo = a["wo"]["kernel"].reshape(nh, dv, -1)
    y = None
    for g0 in range(0, nh, HEAD_GROUP):
        g1 = min(g0 + HEAD_GROUP, nh)
        if y is not None:
            c_q, c_kv, k_pe, keep = _after(y, c_q, c_kv, k_pe, keep)
        q = ops.dense(c_q, _heads_of(a["wq_b"], cfg["q_lora_rank"], nh,
                                     g0, g1)).reshape(s, g1 - g0, dn + dr)
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], pos, cfg["rope_theta"])],
            axis=-1)
        kvh = ops.dense(c_kv, _heads_of(a["wkv_b"], c, nh, g0, g1)).reshape(
            s, g1 - g0, dn + dv)
        k = jnp.concatenate(
            [kvh[..., :dn],
             jnp.broadcast_to(k_pe[:, None], (s, g1 - g0, dr))], axis=-1)
        o = attention(q, k, kvh[..., dn:], keep, scale)
        part = ops.dense(o.reshape(s, (g1 - g0) * dv),
                         {"kernel": wo[g0:g1].reshape((g1 - g0) * dv, -1)})
        y = part if y is None else y + part
    return y


def route(x, p, cfg):
    """x[S, d] -> the weight of every expert for every token [S, E], 0
    where not chosen."""
    scores = jax.nn.sigmoid(ops.dense(x, p["router"]))
    _, chosen = jax.lax.top_k(scores + ops.f32(p["expert_bias"]),
                              cfg["experts_per_token"])
    sc = jnp.take_along_axis(scores, chosen, axis=-1)
    w = sc / sc.sum(axis=-1, keepdims=True) * cfg["route_scale"]
    return (jax.nn.one_hot(chosen, cfg["num_experts"], dtype=jnp.float32)
            * w[..., None]).sum(axis=-2)


def moe(x, p, cfg):
    """x[S, d]: shared expert + the held experts' part of the routed
    sum, the experts in groups."""
    lo, hi = cfg["experts_held"]
    held = hi - lo
    group = min(EXPERT_GROUP, held)
    if held % group:
        raise ValueError(f"{held} held experts are no whole groups of "
                         f"{group}")
    w = route(x, p, cfg)[:, lo:hi].T.reshape(held // group, group, -1)
    xo = ops.operand(x)

    def one(wg, gate, up, down):
        kern = [ops.kernel({"kernel": k}) for k in (gate, up, down)]
        h = ops.silu(jnp.matmul(xo, kern[0], precision=ops.HIGHEST)) \
            * jnp.matmul(xo, kern[1], precision=ops.HIGHEST)
        return wg[:, None] * jnp.matmul(ops.operand(h), kern[2],
                                        precision=ops.HIGHEST)

    def add(y, args):
        return y + jax.vmap(one)(*args).sum(axis=0), None

    kernels = [p["experts"][n]["kernel"] for n in ("gate", "up", "down")]
    y, _ = jax.lax.scan(add, swiglu(x, p["shared"]), (
        w, *(k.reshape((held // group, group) + k.shape[1:])
             for k in kernels)))
    # the work of the algorithm: only the tokens sent to experts held
    # here, at the expected load (tokens * k * held / experts)
    ops.count("experts", x.shape[0] * cfg["experts_per_token"] * held
              / cfg["num_experts"]
              * 3 * ops.dense_flops(1, cfg["hidden"], cfg["expert_ff"]))
    return y


def layer(lp, x, kind, cfg):
    """One block on one sequence x[S, d]; kind = "dense" | "moe"."""
    x = x + attend(lp["attn"], rms_norm(x, lp["attn_norm"], cfg["eps"]),
                   cfg)
    h = rms_norm(x, lp["ffn_norm"], cfg["eps"])
    if kind == "moe":
        return x + moe(h, lp["moe"], cfg)
    out = []
    for q0, q1 in _coarse(x.shape[0]):
        if out:
            (h,) = _after(out[-1], h)
        out.append(swiglu(h[q0:q1], lp["mlp"]))
    return x + jnp.concatenate(out)


def hidden(p, ids, cfg):
    """ids[S] -> the main model's states [S, d] before final_norm."""
    x = embed(p["embed"], ids, cfg)
    for i, kind in enumerate(cfg["layers"]):
        x = layer(p[f"layer_{i}"], x, kind, cfg)
    return x


def mtp_in(p, nxt, h, cfg):
    """The module's input at positions whose next ids are nxt[S] and
    whose main states are h[S, d]: Weh·[enorm(embed) ; hnorm(h)]."""
    mp = p["mtp"]
    both = jnp.concatenate(
        [rms_norm(embed(p["embed"], nxt, cfg), mp["enorm"], cfg["eps"]),
         rms_norm(h, mp["hnorm"], cfg["eps"])], axis=-1)
    return ops.dense(both, mp["eh_proj"])


def mtp_head(p, x, cfg):
    return head({"final_norm": p["mtp"]["norm"], "head": p["head"]}, x, cfg)


def forward(p, ids, out_rows, cfg):
    """ids [B,S] -> float32 logits [B,T,V'] of the last T = len(out_rows)
    rows: row s predicts id s+1. A sequence at a time."""
    outs = []
    for row in ids:
        x = hidden(p, row, cfg)
        outs.append(head(p, x[x.shape[0] - out_rows.shape[0]:], cfg))
    return jnp.stack(outs)


def mtp_forward(p, ids, h, cfg):
    """The module over ids[B,S] given the main states h[B,S,d]: logits
    [B,S-1,V'], row i a guess at id i+2 — counted whole under `other`
    "mtp"."""
    with ops.count_flops() as inner:
        out = jnp.stack([
            mtp_head(p, layer(p["mtp"]["layer"],
                              mtp_in(p, row[1:], hs[:-1], cfg), "moe", cfg),
                     cfg)
            for row, hs in zip(ids, h)])
    ops.count("mtp", inner.total)
    return out


def parts(arch):
    cfg = arch["model"]
    return {"forward": lambda p, ids, out_rows: forward(p, ids, out_rows,
                                                        cfg),
            "mtp": lambda p, ids, h: mtp_forward(p, ids, h, cfg)}


@functools.lru_cache(maxsize=8)
def _jitted(arch_key, weights):
    cfg = json.loads(arch_key)["model"]
    fns = {"embed": lambda p, ids: embed(p, ids, cfg),
           "head": lambda p, x: head(p, x, cfg),
           "mtp_in": lambda p, nxt, h: mtp_in(p, nxt, h, cfg),
           "mtp_head": lambda p, x: mtp_head(p, x, cfg)}
    for kind in set(cfg["layers"]) | {"moe"}:
        fns["layer." + kind] = functools.partial(
            lambda lp, x, kind: layer(lp, x, kind, cfg), kind=kind)
    return {k: jax.jit(ops.traced_with(v, weights)) for k, v in fns.items()}


def _small(params):
    """The leaves the module's input and head read, without the layers."""
    return {"embed": params["embed"], "head": params["head"],
            "mtp": {k: v for k, v in params["mtp"].items() if k != "layer"}}


def both_logits(params, arch: dict, task: dict, served,
                weights: str | None = None, module: bool = True):
    """(main [T, BYTES], module [T-1, BYTES]) float32, teacher-forced on
    the prompt (padded to its bucket as the tokenizer pads it) and the
    served ids. Main row n: the logits at the position that produced
    served id n. Module row n-1, n = 1 .. T-1: the module's logits where
    the program reads its draft of served id n — position P+n-2, given
    the main state there and the id after it. `weights` ("fp8") computes
    the control instead; `module` False leaves the module out (None)."""
    t = arch["tokenizer"]
    cfg = arch["model"]
    p = prompt_bucket(arch, task["prompt"])
    prompt = ops.byte_tokens(task["prompt"], p, t["bos_id"], t["eos_id"])
    served = np.asarray(served, np.int32)
    ids = jnp.asarray(np.concatenate([prompt, served[:-1]]))
    fns = _jitted(json.dumps(arch, sort_keys=True), weights)
    x = fns["embed"](params["embed"], ids)
    for i, kind in enumerate(cfg["layers"]):
        x = fns["layer." + kind](params[f"layer_{i}"], x)
    main = fns["head"]({"final_norm": params["final_norm"],
                        "head": params["head"]}, x[p - 1:])
    main = np.asarray(main)[:, :BYTES]
    if not module or len(served) < 2:
        return main, None
    small = _small(params)
    xm = fns["mtp_in"](small, ids[1:], x[:-1])
    xm = fns["layer.moe"](params["mtp"]["layer"], xm)
    guess = fns["mtp_head"](small, xm[p - 1:])
    return main, np.asarray(guess)[:, :BYTES]


def logits(params, arch: dict, task: dict, served,
           weights: str | None = None) -> np.ndarray:
    """[T, BYTES] float32: the main model's half of `both_logits`."""
    return both_logits(params, arch, task, served, weights,
                       module=False)[0]


def forward_shapes(arch: dict, task: dict, batch: int = 1):
    """One solution is one pass of the main model over its prompt bucket
    and its decode bucket's positions, the head at the positions that
    serve an id; the module's pass over the same positions is listed
    with 0 calls."""
    t = decode_bucket(arch, int(task["max_new_tokens"]))
    s = prompt_bucket(arch, task.get("prompt", "")) + t - 1
    sds = jax.ShapeDtypeStruct
    ids = sds((batch, s), jnp.int32)
    return [("forward", (ids, sds((t,), jnp.int32)), 1),
            ("mtp", (ids, sds((batch, s, arch["model"]["hidden"]),
                              jnp.float32)), 0)]
