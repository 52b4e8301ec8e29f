"""Plain float32 reference of DeepSeek-V3.2-Exp (`deepseek_v32`), the
share of it that a configuration states (`experts_held`, `vocab_rows`).

One teacher-forced forward pass over prompt and served ids together:
no cache of any kind, no latent form, no prefill/decode split, no kernel.
`jax.numpy` at float32 with matmul precision "highest"
(perfbench/reference/ops.py); imports nothing of the program — the
weight tree is the layout a node loads. The equations, as the published
`inference/model.py` computes them (the configuration file restates
them, and the departures, under `assumed`):

  h = embed[ids]
  h = h + attn(attn_norm(h));  h = h + ffn(ffn_norm(h))   (RMSNorm, eps 1e-6)
  query:   c_q = q_norm(x·Wqa); q = c_q·Wqb -> heads x (nope | pe); q_pe
           rotated (adjacent pairs, YaRN frequencies)
  latent:  x·Wkva -> 512 | 64: c_kv = kv_norm(first 512), k_pe = rope(last
           64), one rotary key for all heads
  keys:    c_kv·Wkvb -> heads x (k_nope | v); k = [k_nope | k_pe]   (the
           per-head form at EVERY position)
  indexer: q_i = c_q·Wiq -> 64 x 128, k_i = LayerNorm(x·Wik) (gain and
           bias), the first 64 dims of each rotated (the two halves);
           w = (x·Wiw)·64^-1/2·128^-1/2;
           I[t, s] = sum_j w[t, j]·relu(q_i[t, j]·k_i[s]) for s <= t;
           S(t) = the positions of the min(index_topk, t + 1) largest
           I[t, .] (`lax.top_k`: ties to the lower position)
  attn:    softmax over S(t) of (q[t]·k[s])·scale, scale = 192^-1/2·m^2,
           m = 0.1·mscale_all_dim·ln(factor) + 1; out = concat(o)·Wo
  dense:   (silu(x·W1) * (x·W3))·W2
  experts: s = sigmoid(x·Wr) over ALL experts; b = s + bias; a group of 32
           consecutive experts scores the sum of its two largest b; the 4
           best groups are kept (ties to the lower group); the 8 chosen =
           top-8 of b inside them (ties to the lower id); w = s[chosen] /
           sum s[chosen]·2.5; ffn(x) = shared(x) + sum over the chosen
           experts HELD HERE of w_i·expert_i(x) — what the absent experts
           would add is left out, as in the program
  logits = final_norm(h)·Whead over the vocabulary rows held here

Computed a sequence at a time and layer by layer on the served bfloat16
arrays (one jitted function a layer kind). Inside a layer the heads go
in groups (each through its own rows of Wo: the sum is concat(o)·Wo),
and index scores and attention in blocks of query rows over the keys a
coarser block's rows can reach (`lax.map`: rows are independent), one
after another, so float32 never holds a sequence's per-head keys for
all heads nor more than one block's scores.

The FLOP count (perfbench/flops.py walks `forward` under
`jax.eval_shape`): projections and MLPs through `ops.dense`; the held
experts at the expected load, as the Trinity reference counts them;
and, by name, what `ops.attend` cannot state — it fixes the scale at
D^-1/2 and counts the value product at the query's width — under
`other`: "attention" = 2·heads·(192 + 128) a (query, key) pair THE
SELECTION LEAVES, "indexer" = 2·64·128 a causal pair.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import ops
from perfbench.reference.trinity import decode_bucket, prompt_bucket

BYTES = 256        # ids under it are the byte of the same value
ROW_BLOCK = 128    # query rows an index-score or attention block
COARSE = 16        # ... and how many of them share one slice of the keys
HEAD_GROUP = 16    # heads whose keys and values are expanded at once


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * ops.f32(p["scale"])


def yarn_freqs(cfg) -> np.ndarray:
    dim = cfg["qk_rope_head_dim"]
    i = np.arange(dim // 2, dtype=np.float64)
    f = cfg["rope_theta"] ** (-2.0 * i / dim)

    def corr(turns):
        return dim * math.log(cfg["rope_original"] / (2 * math.pi * turns)) \
            / (2 * math.log(cfg["rope_theta"]))

    low = max(math.floor(corr(cfg["beta_fast"])), 0)
    high = min(math.ceil(corr(cfg["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / cfg["rope_factor"] * ramp + f * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg) -> float:
    m = 0.1 * cfg["mscale_all_dim"] * math.log(cfg["rope_factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _cos_sin(x, pos, cfg):
    """cos and sin of pos[S] x the YaRN frequencies, shaped to broadcast
    against x[S, ..., D/2]."""
    ang = pos.astype(jnp.float32)[:, None] * yarn_freqs(cfg)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    return jnp.cos(ang), jnp.sin(ang)


def rope_pairs(x, pos, cfg):
    """x[S, ..., D] at pos[S]: adjacent pairs (2i, 2i+1) rotated."""
    cos, sin = _cos_sin(x, pos, cfg)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rope_halves(x, pos, cfg):
    """The same angles, the two halves rotated."""
    cos, sin = _cos_sin(x, pos, cfg)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def swiglu(x, p):
    return ops.dense(ops.silu(ops.dense(x, p["gate"])) * ops.dense(x, p["up"]),
                     p["down"])


def kept_pairs(n: int, k: int) -> tuple[int, int]:
    """((query, key) pairs the selection leaves of n positions, pairs the
    causal mask leaves): a query at position t keeps min(t + 1, k)."""
    causal = n * (n + 1) // 2
    return (causal if n <= k else k * (k + 1) // 2 + (n - k) * k), causal


def _row_blocks(fn, rows: int, *row_wise):
    """`fn(first row, *blocks)` over blocks of ROW_BLOCK rows of the
    row-wise arrays (padded to whole blocks; rows are independent),
    stacked back to `rows` rows."""
    n = -(-rows // ROW_BLOCK)
    pad = n * ROW_BLOCK - rows
    blocks = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (n, ROW_BLOCK) + a.shape[1:]) for a in row_wise]
    out = jax.lax.map(lambda args: fn(args[0], *args[1:]),
                      (jnp.arange(n) * ROW_BLOCK, *blocks))
    return out.reshape((n * ROW_BLOCK,) + out.shape[2:])[:rows]


def _coarse(s: int):
    """[(q0, q1)]: coarse blocks of query rows; the rows of one reach
    the keys [0, q1)."""
    step = ROW_BLOCK * COARSE
    return [(q0, min(q0 + step, s)) for q0 in range(0, s, step)]


def _after(done, *arrays):
    """`arrays`, held back until `done` is computed: XLA would else
    start every block and head group at once, and their temporaries
    would all sit in memory together."""
    return jax.lax.optimization_barrier((done, *arrays))[1:]


def selection(q_i, w, k_i, topk: int):
    """keep[S, S] bool: row t holds S(t). q_i[S, Hi, Di], w[S, Hi],
    k_i[S, Di]."""
    s = q_i.shape[0]
    out = []
    for q0, q1 in _coarse(s):
        if out:
            q_i, w, k_i = _after(out[-1], q_i, w, k_i)
        keys = k_i[:q1]
        k = min(topk, q1)

        def block(r0, qb, wb, q0=q0, keys=keys, k=k, q1=q1):
            dots = jnp.einsum("thd,sd->ths", qb, keys, precision=ops.HIGHEST)
            index = (jax.nn.relu(dots) * wb[..., None]).sum(axis=1)
            t = q0 + r0 + jnp.arange(ROW_BLOCK)[:, None]
            causal = jnp.arange(q1)[None, :] <= t
            _, idx = jax.lax.top_k(jnp.where(causal, index, -jnp.inf), k)
            keep = jnp.zeros((ROW_BLOCK, q1), bool).at[
                jnp.arange(ROW_BLOCK)[:, None], idx].set(True)
            return jnp.pad(keep & causal, ((0, 0), (0, s - q1)))

        out.append(_row_blocks(block, q1 - q0, q_i[q0:q1], w[q0:q1]))
    return jnp.concatenate(out)


def attention(q, k, v, keep, scale: float):
    """Softmax attention of q[S, H, Dq] over k[S, H, Dq], v[S, H, Dv]
    under keep[S, S]."""
    s = q.shape[0]
    out = []
    for q0, q1 in _coarse(s):
        if out:
            q, k, v = _after(out[-1], q, k, v)
        kb, vb = k[:q1], v[:q1]

        def block(_r0, qb, mb, kb=kb, vb=vb, q1=q1):
            sc = jnp.einsum("qhd,khd->hqk", qb, kb,
                            precision=ops.HIGHEST) * scale
            sc = jnp.where(mb[None, :, :q1], sc, -jnp.inf)
            # a padded row keeps nothing: give it a finite row to soften
            sc = jnp.where(mb.any(axis=-1)[None, :, None], sc, 0.0)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1),
                              vb, precision=ops.HIGHEST)

        out.append(_row_blocks(block, q1 - q0, q[q0:q1], keep[q0:q1]))
    return jnp.concatenate(out)


def _heads_of(p, rows: int, heads: int, g0: int, g1: int):
    """The kernel's columns of heads [g0, g1), as a dense layer."""
    w = p["kernel"].reshape(rows, heads, -1)[:, g0:g1]
    return {"kernel": w.reshape(rows, -1)}


def attend(lp, x, cfg):
    """The attention half of a block on one sequence x[S, d]."""
    a, ix = lp["attn"], lp["indexer"]
    eps = cfg["eps"]
    s = x.shape[0]
    nh, dn, dr, dv = (cfg["heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    c = cfg["kv_lora_rank"]
    pos = jnp.arange(s)
    c_q = rms_norm(ops.dense(x, a["wq_a"]), a["q_norm"], eps)
    kv = ops.dense(x, a["wkv_a"])
    c_kv = rms_norm(kv[:, :c], a["kv_norm"], eps)
    k_pe = rope_pairs(kv[:, c:], pos, cfg)

    hi, di = cfg["index_heads"], cfg["index_head_dim"]
    kept, causal = kept_pairs(s, cfg["index_topk"])
    if cfg["index_topk"] < s:
        q_i = ops.dense(c_q, ix["wq_b"]).reshape(s, hi, di)
        q_i = jnp.concatenate([rope_halves(q_i[..., :dr], pos, cfg),
                               q_i[..., dr:]], axis=-1)
        k_i = ops.layer_norm(ops.dense(x, ix["wk"]), ix["k_norm"], eps=eps)
        k_i = jnp.concatenate([rope_halves(k_i[:, :dr], pos, cfg),
                               k_i[:, dr:]], axis=-1)
        w = ops.dense(x, ix["weights_proj"]) * (hi ** -0.5 * di ** -0.5)
        keep = selection(q_i, w, k_i, cfg["index_topk"])
        ops.count("indexer", 2.0 * hi * di * causal)
    else:
        keep = jnp.tril(jnp.ones((s, s), bool))
    ops.count("attention", 2.0 * nh * (dn + dr + dv) * kept)

    scale = softmax_scale(cfg)
    wo = a["wo"]["kernel"].reshape(nh, dv, -1)
    y = None
    for g0 in range(0, nh, HEAD_GROUP):
        g1 = min(g0 + HEAD_GROUP, nh)
        if y is not None:
            c_q, c_kv, k_pe, keep = _after(y, c_q, c_kv, k_pe, keep)
        q = ops.dense(c_q, _heads_of(a["wq_b"], cfg["q_lora_rank"], nh,
                                     g0, g1)).reshape(s, g1 - g0, dn + dr)
        q = jnp.concatenate([q[..., :dn],
                             rope_pairs(q[..., dn:], pos, cfg)], axis=-1)
        kvh = ops.dense(c_kv, _heads_of(a["wkv_b"], c, nh, g0, g1)).reshape(
            s, g1 - g0, dn + dv)
        k = jnp.concatenate(
            [kvh[..., :dn],
             jnp.broadcast_to(k_pe[:, None], (s, g1 - g0, dr))], axis=-1)
        o = attention(q, k, kvh[..., dn:], keep, scale)
        # the group's rows of Wo: the sum over groups is concat(o)·Wo
        part = ops.dense(o.reshape(s, (g1 - g0) * dv),
                         {"kernel": wo[g0:g1].reshape((g1 - g0) * dv, -1)})
        y = part if y is None else y + part
    return y


def route(x, p, cfg):
    """x[S, d] -> the weight of every expert for every token [S, E], 0
    where not chosen."""
    e, k = cfg["num_experts"], cfg["experts_per_token"]
    ng, per = cfg["n_group"], cfg["num_experts"] // cfg["n_group"]
    scores = jax.nn.sigmoid(ops.dense(x, p["router"]))
    b = scores + ops.f32(p["expert_bias"])
    two, _ = jax.lax.top_k(b.reshape(-1, ng, per), 2)
    _, groups = jax.lax.top_k(two.sum(axis=-1), cfg["topk_group"])
    kept = jax.nn.one_hot(groups, ng, dtype=jnp.float32).sum(axis=1) > 0
    kept = jnp.repeat(kept, per, axis=-1)
    _, chosen = jax.lax.top_k(jnp.where(kept, b, -jnp.inf), k)
    sc = jnp.take_along_axis(scores, chosen, axis=-1)
    w = sc / sc.sum(axis=-1, keepdims=True) * cfg["route_scale"]
    return (jax.nn.one_hot(chosen, e, dtype=jnp.float32)
            * w[..., None]).sum(axis=-2)


def moe(x, p, cfg):
    """x[S, d]: shared expert + the held experts' part of the routed sum."""
    lo, hi = cfg["experts_held"]
    dense_w = route(x, p, cfg)
    y = swiglu(x, p["shared"])
    for j in range(hi - lo):
        (xo,) = _after(y, ops.operand(x))
        kern = {n: ops.kernel({"kernel": p["experts"][n]["kernel"][j]})
                for n in ("gate", "up", "down")}
        h = ops.silu(jnp.matmul(xo, kern["gate"], precision=ops.HIGHEST)) \
            * jnp.matmul(xo, kern["up"], precision=ops.HIGHEST)
        y = y + dense_w[:, lo + j, None] * jnp.matmul(
            ops.operand(h), kern["down"], precision=ops.HIGHEST)
    # the work of the algorithm: only the tokens sent to experts held
    # here, at the expected load (tokens * k * held / experts)
    ops.count("experts", x.shape[0] * cfg["experts_per_token"] * (hi - lo)
              / cfg["num_experts"]
              * 3 * ops.dense_flops(1, cfg["hidden"], cfg["expert_ff"]))
    return y


def layer(lp, x, kind, cfg):
    """One block on one sequence x[S, d]; kind = "dense" | "moe"."""
    x = x + attend(lp, rms_norm(x, lp["attn_norm"], cfg["eps"]), cfg)
    h = rms_norm(x, lp["ffn_norm"], cfg["eps"])
    if kind == "moe":
        return x + moe(h, lp["moe"], cfg)
    # rows are independent: a coarse block at a time, so the 18,432-wide
    # products of a whole sequence never exist at once
    out = []
    for q0, q1 in _coarse(x.shape[0]):
        if out:
            (h,) = _after(out[-1], h)
        out.append(swiglu(h[q0:q1], lp["mlp"]))
    return x + jnp.concatenate(out)


def embed(p, ids, cfg):
    lo, hi = cfg["vocab_rows"]
    local = ids - lo
    mine = (local >= 0) & (local < hi - lo)
    x = ops.f32(p["embedding"])[jnp.clip(local, 0, hi - lo - 1)]
    return jnp.where(mine[..., None], x, 0.0)


def head(p, x, cfg):
    return ops.dense(rms_norm(x, p["final_norm"], cfg["eps"]), p["head"])


def forward(p, ids, out_rows, cfg):
    """ids [B,S] -> float32 logits [B,T,V'] of the last T = len(out_rows)
    rows: row s predicts id s+1. A sequence at a time. (`out_rows` is
    read for its length alone: the head is computed where an id was
    served.)"""
    outs = []
    for row in ids:
        x = embed(p["embed"], row, cfg)
        for i, kind in enumerate(cfg["layers"]):
            x = layer(p[f"layer_{i}"], x, kind, cfg)
        outs.append(head(p, x[x.shape[0] - out_rows.shape[0]:], cfg))
    return jnp.stack(outs)


def parts(arch):
    return {"forward": lambda p, ids, out_rows: forward(p, ids, out_rows,
                                                        arch["model"])}


@functools.lru_cache(maxsize=8)
def _jitted(arch_key, weights):
    cfg = json.loads(arch_key)["model"]
    fns = {"embed": lambda p, ids: embed(p, ids, cfg),
           "head": lambda p, x: head(p, x, cfg)}
    for kind in set(cfg["layers"]):
        fns["layer." + kind] = functools.partial(
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
    ids = jnp.asarray(np.concatenate([prompt, served[:-1]]))
    fns = _jitted(json.dumps(arch, sort_keys=True), weights)
    x = fns["embed"](params["embed"], ids)
    for i, kind in enumerate(cfg["layers"]):
        x = fns["layer." + kind](params[f"layer_{i}"], x)
    out = fns["head"]({"final_norm": params["final_norm"],
                       "head": params["head"]}, x[p - 1:])
    return np.asarray(out)[:, :BYTES]


def forward_shapes(arch: dict, task: dict, batch: int = 1):
    """One solution is one pass over its prompt bucket and its decode
    bucket's positions (the work of prefill plus every decode step, each
    counted once: the pairs the selection leaves), the head at the
    positions that serve an id."""
    t = decode_bucket(arch, int(task["max_new_tokens"]))
    s = prompt_bucket(arch, task.get("prompt", "")) + t - 1
    return [("forward", (jax.ShapeDtypeStruct((batch, s), jnp.int32),
                         jax.ShapeDtypeStruct((t,), jnp.int32)), 1)]
