"""Plain float32 reference of dots3-note-prev (`dots3_note`), the share of
it that a configuration states (`experts_held`, `vocab_rows`).

One teacher-forced forward pass over prompt and served ids together: no
cache of any kind, no latent form, no ring, no prefill/decode split, no
kernel, no batching. `jax.numpy` at float32 with matmul precision
"highest" (perfbench/reference/ops.py); imports nothing of the program —
the weight tree is the layout a node loads. Where the mathematics is
DeepSeek-V3.2's it is deepseek_v32's reference (the norms, the rotary
pairs, the indexer's selection, attention under a mask, the router, the
held experts, the embedding and the head), imported, not copied. The
equations (the configuration file restates them, and the departures,
under `assumed`):

  h = embed[ids]
  h = h + attn_k(attn_norm(h));  h = h + ffn(ffn_norm(h))   (RMSNorm, eps 1e-5)
  attn of a layer kind k — full: 128 heads, latent 512, q/k heads 128 + 64,
  rotary theta 8e7, the indexer; sliding: 64 heads, latent 1024, q/k heads
  192 + 64, theta 5e4, the window of 513 — with x the normed input:
    query:   c_q = q_norm(x.Wqa); q = c_q.Wqb . (d / q_lora_rank)^1/2 ->
             heads x (nope | pe); q_pe rotated (adjacent pairs, plain theta)
    latent:  x.Wkva -> c | 64: c_kv = kv_norm(first c) . (d / c)^1/2,
             k_pe = rope(last 64), one rotary key for all heads
    keys:    c_kv.Wkvb -> heads x (k_nope | v); k = [k_nope | k_pe]
    keep:    full: S(t), deepseek_v32's indexer on UNSCALED inputs (c_q, x),
             the min(2048, t + 1) best positions; sliding: t - 513 < s <= t
    attn:    softmax over keep of (q[t].k[s]) . (nope + pe)^-1/2;
             o_h = o_h . sigmoid(x.Wg)_h (headwise gate); out = concat(o).Wo
  dense:   (silu(x.W1) * (x.W3)).W2, width 13,824
  experts: s = sigmoid(x.Wr) over ALL 256; the 8 chosen = top-8 of s + bias
           (one group: no group limit); w = s[chosen] / sum s[chosen];
           ffn(x) = shared(x) + sum over the chosen experts HELD HERE of
           w_i.expert_i(x) — what the absent experts would add is left out
  logits = final_norm(h).Whead over the vocabulary rows held here

Computed a sequence at a time and layer by layer on the served bfloat16
arrays (one jitted function a layer kind), in blocks as deepseek_v32's
reference computes them; the band in blocks of query rows over the keys
the band reaches from the block's first row.

The FLOP count (perfbench/flops.py walks `forward` under `jax.eval_shape`):
projections, gates and MLPs through `ops.dense`; the held experts at the
expected load; and, by name, under `other`: "attention" = 2.heads.(192 +
128) a (query, key) pair the SELECTION leaves in a full layer, "indexer"
= 2.64.128 a causal pair, "window_attention" = 2.heads.(256 + 128) a
pair the BAND leaves in a sliding layer.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import deepseek_v32 as dsv32
from perfbench.reference import ops
from perfbench.reference.trinity import decode_bucket, prompt_bucket

BYTES = 256        # ids under it are the byte of the same value
HEAD_GROUP = 16    # heads whose keys and values are expanded at once

# what the program's banded kernel serves: prefill's rows from this
# many prompt positions on the TPU (`ops/selected_flash.py`)
KERNEL_MIN_ROWS = 2048


def kind(cfg: dict, attn: str) -> dict:
    """A layer kind's attention shape, under deepseek_v32's reference's
    names, with its rescale constants, window and plain rotary."""
    pre = "" if attn == "full" else "swa_"
    out = {"heads": cfg[pre + "heads"],
           "q_lora_rank": cfg[pre + "q_lora_rank"],
           "kv_lora_rank": cfg[pre + "kv_lora_rank"],
           "qk_nope_head_dim": cfg[pre + "qk_nope_head_dim"],
           "qk_rope_head_dim": cfg[pre + "qk_rope_head_dim"],
           "v_head_dim": cfg[pre + "v_head_dim"],
           "rope_theta": cfg[pre + "rope_theta"],
           "window": None if attn == "full" else cfg["window"],
           # rope_scaling null: the YaRN table at factor 1 is theta^(-2i/d)
           "rope_factor": 1.0, "rope_original": 4096, "beta_fast": 32.0,
           "beta_slow": 1.0, "mscale_all_dim": 1.0, "eps": cfg["eps"]}
    out["q_scale"] = math.sqrt(cfg["hidden"] / out["q_lora_rank"])
    out["kv_scale"] = math.sqrt(cfg["hidden"] / out["kv_lora_rank"])
    return out


def router(cfg: dict) -> dict:
    """The expert layer's config under deepseek_v32's reference's names:
    one routing group, so its group limit keeps every expert."""
    return {**cfg, "n_group": 1, "topk_group": 1}


def band_pairs(n: int, window: int) -> int:
    """(query, key) pairs the band leaves of n positions: a query at
    position t keeps min(t + 1, window)."""
    return dsv32.kept_pairs(n, window)[0]


def window_work(heads: int, p: int, window: int, dn: int, dr: int, dv: int,
                itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one sequence's banded attention at p positions,
    the least the chip could do: 2 . heads . (dn + dr + dv) a (query,
    key) pair the band leaves; the query [p, heads, dn + dr], the
    expanded K-nope | V [p, heads, dn + dv], the one rotary key [p, dr]
    and the output [p, heads, dv], each read or written once."""
    flops = 2.0 * heads * (dn + dr + dv) * band_pairs(p, window)
    return flops, float(itemsize) * p * (
        heads * (dn + dr) + heads * (dn + dv) + dr + heads * dv)


def window_attention(q, k, v, window: int, scale: float):
    """Softmax attention of q[S, H, Dq] over k[S, H, Dq], v[S, H, Dv],
    each query over the last `window` keys, its own included: a coarse
    block of query rows at a time over the keys its first row reaches."""
    s = q.shape[0]
    out = []
    for q0, q1 in dsv32._coarse(s):
        if out:
            q, k, v = dsv32._after(out[-1], q, k, v)
        k0 = max(0, q0 - window + 1)
        kb, vb = k[k0:q1], v[k0:q1]

        def block(r0, qb, kb=kb, vb=vb, q0=q0, k0=k0):
            t = q0 + r0 + jnp.arange(dsv32.ROW_BLOCK)[:, None]
            pos = k0 + jnp.arange(kb.shape[0])[None, :]
            band = (pos <= t) & (pos > t - window)
            sc = jnp.einsum("qhd,khd->hqk", qb, kb,
                            precision=ops.HIGHEST) * scale
            sc = jnp.where(band[None], sc, -jnp.inf)
            # a padded row past the keys may reach none: a finite row
            sc = jnp.where(band.any(axis=-1)[None, :, None], sc, 0.0)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1),
                              vb, precision=ops.HIGHEST)

        out.append(dsv32._row_blocks(block, q1 - q0, q[q0:q1]))
    return jnp.concatenate(out)


def attend(lp, x, attn: str, cfg: dict):
    """The attention half of a block of kind `attn` on one sequence x[S, d]
    (already normed)."""
    a = lp["attn"]
    k_ = kind(cfg, attn)
    eps = cfg["eps"]
    s = x.shape[0]
    nh, dn, dr, dv = (k_["heads"], k_["qk_nope_head_dim"],
                      k_["qk_rope_head_dim"], k_["v_head_dim"])
    c = k_["kv_lora_rank"]
    pos = jnp.arange(s)
    c_q = dsv32.rms_norm(ops.dense(x, a["wq_a"]), a["q_norm"], eps)
    kv = ops.dense(x, a["wkv_a"])
    c_kv = dsv32.rms_norm(kv[:, :c], a["kv_norm"], eps) * k_["kv_scale"]
    k_pe = dsv32.rope_pairs(kv[:, c:], pos, k_)
    gate = jax.nn.sigmoid(ops.dense(x, a["gate"]))            # [S, H]

    if attn == "full":
        ix = lp["indexer"]
        hi, di = cfg["index_heads"], cfg["index_head_dim"]
        kept, causal = dsv32.kept_pairs(s, cfg["index_topk"])
        if cfg["index_topk"] < s:
            q_i = ops.dense(c_q, ix["wq_b"]).reshape(s, hi, di)
            q_i = jnp.concatenate([dsv32.rope_halves(q_i[..., :dr], pos, k_),
                                   q_i[..., dr:]], axis=-1)
            k_i = ops.layer_norm(ops.dense(x, ix["wk"]), ix["k_norm"],
                                 eps=eps)
            k_i = jnp.concatenate([dsv32.rope_halves(k_i[:, :dr], pos, k_),
                                   k_i[:, dr:]], axis=-1)
            w = ops.dense(x, ix["weights_proj"]) * (hi ** -0.5 * di ** -0.5)
            keep = dsv32.selection(q_i, w, k_i, cfg["index_topk"])
            ops.count("indexer", 2.0 * hi * di * causal)
        else:
            keep = jnp.tril(jnp.ones((s, s), bool))
        ops.count("attention", 2.0 * nh * (dn + dr + dv) * kept)
    else:
        ops.count("window_attention", 2.0 * nh * (dn + dr + dv)
                  * band_pairs(s, k_["window"]))

    scale = (dn + dr) ** -0.5
    wo = a["wo"]["kernel"].reshape(nh, dv, -1)
    y = None
    for g0 in range(0, nh, HEAD_GROUP):
        g1 = min(g0 + HEAD_GROUP, nh)
        if y is not None:
            c_q, c_kv, k_pe, gate = dsv32._after(y, c_q, c_kv, k_pe, gate)
        q = ops.dense(c_q, dsv32._heads_of(a["wq_b"], k_["q_lora_rank"], nh,
                                           g0, g1)).reshape(
            s, g1 - g0, dn + dr) * k_["q_scale"]
        q = jnp.concatenate([q[..., :dn],
                             dsv32.rope_pairs(q[..., dn:], pos, k_)], axis=-1)
        kvh = ops.dense(c_kv, dsv32._heads_of(a["wkv_b"], c, nh, g0,
                                              g1)).reshape(s, g1 - g0,
                                                           dn + dv)
        k = jnp.concatenate(
            [kvh[..., :dn],
             jnp.broadcast_to(k_pe[:, None], (s, g1 - g0, dr))], axis=-1)
        if attn == "full":
            o = dsv32.attention(q, k, kvh[..., dn:], keep, scale)
        else:
            o = window_attention(q, k, kvh[..., dn:], k_["window"], scale)
        o = o * gate[:, g0:g1, None]
        # the group's rows of Wo: the sum over groups is concat(o).Wo
        part = ops.dense(o.reshape(s, (g1 - g0) * dv),
                         {"kernel": wo[g0:g1].reshape((g1 - g0) * dv, -1)})
        y = part if y is None else y + part
    return y


def layer(lp, x, mlp: str, attn: str, cfg):
    """One block on one sequence x[S, d]; mlp = "dense" | "moe", attn =
    "full" | "sliding"."""
    x = x + attend(lp, dsv32.rms_norm(x, lp["attn_norm"], cfg["eps"]), attn,
                   cfg)
    h = dsv32.rms_norm(x, lp["ffn_norm"], cfg["eps"])
    if mlp == "moe":
        return x + dsv32.moe(h, lp["moe"], router(cfg))
    out = []
    for q0, q1 in dsv32._coarse(x.shape[0]):
        if out:
            (h,) = dsv32._after(out[-1], h)
        out.append(dsv32.swiglu(h[q0:q1], lp["mlp"]))
    return x + jnp.concatenate(out)


def forward(p, ids, out_rows, cfg):
    """ids [B,S] -> float32 logits [B,T,V'] of the last T = len(out_rows)
    rows: row s predicts id s+1. A sequence at a time."""
    outs = []
    for row in ids:
        x = dsv32.embed(p["embed"], row, cfg)
        for i, (mlp, attn) in enumerate(cfg["layers"]):
            x = layer(p[f"layer_{i}"], x, mlp, attn, cfg)
        outs.append(dsv32.head(p, x[x.shape[0] - out_rows.shape[0]:], cfg))
    return jnp.stack(outs)


def parts(arch):
    return {"forward": lambda p, ids, out_rows: forward(p, ids, out_rows,
                                                        arch["model"])}


@functools.lru_cache(maxsize=8)
def _jitted(arch_key, weights):
    cfg = json.loads(arch_key)["model"]
    fns = {"embed": lambda p, ids: dsv32.embed(p, ids, cfg),
           "head": lambda p, x: dsv32.head(p, x, cfg)}
    for mlp, attn in {tuple(k) for k in cfg["layers"]}:
        fns[f"layer.{mlp}.{attn}"] = functools.partial(
            lambda lp, x, mlp, attn: layer(lp, x, mlp, attn, cfg),
            mlp=mlp, attn=attn)
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
    for i, (mlp, attn) in enumerate(cfg["layers"]):
        x = fns[f"layer.{mlp}.{attn}"](params[f"layer_{i}"], x)
    out = fns["head"]({"final_norm": params["final_norm"],
                       "head": params["head"]}, x[p - 1:])
    return np.asarray(out)[:, :BYTES]


def forward_shapes(arch: dict, task: dict, batch: int = 1):
    """One solution is one pass over its prompt bucket and its decode
    bucket's positions (the work of prefill plus every decode step, each
    counted once: the pairs the selection and the band leave), the head
    at the positions that serve an id."""
    t = decode_bucket(arch, int(task["max_new_tokens"]))
    s = prompt_bucket(arch, task.get("prompt", "")) + t - 1
    return [("forward", (jax.ShapeDtypeStruct((batch, s), jnp.int32),
                         jax.ShapeDtypeStruct((t,), jnp.int32)), 1)]


def window_kernel_calls(arch: dict, task: dict) -> list[tuple]:
    """The reference's banded attention that the program's prefill serves
    with its banded kernel, one call a sliding layer a sequence:
    [(heads, prompt positions, window, dn, dr, dv)] — `window_work`'s
    arguments —, none where the prompt bucket is under KERNEL_MIN_ROWS."""
    p = prompt_bucket(arch, task.get("prompt", ""))
    if p < KERNEL_MIN_ROWS:
        return []
    k_ = kind(arch["model"], "sliding")
    call = (k_["heads"], p, k_["window"], k_["qk_nope_head_dim"],
            k_["qk_rope_head_dim"], k_["v_head_dim"])
    return [call for _, attn in arch["model"]["layers"] if attn == "sliding"]
