"""Plain float32 reference of Nemotron 3 Super (`nemotron_h`) with its
multi-token prediction module, the share of it that a configuration
states (`experts_held`, `vocab_rows`).

One teacher-forced forward pass over prompt and served ids together: no
cache of any kind, no chunked scan, no prefill/decode split, no
speculation, no kernel. `jax.numpy` at float32 with matmul precision
"highest" (perfbench/reference/ops.py); imports nothing of the program —
the weight tree is the layout a node loads. RMSNorm, blocked softmax
attention under a mask, the embedding and the head are the same to the
letter as in `reference/deepseek_v32.py`, the router and the module's
input and head as in `reference/joyai_llm_flash.py`, and are imported
from them. The equations (the configuration file restates them under
`assumed`):

  h = embed[ids]                        (no scale)
  a layer is ONE of these, in `layers` order: h = h + f(norm(h))
          (RMSNorm, eps 1e-5)
  mamba:  in_proj -> z [d_inner] | xBC [d_inner + 2·G·N] | dt [H];
          xBC = silu(depthwise causal conv1d, kernel K, with bias);
          x [H, Pd], B [G, N], C [G, N], head h reading group h // (H/G);
          dt = softplus(dt + dt_bias), A = -exp(A_log);
          s_t = exp(dt_t·A)·s_{t-1} + dt_t·x_t ⊗ B_t  (s [H, Pd, N] from 0)
          y_t = s_t·C_t + D·x_t — a `lax.scan` over positions, one at a
          time;
          out = out_proj(w · RMSNorm_{groups of d_inner/G}(y · silu(z)))
  attention: q = x·Wq -> 32 heads, k = x·Wk, v = x·Wv -> 2 heads of 128,
          query head j reading KV head j // 16; softmax over every key
          s <= t of (q·k)·128^-1/2 — no rotary embedding; out = concat·Wo
  moe:    s = sigmoid(x·Wr) over all experts; the chosen = top-k of
          s + bias (ties to the lower id); w = s[chosen] / sum s[chosen]
          · route_scale; u = x·W_down (the latent); ffn(x) =
          relu(x·U_s)²·D_s + (sum over the chosen experts held here of
          w_i·relu(u·U_i)²·D_i)·W_up
  logits = final_norm(h)·Whead over the vocabulary rows held here
  module: at position i, with h_i the main model's state BEFORE
          final_norm and t_{i+1} the next id:
          x_i = Weh·[enorm(embed[t_{i+1}]) ; hnorm(h_i)]; one attention
          layer and one moe layer (the equations above, causal over the
          module's own x);
          logits_i = norm(x_i')·Whead: a guess at id i+2

Departures from the published model: none in the equations; the
weights are bfloat16 (the served arrays, widened here), the state and
every sum float32.

Computed a sequence at a time and layer by layer on the served bfloat16
arrays (one jitted function a layer kind). A float32 expert layer does
not fit beside the served weights, so the held experts are walked in
groups of EXPERT_GROUP: each group's kernels are widened, every token
goes through every expert of the group, and the router's weight (0
where not chosen) picks what counts.

The FLOP count (perfbench/flops.py walks the parts under
`jax.eval_shape`): `forward` is what a solution needs — the main model
over prompt + served ids, once; projections and MLPs through
`ops.dense`; the held experts at the expected load under `other`
"experts"; attention's causal pairs under "attention" (2·heads·(128 +
128) a pair); and the state-space layers' recurrence as the chunked
scan does its products at `chunk_size` (`ssd_flops`) under "ssd" — the
recurrence itself is elementwise and `ops.dense` sees none of it. `mtp`
is the module over the same positions, all of it under `other` "mtp",
and `forward_shapes` gives it 0 calls: a draft is work the program
chooses to spend, not work a solution needs.
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
    attention,
    embed,
    head,
    rms_norm,
)
from perfbench.reference.joyai_llm_flash import mtp_head, mtp_in, route
from perfbench.reference.trinity import decode_bucket, prompt_bucket

EXPERT_GROUP = 16   # experts whose kernels are float32 at once


def ssd_flops(s: int, cfg: dict) -> float:
    """The chunked scan's products over s positions at `chunk_size`: C·B
    and the weighted sum of x over the causal pairs inside each chunk,
    a chunk's own state and its read-back at every position."""
    g, n = cfg["n_groups"], cfg["ssm_state"]
    h, pd = cfg["mamba_heads"], cfg["mamba_head_dim"]
    chunk = cfg["chunk_size"]
    sizes = [min(chunk, s - c0) for c0 in range(0, s, chunk)]
    pairs = sum(c * (c + 1) // 2 for c in sizes)
    return 2.0 * (g * n + h * pd) * pairs + 2.0 * 2 * h * pd * n * s


def mamba(lp, x, cfg):
    """A Mamba-2 layer's mixer on one sequence x[S, d] (normed)."""
    mp = lp["mixer"]
    s = x.shape[0]
    h, pd, n, g = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                   cfg["ssm_state"], cfg["n_groups"])
    di = h * pd
    out = ops.dense(x, mp["in_proj"])
    z, xbc, dt = out[:, :di], out[:, di:-h], out[:, -h:]
    k = cfg["conv_kernel"]
    w = ops.f32(mp["conv"]["kernel"])
    pad = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = ops.silu(ops.f32(mp["conv_bias"])
                   + sum(w[i] * pad[i:i + s] for i in range(k)))
    xs = xbc[:, :di].reshape(s, h, pd)
    rep = h // g
    b = jnp.repeat(xbc[:, di:di + g * n].reshape(s, g, n), rep, axis=1)
    c = jnp.repeat(xbc[:, di + g * n:].reshape(s, g, n), rep, axis=1)
    dt = jax.nn.softplus(dt + ops.f32(mp["dt_bias"]))
    a = -jnp.exp(ops.f32(mp["A_log"]))
    d_skip = ops.f32(mp["D"])

    def one(state, t):
        x_t, b_t, c_t, dt_t = t
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", state, c_t, precision=ops.HIGHEST)
        return state, y + d_skip[:, None] * x_t

    _, y = jax.lax.scan(one, jnp.zeros((h, pd, n), jnp.float32),
                        (xs, b, c, dt))
    ops.count("ssd", ssd_flops(s, cfg))
    gated = y.reshape(s, di) * ops.silu(z)
    gs = gated.reshape(s, g, di // g)
    gs = gs * jax.lax.rsqrt(jnp.mean(gs * gs, axis=-1, keepdims=True)
                            + cfg["eps"])
    return ops.dense(gs.reshape(s, di) * ops.f32(mp["norm"]["scale"]),
                     mp["out_proj"])


def attend(a, x, cfg):
    """GQA on one sequence x[S, d] (normed), full causal, no rotary."""
    s = x.shape[0]
    nh, kv, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    q = ops.dense(x, a["q"]).reshape(s, nh, hd)
    k, v = (jnp.repeat(ops.dense(x, a[n]).reshape(s, kv, hd), nh // kv,
                       axis=1) for n in ("k", "v"))
    ops.count("attention", 2.0 * nh * (hd + hd) * (s * (s + 1) // 2))
    o = attention(q, k, v, jnp.tril(jnp.ones((s, s), bool)), hd ** -0.5)
    return ops.dense(o.reshape(s, nh * hd), a["o"])


def relu2(x, p):
    return ops.dense(jnp.square(jax.nn.relu(ops.dense(x, p["up"]))),
                     p["down"])


def moe(p, x, cfg):
    """x[S, d] (normed): the shared expert on the full width + the held
    experts' part of the routed sum in the latent space, the experts in
    groups."""
    lo, hi = cfg["experts_held"]
    held = hi - lo
    group = min(EXPERT_GROUP, held)
    if held % group:
        raise ValueError(f"{held} held experts are no whole groups of "
                         f"{group}")
    w = route(x, p, cfg)[:, lo:hi].T.reshape(held // group, group, -1)
    u = ops.operand(ops.dense(x, p["latent_down"]))

    def one(wg, up, down):
        hid = jnp.square(jax.nn.relu(jnp.matmul(
            u, ops.kernel({"kernel": up}), precision=ops.HIGHEST)))
        return wg[:, None] * jnp.matmul(ops.operand(hid),
                                        ops.kernel({"kernel": down}),
                                        precision=ops.HIGHEST)

    def add(y, args):
        return y + jax.vmap(one)(*args).sum(axis=0), None

    kernels = [p["experts"][n]["kernel"] for n in ("up", "down")]
    y, _ = jax.lax.scan(add, jnp.zeros((x.shape[0], cfg["latent"]),
                                       jnp.float32), (
        w, *(kk.reshape((held // group, group) + kk.shape[1:])
             for kk in kernels)))
    # the work of the algorithm: only the tokens sent to experts held
    # here, at the expected load (tokens * k * held / experts)
    ops.count("experts", x.shape[0] * cfg["experts_per_token"] * held
              / cfg["num_experts"]
              * 2 * ops.dense_flops(1, cfg["latent"], cfg["expert_ff"]))
    return relu2(x, p["shared"]) + ops.dense(y, p["latent_up"])


def layer(lp, x, kind, cfg):
    """One layer on one sequence x[S, d]; kind = "mamba" | "attention" |
    "moe"."""
    h = rms_norm(x, lp["norm"], cfg["eps"])
    if kind == "mamba":
        return x + mamba(lp, h, cfg)
    if kind == "attention":
        return x + attend(lp["attn"], h, cfg)
    return x + moe(lp["moe"], h, cfg)


def hidden(p, ids, cfg):
    """ids[S] -> the main model's states [S, d] before final_norm."""
    x = embed(p["embed"], ids, cfg)
    for i, kind in enumerate(cfg["layers"]):
        x = layer(p[f"layer_{i}"], x, kind, cfg)
    return x


def mtp_layers(p, x, cfg):
    """The module's attention layer then its expert layer."""
    mp = p["mtp"]
    return layer(mp["moe"], layer(mp["attention"], x, "attention", cfg),
                 "moe", cfg)


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
            mtp_head(p, mtp_layers(p, mtp_in(p, row[1:], hs[:-1], cfg), cfg),
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
           "head": lambda p, x: head(p, x, cfg)}
    for kind in set(cfg["layers"]):
        fns["layer." + kind] = functools.partial(
            lambda lp, x, kind: layer(lp, x, kind, cfg), kind=kind)
    return {k: jax.jit(ops.traced_with(v, weights)) for k, v in fns.items()}


def logits(params, arch: dict, task: dict, served,
           weights: str | None = None) -> np.ndarray:
    """[T, BYTES] float32: for each of the T served ids, the main model's
    logits over the byte slice at the position that produced it, given
    the prompt (padded to its bucket as the tokenizer pads it) and the
    served ids before it. `weights` ("fp8") computes the control
    instead."""
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
