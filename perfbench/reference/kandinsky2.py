"""Plain float32 reference of the Kandinsky 2.2 txt2img path.

Text tower (open_clip bigG text, exact gelu) with EOT pooling and the
text projection; the diffusion prior (PriorTransformer over [77 text
tokens, pooled embed, time, noisy image embed, prd token]) with its
deterministic x0-prediction DDIM on the cosine schedule and
classifier-free guidance against a zeroed text context; the decoder UNet
(unCLIP-style: resnet down/up-sampling, scale-shift time conditioning,
added-KV attention over [10 context tokens ‖ spatial tokens] at the three
lower levels, epsilon in the first 4 of 8 output channels) under DDIM
with guidance against a zero image embedding; the MOVQ decoder with
spatially modulated norms; conversion to 8-bit pixels. One task at a
time. Imports nothing of the program.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import ops
from perfbench.reference.schedules import DDIM, prior_schedule

MOVQ_FACTOR = 8
NEG = -1e9


def text_embed(p, ids, arch):
    """-> (states[:, :text_len], pooled projected embed, key mask)."""
    states = ops.text_tower(p["text"], ids, arch["text"])
    eos = arch["tokenizer"]["eos_id"]
    first_eos = jnp.argmax((ids == eos).astype(jnp.int32), axis=1)
    pooled = states[jnp.arange(states.shape[0]), first_eos]
    pooled = ops.dense(pooled, p["text_proj"]["proj"], bias=False)
    n = arch["prior"]["text_len"]
    mask = (jnp.arange(ids.shape[1])[None, :] <= first_eos[:, None])
    return states[:, :n], pooled, mask[:, :n].astype(jnp.float32)


def prior_forward(p, noisy, t, tokens, pooled, text_mask, cfg):
    """x0 prediction of the normalised image embedding, [B, clip_dim]."""
    w, nh = cfg["width"], cfg["heads"]
    b = noisy.shape[0]
    temb = ops.dense(ops.sinusoidal(t, w), p["time_linear_1"])
    temb = ops.dense(ops.silu(temb), p["time_linear_2"])
    seq = jnp.concatenate([
        ops.dense(tokens, p["text_proj"]),
        ops.dense(pooled, p["pooled_proj"])[:, None],
        temb[:, None],
        ops.dense(noisy, p["embed_proj"])[:, None],
        jnp.broadcast_to(ops.f32(p["prd_embed"]), (b, 1, w)),
    ], axis=1) + ops.f32(p["pos_embed"])
    full = jnp.concatenate([text_mask, jnp.ones((b, 4), jnp.float32)], axis=1)
    mask = (1.0 - full)[:, None, None, :] * NEG
    for i in range(cfg["layers"]):
        bp = p[f"block_{i}"]
        seq = seq + ops.mha(ops.layer_norm(seq, bp["norm1"]), bp["attn1"], nh,
                            mask=mask)
        h = ops.layer_norm(seq, bp["norm3"])
        seq = seq + ops.dense(ops.gelu(ops.dense(h, bp["ff_in"])),
                              bp["ff_out"])
    return ops.dense(ops.layer_norm(seq[:, -1], p["norm_out"]), p["out_proj"])


def _added_kv_attention(x, p, context, u):
    b, hh, ww, c = x.shape
    nh = c // u["head_dim"] if u.get("head_dim") else u["num_heads"]
    hs = ops.group_norm(x, p["group_norm"]["GroupNorm_0"]) \
        .reshape(b, hh * ww, c)
    q = ops.heads(ops.dense(hs, p["to_q"]), nh)
    k = jnp.concatenate([ops.dense(context, p["add_k_proj"]),
                         ops.dense(hs, p["to_k"])], axis=1)
    v = jnp.concatenate([ops.dense(context, p["add_v_proj"]),
                         ops.dense(hs, p["to_v"])], axis=1)
    out = ops.unheads(ops.attend(q, ops.heads(k, nh), ops.heads(v, nh)))
    return x + ops.dense(out, p["to_out"]).reshape(b, hh, ww, c)


def decoder_forward(p, x, t, image_embed, cfg):
    """[B,h,w,4], [B], [B,clip_dim] -> [B,h,w,8] (epsilon ‖ variance)."""
    u, up = cfg["unet"], p["unet"]
    chans, lpb = u["block_channels"], u["layers_per_block"]
    att = u["attention_levels"]
    ctx = ops.dense(image_embed, p["embed_to_context"]).reshape(
        image_embed.shape[0], cfg["context_tokens"], u["context_dim"])
    ctx = ops.layer_norm(ctx, p["context_norm"])
    add = ops.dense(ops.silu(ops.dense(image_embed, p["add_linear_1"])),
                    p["add_linear_2"])
    te = up["TimestepEmbedding_0"]
    temb = ops.dense(ops.silu(ops.dense(ops.sinusoidal(t, chans[0]),
                                        te["Dense_0"])), te["Dense_1"]) + add
    res = functools.partial(ops.resnet, scale_shift=True)
    h = ops.conv(x, up["conv_in"])
    skips = [h]
    for lvl in range(len(chans)):
        for j in range(lpb):
            h = res(h, up[f"down_{lvl}_res_{j}"], temb)
            if att[lvl]:
                h = _added_kv_attention(h, up[f"down_{lvl}_attn_{j}"], ctx, u)
            skips.append(h)
        if lvl < len(chans) - 1:
            h = res(h, up[f"down_{lvl}_ds"], temb, resample="down")
            skips.append(h)
    h = res(h, up["mid_res_0"], temb)
    h = _added_kv_attention(h, up["mid_attn"], ctx, u)
    h = res(h, up["mid_res_1"], temb)
    for lvl in reversed(range(len(chans))):
        for j in range(lpb + 1):
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = res(h, up[f"up_{lvl}_res_{j}"], temb)
            if att[lvl]:
                h = _added_kv_attention(h, up[f"up_{lvl}_attn_{j}"], ctx, u)
        if lvl > 0:
            h = res(h, up[f"up_{lvl}_us"], temb, resample="up")
    h = ops.silu(ops.group_norm(h, up["norm_out"]["GroupNorm_0"]))
    return ops.conv(h, up["conv_out"])


def _spatial_norm(h, z, p):
    reps = h.shape[1] // z.shape[1]
    z_up = jnp.repeat(jnp.repeat(z, reps, axis=1), reps, axis=2)
    normed = ops.group_norm(h, p["norm"]["GroupNorm_0"], eps=1e-6)
    return normed * ops.conv(z_up, p["conv_y"]) + ops.conv(z_up, p["conv_b"])


def _movq_res(x, z, p):
    h = ops.conv(ops.silu(_spatial_norm(x, z, p["norm1"])), p["Conv_0"])
    h = ops.conv(ops.silu(_spatial_norm(h, z, p["norm2"])), p["Conv_1"])
    if "skip" in p:
        x = ops.conv(x, p["skip"])
    return x + h


def movq_decode(p, z, cfg):
    """Continuous latents -> pixels in [-1, 1]; norms condition on raw z."""
    chans, lpb = cfg["block_channels"], cfg["layers_per_block"]
    h = ops.conv(ops.conv(z, p["post_quant"]), p["conv_in"])
    h = _movq_res(h, z, p["mid_res_0"])
    b, hh, ww, c = h.shape
    a = _spatial_norm(h, z, p["mid_attn_norm"]).reshape(b, hh * ww, c)
    h = h + ops.mha(a, p["mid_attn"], 1).reshape(b, hh, ww, c)
    h = _movq_res(h, z, p["mid_res_1"])
    for lvl in reversed(range(len(chans))):
        for j in range(lpb + 1):
            h = _movq_res(h, z, p[f"up_{lvl}_res_{j}"])
        if lvl > 0:
            h = ops.conv(ops.upsample2(h), p[f"up_{lvl}_us"]["Conv_0"])
    h = ops.silu(_spatial_norm(h, z, p["norm_out"]))
    return ops.conv(h, p["conv_out"])


def parts(arch):
    return {
        "text": lambda p, ids: text_embed(p, ids, arch),
        "prior": lambda p, x, t, tok, pool, mask: prior_forward(
            p["prior"], x, t, tok, pool, mask, arch["prior"]),
        "decoder": lambda p, x, t, emb: decoder_forward(
            p["decoder"], x, t, emb, arch["decoder"]),
        "movq": lambda p, z: movq_decode(p["movq"], z, arch["movq"]),
    }


@functools.lru_cache(maxsize=8)
def _jitted(arch_key, weights):
    return {k: jax.jit(ops.traced_with(v, weights))
            for k, v in parts(json.loads(arch_key)).items()}


def image(params, arch: dict, task: dict, seed: int,
          weights: str | None = None) -> np.ndarray:
    """The uint8 [H,W,3] image the protocol fixes for (task input, seed);
    `weights` ("fp8") computes the control instead."""
    fns = _jitted(json.dumps(arch, sort_keys=True), weights)
    tk = arch["tokenizer"]
    ids = jnp.asarray(ops.byte_tokens(task["prompt"],
                                      arch["text"]["max_length"],
                                      tk["bos_id"], tk["eos_id"])[None])
    tok, pooled, mask = fns["text"](params, ids)
    key = ops.task_keys(seed)
    g = np.float32(task["guidance_scale"])

    # prior: DDIM on x0 predictions, uncond = zeroed text with all keys valid
    steps = arch["prior_steps"]
    ts, abar = prior_schedule(steps)
    x = jax.random.normal(jax.random.fold_in(key, 0x9A10),
                          (arch["prior"]["clip_dim"],), jnp.float32)[None]
    tok2 = jnp.concatenate([jnp.zeros_like(tok), tok], axis=0)
    pool2 = jnp.concatenate([jnp.zeros_like(pooled), pooled], axis=0)
    mask2 = jnp.concatenate([jnp.ones_like(mask), mask], axis=0)
    for i in range(steps):
        t = jnp.full((2,), np.float32(ts[i]))
        x0 = fns["prior"](params, jnp.concatenate([x, x], axis=0), t, tok2,
                          pool2, mask2)
        x0 = x0[:1] + g * (x0[1:] - x0[:1])
        a_t = np.float32(abar[i])
        a_p = np.float32(abar[i + 1]) if i + 1 < steps else np.float32(1.0)
        eps = (x - np.sqrt(a_t) * x0) / np.sqrt(np.float32(1.0) - a_t)
        x = np.sqrt(a_p) * x0 + np.sqrt(np.float32(1.0) - a_p) * eps
    stats = ops.f32(params["prior_stats"])
    embed = x * stats[1][None] + stats[0][None]

    # decoder: DDIM on epsilon, uncond = zero image embedding
    lh, lw = task["height"] // MOVQ_FACTOR, task["width"] // MOVQ_FACTOR
    in_ch = arch["decoder"]["unet"]["in_channels"]
    x = jax.random.normal(key, (lh, lw, in_ch), jnp.float32)[None]
    sampler = DDIM(int(task["num_inference_steps"]))
    emb2 = jnp.concatenate([jnp.zeros_like(embed), embed], axis=0)
    for i in range(sampler.calls):
        t = jnp.full((2,), float(sampler.timesteps[i]), jnp.float32)
        out = fns["decoder"](params, jnp.concatenate([x, x], axis=0), t, emb2)
        eps = out[..., :in_ch]
        eps = eps[:1] + g * (eps[1:] - eps[:1])
        x, _ = sampler.step(i, x, eps, None)
    return np.asarray(ops.to_uint8(fns["movq"](params, x)))[0]


def forward_shapes(arch: dict, task: dict, batch: int = 1):
    sds = jax.ShapeDtypeStruct
    f = jnp.float32
    lh, lw = task["height"] // MOVQ_FACTOR, task["width"] // MOVQ_FACTOR
    pr = arch["prior"]
    n, d, tw = pr["text_len"], pr["clip_dim"], arch["text"]["width"]
    b2 = 2 * batch
    return [
        ("text", (sds((batch, arch["text"]["max_length"]), jnp.int32),), 1),
        ("prior", (sds((b2, d), f), sds((b2,), f), sds((b2, n, tw), f),
                   sds((b2, d), f), sds((b2, n), f)), arch["prior_steps"]),
        ("decoder", (sds((b2, lh, lw, arch["decoder"]["unet"]["in_channels"]),
                         f), sds((b2,), f), sds((b2, d), f)),
         int(task["num_inference_steps"])),
        ("movq", (sds((batch, lh, lw, arch["movq"]["latent_channels"]), f),),
         1),
    ]
