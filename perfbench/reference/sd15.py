"""Plain float32 reference of the Stable Diffusion 1.5 txt2img path.

Text tower (CLIP ViT-L/14 text, quick-gelu), epsilon UNet with spatial
transformers (self-attention, cross-attention over 77 tokens, GEGLU),
classifier-free guidance, the template's sampler, the VAE decoder and the
conversion to 8-bit pixels. One task at a time (batch 1; the CFG pair is
batch 2). Imports nothing of the program; the weight tree is the
checkpoint layout the node loads (a nested dict of bf16 arrays).
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import ops
from perfbench.reference.schedules import SAMPLERS

LATENT_SCALE = 0.18215
VAE_FACTOR = 8


def _transformer_block(x, p, context, n_heads):
    x = x + ops.mha(ops.layer_norm(x, p["LayerNorm_0"]), p["attn1"], n_heads)
    x = x + ops.mha(ops.layer_norm(x, p["LayerNorm_1"]), p["attn2"], n_heads,
                    context=context)
    h = ops.layer_norm(x, p["LayerNorm_2"])
    h = ops.dense(h, p["ff"]["ff_val"]) \
        * ops.gelu(ops.dense(h, p["ff"]["ff_gate"]))
    return x + ops.dense(h, p["ff_out"])


def _spatial_transformer(x, p, context, n_heads, depth):
    b, h, w, c = x.shape
    res = x
    x = ops.group_norm(x, p["GroupNorm32_0"]["GroupNorm_0"], eps=1e-6)
    x = ops.conv(x, p["proj_in"]).reshape(b, h * w, c)
    for i in range(depth):
        x = _transformer_block(x, p[f"block_{i}"], context, n_heads)
    return ops.conv(x.reshape(b, h, w, c), p["proj_out"]) + res


def unet(p, x, t, context, cfg):
    """epsilon(x_t, t, context); x NHWC [B,h,w,4], t [B], context [B,L,D]."""
    chans = cfg["block_channels"]
    lpb, att = cfg["layers_per_block"], cfg["attention_levels"]
    nh, depth = cfg["num_heads"], cfg.get("transformer_depth", 1)
    te = p["TimestepEmbedding_0"]
    temb = ops.dense(ops.silu(ops.dense(ops.sinusoidal(t, chans[0]),
                                        te["Dense_0"])), te["Dense_1"])
    h = ops.conv(x, p["conv_in"])
    skips = [h]
    for lvl in range(len(chans)):
        for j in range(lpb):
            h = ops.resnet(h, p[f"down_{lvl}_res_{j}"], temb)
            if att[lvl]:
                h = _spatial_transformer(h, p[f"down_{lvl}_attn_{j}"],
                                         context, nh, depth)
            skips.append(h)
        if lvl < len(chans) - 1:
            h = ops.conv(h, p[f"down_{lvl}_ds"]["Conv_0"], stride=2)
            skips.append(h)
    h = ops.resnet(h, p["mid_res_0"], temb)
    h = _spatial_transformer(h, p["mid_attn"], context, nh, depth)
    h = ops.resnet(h, p["mid_res_1"], temb)
    for lvl in reversed(range(len(chans))):
        for j in range(lpb + 1):
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = ops.resnet(h, p[f"up_{lvl}_res_{j}"], temb)
            if att[lvl]:
                h = _spatial_transformer(h, p[f"up_{lvl}_attn_{j}"],
                                         context, nh, depth)
        if lvl > 0:
            h = ops.conv(ops.upsample2(h), p[f"up_{lvl}_us"]["Conv_0"])
    h = ops.silu(ops.group_norm(h, p["norm_out"]["GroupNorm_0"]))
    return ops.conv(h, p["conv_out"])


def vae_decode(p, z, cfg):
    """AutoencoderKL decoder: latents / scale -> pixels in [-1, 1]."""
    chans, lpb = cfg["block_channels"], cfg["layers_per_block"]
    h = ops.conv(ops.conv(z, p["post_quant"]), p["conv_in"])
    h = ops.resnet(h, p["mid_res_0"], eps=1e-6)
    b, hh, ww, c = h.shape
    a = ops.group_norm(h, p["mid_attn"]["GroupNorm32_0"]["GroupNorm_0"],
                       eps=1e-6).reshape(b, hh * ww, c)
    h = h + ops.mha(a, p["mid_attn"]["Attention_0"], 1).reshape(b, hh, ww, c)
    h = ops.resnet(h, p["mid_res_1"], eps=1e-6)
    for lvl in reversed(range(len(chans))):
        for j in range(lpb + 1):
            h = ops.resnet(h, p[f"up_{lvl}_res_{j}"], eps=1e-6)
        if lvl > 0:
            h = ops.conv(ops.upsample2(h), p[f"up_{lvl}_us"]["Conv_0"])
    h = ops.silu(ops.group_norm(h, p["norm_out"]["GroupNorm_0"], eps=1e-6))
    return ops.conv(h, p["conv_out"])


def _tokens(arch, text):
    t = arch["tokenizer"]
    return ops.byte_tokens(text, arch["text"]["max_length"], t["bos_id"],
                           t["eos_id"])[None]


def parts(arch):
    """The forward of each part, as (name, fn(params, *shapes), calls per
    solution) — what the FLOP count walks and what `image` jits."""
    return {
        "text": lambda p, ids: ops.text_tower(p["text"], ids, arch["text"]),
        "unet": lambda p, x, t, ctx: unet(p["unet"], x, t, ctx, arch["unet"]),
        "vae": lambda p, z: vae_decode(p["vae"], z, arch["vae"]),
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
    ctx_u = fns["text"](params, _tokens(arch, task.get("negative_prompt", "")))
    ctx_c = fns["text"](params, _tokens(arch, task["prompt"]))
    context = jnp.concatenate([ctx_u, ctx_c], axis=0)
    lh, lw = task["height"] // VAE_FACTOR, task["width"] // VAE_FACTOR
    key = ops.task_keys(seed)
    x = jax.random.normal(key, (lh, lw, arch["unet"]["in_channels"]),
                          jnp.float32)[None]
    sampler = SAMPLERS[task["scheduler"]](int(task["num_inference_steps"]))
    g = np.float32(task["guidance_scale"])
    state = sampler.start(x)
    for i in range(sampler.calls):
        t = jnp.full((2,), float(sampler.timesteps[i]), jnp.float32)
        eps = fns["unet"](params, jnp.concatenate([x, x], axis=0), t, context)
        eps = eps[:1] + g * (eps[1:] - eps[:1])
        x, state = sampler.step(i, x, eps, state)
    pixels = fns["vae"](params, x / np.float32(LATENT_SCALE))
    return np.asarray(ops.to_uint8(pixels))[0]


def forward_shapes(arch: dict, task: dict, batch: int = 1):
    """(part, abstract args, calls per solution) at a task's shapes, for the
    FLOP count: `batch` rows of text, the CFG pair doubles the UNet batch."""
    sds = jax.ShapeDtypeStruct
    lh, lw = task["height"] // VAE_FACTOR, task["width"] // VAE_FACTOR
    length, width = arch["text"]["max_length"], arch["text"]["width"]
    steps = SAMPLERS[task["scheduler"]](int(task["num_inference_steps"])).calls
    return [
        ("text", (sds((batch, length), jnp.int32),), 2),
        ("unet", (sds((2 * batch, lh, lw, arch["unet"]["in_channels"]),
                      jnp.float32),
                  sds((2 * batch,), jnp.float32),
                  sds((2 * batch, length, width), jnp.float32)), steps),
        ("vae", (sds((batch, lh, lw, arch["vae"]["latent_channels"]),
                     jnp.float32),), 1),
    ]
