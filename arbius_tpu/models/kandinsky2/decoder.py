"""Kandinsky-2 decoder UNet: CLIP-image-embedding-conditioned denoiser.

Second diffusion stage of the kandinsky2 template: where SD-1.5
cross-attends over 77 text tokens, Kandinsky's decoder conditions on the
single CLIP image embedding the prior produced — projected BOTH into a
short context token sequence (the published ImageProjection head: linear
→ reshape to tokens → LayerNorm) AND into the timestep embedding (the
published add_embedding MLP).

The UNet interior follows the published unCLIP-family decoder (diffusers
`UNet2DConditionModel` with ResnetDownsample/SimpleCrossAttn blocks), NOT
SD's transformer blocks:

  - attention is single-layer ADDED-KV attention: queries from spatial
    tokens, keys/values from [projected context ‖ spatial tokens]
    (`add_k_proj`/`add_v_proj`), group-normed input, biased projections —
    no proj_in/proj_out, no GEGLU feed-forward;
  - attention sits at every level EXCEPT the highest resolution
    (attention_levels (False, True, True, True));
  - down/upsampling is resnet-based (a resnet whose both branches 2×
    average-pool / nearest-upsample), not a strided conv;
  - resnet time conditioning is scale/shift (FiLM), head size is a fixed
    64 (head count grows with width), and the output carries 2× channels
    (epsilon + learned variance; samplers here consume the epsilon half).

TPU execution profile: bucketed static shapes, bf16 MXU convs/attention,
one jitted program per shape bucket — identical discipline to SD-1.5.
The added-KV attention goes through `ops.flash.attention`, the door
`models.common.Attention` uses: from 1024 query rows on a TPU the flash
kernel (the 48x48 level at 768x768: 2304 queries over 10 + 2304 keys,
whose float32 scores XLA's einsum wrote to HBM and read back, 2 GB a call
at batch 8), below that and off the TPU `sp_attention_reference`.
Conversion source: the diffusers-format kandinsky decoder checkpoint —
see kandinsky2/convert.py (`kandinsky_unet_key_for`).
"""
from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax.numpy as jnp

from arbius_tpu.models.common import (
    GroupNorm32,
    ResnetBlock,
    TimestepEmbedding,
    sinusoidal_embedding,
)
from arbius_tpu.models.sd15.unet import UNetConfig


@dataclass(frozen=True)
class DecoderConfig:
    unet: UNetConfig = UNetConfig(block_channels=(384, 768, 1152, 1536),
                                  layers_per_block=3,
                                  attention_levels=(False, True, True, True),
                                  out_channels=8, head_dim=64,
                                  context_dim=768, time_scale_shift=True)
    clip_dim: int = 1280
    context_tokens: int = 10      # image embed → this many pseudo-tokens

    @classmethod
    def tiny(cls) -> "DecoderConfig":
        import dataclasses

        unet = dataclasses.replace(
            UNetConfig.tiny(), attention_levels=(False, True, True, True),
            time_scale_shift=True)
        return cls(unet=unet, clip_dim=16, context_tokens=2)


class AttnAddedKV(nn.Module):
    """unCLIP-family attention: group-normed spatial queries over
    [context ‖ spatial] keys/values, all projections biased, residual
    inside. The attention itself is `ops.flash.attention`'s: scores and
    softmax in float32, probabilities in v's type (determinism +
    stability policy), kernel or einsum by the call's shape and backend."""
    num_heads: int
    head_dim: int
    context_dim: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, context):
        b, hh, ww, c = x.shape
        inner = self.num_heads * self.head_dim
        residual = x
        hs = GroupNorm32(name="group_norm")(x).reshape(b, hh * ww, c)
        hs = hs.astype(self.dtype)
        ctx = context.astype(self.dtype)
        q = nn.Dense(inner, dtype=self.dtype, name="to_q")(hs)
        k = nn.Dense(inner, dtype=self.dtype, name="to_k")(hs)
        v = nn.Dense(inner, dtype=self.dtype, name="to_v")(hs)
        ek = nn.Dense(inner, dtype=self.dtype, name="add_k_proj")(ctx)
        ev = nn.Dense(inner, dtype=self.dtype, name="add_v_proj")(ctx)
        # context tokens lead the key/value sequence (published order)
        k = jnp.concatenate([ek, k], axis=1)
        v = jnp.concatenate([ev, v], axis=1)

        def split(t):
            return t.reshape(t.shape[0], t.shape[1], self.num_heads,
                             self.head_dim).transpose(0, 2, 1, 3)

        # the pallas flash kernel on TPU from 1024 query rows (the 48x48
        # level at 768x768), XLA einsum otherwise — same math either way
        from arbius_tpu.ops.flash import attention as fused_attention

        out = fused_attention(split(q), split(k), split(v))
        out = out.transpose(0, 2, 1, 3).reshape(b, hh * ww, inner)
        out = nn.Dense(c, dtype=self.dtype, name="to_out")(out)
        return residual + out.reshape(b, hh, ww, c)


class KandinskyUNet(nn.Module):
    """__call__(latents[B,h,w,4], t[B], context[B,S,D], extra_temb[B,4ch0])
    -> eps[+variance]. Published unCLIP-style topology (module docstring)."""
    config: UNetConfig

    @nn.compact
    def __call__(self, x, t, context, extra_temb=None):
        cfg = self.config
        dt = cfg.jdtype
        x = x.astype(dt)
        context = context.astype(dt)
        ss = cfg.time_scale_shift

        temb = sinusoidal_embedding(t, cfg.block_channels[0])
        temb = TimestepEmbedding(cfg.block_channels[0] * 4, dt)(temb)
        if extra_temb is not None:
            temb = temb + extra_temb.astype(temb.dtype)

        h = nn.Conv(cfg.block_channels[0], (3, 3), padding=1, dtype=dt,
                    name="conv_in")(x)
        skips = [h]

        # encoder
        for level, ch in enumerate(cfg.block_channels):
            for j in range(cfg.layers_per_block):
                h = ResnetBlock(ch, dt, ss,
                                name=f"down_{level}_res_{j}")(h, temb)
                if cfg.attention_levels[level]:
                    heads, hd = cfg.heads_for(ch)
                    h = AttnAddedKV(heads, hd, cfg.context_dim, dt,
                                    name=f"down_{level}_attn_{j}")(h, context)
                skips.append(h)
            if level < len(cfg.block_channels) - 1:
                h = ResnetBlock(ch, dt, ss, resample="down",
                                name=f"down_{level}_ds")(h, temb)
                skips.append(h)

        # mid
        mid_ch = cfg.block_channels[-1]
        h = ResnetBlock(mid_ch, dt, ss, name="mid_res_0")(h, temb)
        mheads, mhd = cfg.heads_for(mid_ch)
        h = AttnAddedKV(mheads, mhd, cfg.context_dim, dt,
                        name="mid_attn")(h, context)
        h = ResnetBlock(mid_ch, dt, ss, name="mid_res_1")(h, temb)

        # decoder
        for level in reversed(range(len(cfg.block_channels))):
            ch = cfg.block_channels[level]
            for j in range(cfg.layers_per_block + 1):
                h = jnp.concatenate([h, skips.pop()], axis=-1)
                h = ResnetBlock(ch, dt, ss,
                                name=f"up_{level}_res_{j}")(h, temb)
                if cfg.attention_levels[level]:
                    heads, hd = cfg.heads_for(ch)
                    h = AttnAddedKV(heads, hd, cfg.context_dim, dt,
                                    name=f"up_{level}_attn_{j}")(h, context)
            if level > 0:
                h = ResnetBlock(ch, dt, ss, resample="up",
                                name=f"up_{level}_us")(h, temb)

        h = GroupNorm32(name="norm_out")(h)
        h = nn.silu(h)
        return nn.Conv(cfg.out_channels, (3, 3), padding=1,
                       dtype=jnp.float32, name="conv_out")(h.astype(jnp.float32))


class DecoderUNet(nn.Module):
    """__call__(latents[B,h,w,4], t[B], image_embed[B,clip_dim]) -> eps[+var]."""
    config: DecoderConfig

    @nn.compact
    def __call__(self, x, t, image_embed):
        cfg = self.config
        dt = cfg.unet.jdtype
        emb = image_embed.astype(dt)
        # cross-attention context (published ImageProjection)
        ctx = nn.Dense(cfg.context_tokens * cfg.unet.context_dim, dtype=dt,
                       name="embed_to_context")(emb)
        ctx = ctx.reshape(emb.shape[0], cfg.context_tokens,
                          cfg.unet.context_dim)
        ctx = nn.LayerNorm(epsilon=1e-5, dtype=jnp.float32, name="context_norm")(
            ctx.astype(jnp.float32)).astype(dt)
        # additive timestep-embedding branch (published add_embedding)
        tdim = cfg.unet.block_channels[0] * 4
        add = nn.Dense(tdim, dtype=dt, name="add_linear_1")(emb)
        add = nn.Dense(tdim, dtype=dt, name="add_linear_2")(nn.silu(add))
        return KandinskyUNet(cfg.unet, name="unet")(x, t, ctx,
                                                    extra_temb=add)
