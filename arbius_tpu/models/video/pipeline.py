"""Text-to-video pipeline (zeroscope / damo template classes).

End-to-end jitted program per shape bucket: text encode → CFG UNet3D
denoise scan → per-frame VAE decode → uint8 frames. The node's video
runner encodes the frames to deterministic H.264 MP4 (codecs.encode_mp4_h264)
and CIDs the bytes — replacing the reference's cog container + ffmpeg
black box (`templates/zeroscopev2xl.json` out-1.mp4).

Parallel layout (mesh axes): dp shards samples, sp shards FRAMES — the
whole denoise scan runs under one shard_map, temporal ops communicating
via halo exchange + ring attention (see unet3d.py). Noise is derived per
(sample-key, step, GLOBAL frame index), so the sp layout does not change
which noise a frame sees — resharding changes only reduction order, not
the random stream.

Determinism contract: same as SD-1.5/Kandinsky — (model build, input,
seed, bucket, mesh layout) fixes output bytes; buckets are padded to a
canonical batch by the node.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from arbius_tpu.models.sd15.text_encoder import TextEncoder, TextEncoderConfig
from arbius_tpu.models.sd15.tokenizer import ByteTokenizer
from arbius_tpu.models.sd15.vae import (
    SD_LATENT_SCALE,
    VAEConfig,
    VAEDecoder,
    decode_to_images,
)
from arbius_tpu.models.video.unet3d import UNet3DCondition, UNet3DConfig
from arbius_tpu.schedulers import get_sampler


@dataclass(frozen=True)
class Text2VideoConfig:
    unet: UNet3DConfig = UNet3DConfig()
    vae: VAEConfig = VAEConfig()
    # published ModelScope/zeroscope text tower: OpenCLIP ViT-H-class —
    # hidden 1024, 16 heads, 24 layers, plain gelu
    text: TextEncoderConfig = TextEncoderConfig(width=1024, heads=16,
                                                layers=24, act="gelu")

    @classmethod
    def tiny(cls, sp_axis: str | None = None,
             sp_strategy: str = "ring") -> "Text2VideoConfig":
        return cls(unet=UNet3DConfig.tiny(sp_axis=sp_axis,
                                          sp_strategy=sp_strategy),
                   vae=VAEConfig.tiny(),
                   text=TextEncoderConfig.tiny())


class Text2VideoPipeline:
    VAE_FACTOR = 8

    def __init__(self, config: Text2VideoConfig | None = None, tokenizer=None,
                 mesh=None, precision: str = "bf16"):
        from arbius_tpu.quant import validate_mode

        self.config = config or Text2VideoConfig()
        self.mesh = mesh
        # precision mode (docs/quantization.md): "bf16" is the historic
        # program byte-for-byte; int8/fp8 take the factory-quantized
        # UNet3D/temporal-conv weight tree (the ROADMAP's quantized
        # hot loop) and dequantize in-program — own golden per mode
        self.precision = validate_mode(precision)
        if self.config.text.width != self.config.unet.context_dim:
            raise ValueError(
                f"text width ({self.config.text.width}) must equal unet "
                f"context_dim ({self.config.unet.context_dim})")
        sp = mesh.shape.get("sp", 1) if mesh is not None else 1
        if sp > 1 and self.config.unet.sp_axis != "sp":
            raise ValueError(
                "mesh has sp>1 but unet.sp_axis is not 'sp' — the model "
                "must be built sharding-aware (UNet3DConfig(sp_axis='sp'))")
        if sp == 1 and self.config.unet.sp_axis is not None and mesh is None:
            raise ValueError("unet.sp_axis set but no mesh given")
        self.tokenizer = tokenizer or ByteTokenizer(
            max_length=self.config.text.max_length)
        self.text_encoder = TextEncoder(self.config.text)
        self.unet = UNet3DCondition(self.config.unet)
        self.vae = VAEDecoder(self.config.vae)
        self._buckets: dict[tuple, object] = {}
        self._coll_est: dict[tuple, dict] = {}  # per-bucket traffic estimate

    # -- params ----------------------------------------------------------
    def init_params(self, seed: int = 0, frames: int = 2, height: int = 64,
                    width: int = 64, dtype=None) -> dict:
        """Init with sp_axis disabled (collectives need a mesh); the param
        tree is identical either way, so these params drive both paths.

        One jitted program (eager flax init dispatches hundreds of small
        ops one by one); `dtype` folds the weights cast in so the f32
        tree is never fully resident (see SD15Pipeline.init_params)."""
        cfg = self.config
        lh, lw = height // self.VAE_FACTOR, width // self.VAE_FACTOR
        unet_local = UNet3DCondition(
            dataclasses.replace(cfg.unet, sp_axis=None))

        def _init(key):
            k1, k2, k3 = jax.random.split(key, 3)
            lat = jnp.zeros((1, frames, lh, lw, cfg.unet.in_channels))
            ids = jnp.zeros((1, cfg.text.max_length), jnp.int32)
            ctx = jnp.zeros((1, cfg.text.max_length, cfg.unet.context_dim))
            return {
                "unet": unet_local.init(k1, lat, jnp.zeros((1,)), ctx)["params"],
                "vae": self.vae.init(k2, lat[:, 0])["params"],
                "text": self.text_encoder.init(k3, ids)["params"],
            }

        from arbius_tpu.utils import with_cast

        return jax.jit(with_cast(_init, dtype))(jax.random.PRNGKey(seed))

    def place_params(self, params: dict, tp_rules=()) -> dict:
        """Video path shards dp×sp via shard_map with replicated params
        (in_spec P()); TP param sharding is not wired into this pipeline,
        so the default is full replication — pass rules only if you also
        change the shard_map in_specs."""
        if self.mesh is None:
            return params
        from arbius_tpu.parallel import shard_params

        return shard_params(params, self.mesh, list(tp_rules))

    # -- compiled bucket -------------------------------------------------
    def compiled_bucket(self, batch: int, frames: int, height: int,
                        width: int, steps: int, scheduler: str):
        return self._get_bucket(batch, frames, height, width, steps,
                                scheduler)[0]

    def bucket_tag(self, batch: int, frames: int, height: int, width: int,
                   steps: int, scheduler: str) -> str:
        """One definition of this family's executable-cache tag — the
        warm sets and the AOT disk-warm scan join on it
        (docs/compile-cache.md). Non-default precision modes suffix it
        (".int8"/".fp8") — a quantized bucket never shares a warm
        signal with its bf16 twin; bf16 tags stay byte-identical."""
        from arbius_tpu.quant import mode_tag

        return "video." + ".".join(
            str(k) for k in (batch, frames, height, width, steps,
                             scheduler)) + mode_tag(self.precision)

    def _get_bucket(self, batch: int, frames: int, height: int,
                    width: int, steps: int, scheduler: str,
                    aot_args=None):
        """(fn, warm, tag) — cache lookup reported through the
        jit-cache metrics (docs/observability.md); `aot_args` opts into
        the AOT disk tier (docs/compile-cache.md)."""
        from arbius_tpu.obs import jit_cache_get

        key = (batch, frames, height, width, steps, scheduler)
        return jit_cache_get(
            self._buckets, key,
            lambda: self._build_bucket(batch, frames, height, width,
                                       steps, scheduler),
            tag=self.bucket_tag(*key), aot_args=aot_args)

    def _build_bucket(self, batch: int, frames: int, height: int,
                      width: int, steps: int, scheduler: str):
        cfg = self.config
        sampler = get_sampler(scheduler, steps)
        lh, lw = height // self.VAE_FACTOR, width // self.VAE_FACTOR
        sp = self.mesh.shape.get("sp", 1) if self.mesh is not None else 1
        dp = self.mesh.shape.get("dp", 1) if self.mesh is not None else 1
        if frames % sp:
            raise ValueError(f"frames {frames} not divisible by sp={sp}")
        if batch % dp:
            raise ValueError(f"batch {batch} not divisible by dp={dp}")
        t_local = frames // sp
        precision = self.precision

        def run(params, ids_c, ids_u, guidance, seeds_lo, seeds_hi):
            if precision != "bf16":
                from arbius_tpu.quant import dequantize_tree

                # int8/fp8 kernels → f32 via their f32 scales (GRAPH407
                # contract); guarded so bf16 stays byte-identical
                params = dequantize_tree(params)
            b_local = ids_c.shape[0]
            if cfg.unet.sp_axis is not None:
                sp_rank = jax.lax.axis_index(cfg.unet.sp_axis)
            else:
                sp_rank = 0
            frame0 = sp_rank * t_local
            ctx_c = self.text_encoder.apply({"params": params["text"]}, ids_c)
            ctx_u = self.text_encoder.apply({"params": params["text"]}, ids_u)
            context = jnp.concatenate([ctx_u, ctx_c], axis=0)

            keys = jax.vmap(
                lambda lo, hi: jax.random.fold_in(jax.random.PRNGKey(lo), hi)
            )(seeds_lo, seeds_hi)

            def noise_for(step_tag):
                # noise keyed by (sample, step, GLOBAL frame): sp-invariant
                def per_sample(k):
                    kk = jax.random.fold_in(k, step_tag)
                    return jax.vmap(lambda f: jax.random.normal(
                        jax.random.fold_in(kk, f),
                        (lh, lw, cfg.unet.in_channels), jnp.float32))(
                        frame0 + jnp.arange(t_local))
                return jax.vmap(per_sample)(keys)

            # init-noise tag is outside the step range [0, num_model_calls)
            x = noise_for(jnp.int32(1 << 30)) * sampler.init_noise_sigma
            g = guidance.astype(jnp.float32)[:, None, None, None, None]

            def body(carry, i):
                x, state = carry
                xin = jnp.concatenate([x, x], axis=0) * sampler.input_scale[i]
                t = jnp.full((2 * b_local,), sampler.timesteps[i])
                eps = self.unet.apply({"params": params["unet"]}, xin, t,
                                      context)
                eps_u, eps_c = jnp.split(eps.astype(jnp.float32), 2, axis=0)
                eps = eps_u + g * (eps_c - eps_u)
                x, state = sampler.step(i, x, eps, state, noise_for(i))
                return (x, state), None

            (x, _), _ = jax.lax.scan(body, (x, sampler.init_carry(x)),
                                     jnp.arange(sampler.num_model_calls))
            flat = x.reshape(b_local * t_local, lh, lw,
                             cfg.unet.in_channels)
            pixels = self.vae.apply({"params": params["vae"]},
                                    flat / SD_LATENT_SCALE)
            images = decode_to_images(pixels)
            return images.reshape(b_local, t_local, height, width, 3)

        if self.mesh is not None:
            from jax.experimental.shard_map import shard_map
            from jax.sharding import PartitionSpec as P

            fn = jax.jit(shard_map(
                run, mesh=self.mesh,
                in_specs=(P(), P("dp"), P("dp"), P("dp"), P("dp"), P("dp")),
                out_specs=P("dp", "sp"),
                check_rep=False))
        else:
            fn = jax.jit(run)
        return fn

    # -- public API ------------------------------------------------------
    def generate(self, params: dict, prompts: list[str],
                 negative_prompts: list[str] | None, seeds: list[int], *,
                 num_frames: int = 16, width: int = 256, height: int = 256,
                 fps: int = 8, num_inference_steps: int = 20,
                 guidance_scale: float | list[float] = 9.0,
                 scheduler: str = "DDIM",
                 as_device: bool = False) -> np.ndarray:
        del fps  # container metadata, applied by the mp4 muxer
        batch = len(prompts)
        negs = negative_prompts or [""] * batch
        if len(negs) != batch or len(seeds) != batch:
            raise ValueError("prompts/negative_prompts/seeds must align")
        levels = len(self.config.unet.block_channels)
        granule = self.VAE_FACTOR * (2 ** (levels - 1))
        if height % granule or width % granule:
            raise ValueError(f"height/width must be multiples of {granule}")
        g = list(guidance_scale) if isinstance(guidance_scale, (list, tuple)) \
            else [guidance_scale] * batch
        ids_c = self.tokenizer.encode_batch(prompts)
        ids_u = self.tokenizer.encode_batch(negs)
        vocab = self.config.text.vocab_size
        if int(ids_c.max()) >= vocab or int(ids_u.max()) >= vocab:
            raise ValueError(
                f"tokenizer produced id >= vocab_size ({vocab}); "
                "tokenizer and text-encoder config are mismatched")
        seeds_arr = np.asarray(seeds, dtype=np.uint64)
        args = (jnp.asarray(ids_c), jnp.asarray(ids_u),
                jnp.asarray(g, jnp.float32),
                jnp.asarray(seeds_arr & 0xFFFFFFFF, jnp.uint32),
                jnp.asarray(seeds_arr >> np.uint64(32), jnp.uint32))
        # args before the lookup: the AOT tier keys against the exact
        # dispatch operands (docs/compile-cache.md)
        fn, warm, tag = self._get_bucket(
            batch, num_frames, height, width, num_inference_steps,
            scheduler, aot_args=lambda: (params, *args))
        from arbius_tpu.obs import timed_dispatch

        with timed_dispatch(warm, tag):
            out = fn(params, *args)
        if self.mesh is not None:
            from arbius_tpu.parallel import meshsolve

            # params ride the shard_map replicated (in_spec P()), so the
            # traffic model is the dp/sp output-gather + halo terms only
            # (out is uint8 already — no tp term exists for wire_dtype
            # to quantize; a future tp-sharded video path would thread
            # it like the image families do)
            meshsolve.record_bucket_estimate(
                self._coll_est,
                (batch, num_frames, height, width, num_inference_steps,
                 scheduler),
                self.mesh, out, batch, tag=tag)
        if as_device:
            # async-dispatch handle: the video runner's chunk pipeline
            # muxes the previous chunk while the chip crunches this one
            return out
        return np.asarray(out)


# mesh layouts this family ships (docs/multichip.md): the video path
# runs the whole denoise scan under shard_map — dp shards samples, sp
# shards frames (ring/ulysses temporal attention, ops/), tp rides the
# rule table. Unlike the image families there is no dp-only entry: the
# sp collectives are the reason this family meshes at all.
MESH_LAYOUTS: tuple[tuple[str, ...], ...] = (("dp", "sp", "tp"),)
# the shard_map hard-partitions the batch axis over dp — an indivisible
# canonical_batch is a boot error, not a replicate-degrade
# (meshsolve.check_mesh_contract reads this, like MESH_LAYOUTS, as data)
MESH_BATCH_HARD = True


def trace_specs():
    """graphlint trace specs (models/trace_specs.py): the UNet3D video
    bucket single-device AND under each shipped shard_map layout
    (MESH_LAYOUTS). The mesh variant traces over
    `parallel.abstract_mesh`, so the ring attention / halo exchange
    collectives land in the fingerprint with no physical devices (and
    no device ids) involved — mesh layout is part of the determinism
    class (docs/determinism.md) and therefore part of the golden key."""
    from arbius_tpu.models.trace_specs import TraceSpec
    from arbius_tpu.parallel import meshsolve
    from arbius_tpu.schedulers import sampler_tag

    def build_single(precision="bf16"):
        def build():
            p = Text2VideoPipeline(Text2VideoConfig.tiny(),
                                   precision=precision)
            return _bucket_args(p, batch=1, precision=precision)

        return build

    def build_sharded():
        p = Text2VideoPipeline(Text2VideoConfig.tiny(sp_axis="sp"),
                               mesh=meshsolve.golden_mesh(MESH_LAYOUTS[0]))
        return _bucket_args(p, batch=2)

    def _bucket_args(p, batch, precision="bf16"):
        shapes = jax.eval_shape(
            lambda: p.init_params(frames=2, height=64, width=64))
        if precision != "bf16":
            from arbius_tpu.quant import abstract_quantized

            shapes = abstract_quantized(shapes, precision)
        sds = jax.ShapeDtypeStruct
        length = p.config.text.max_length
        args = (shapes,
                sds((batch, length), jnp.int32),
                sds((batch, length), jnp.int32),
                sds((batch,), jnp.float32),
                sds((batch,), jnp.uint32), sds((batch,), jnp.uint32))
        return p.compiled_bucket(batch, 2, 64, 64, 2, "DDIM"), args

    bucket = f"f2.64x64.{sampler_tag('DDIM', 2)}"
    sharded_tag = meshsolve.golden_layout_tag(MESH_LAYOUTS[0])
    return [
        TraceSpec(model="zeroscopev2xl", entry="txt2vid",
                  bucket=f"b1.{bucket}", mesh="single", dtype="bfloat16",
                  build=build_single()),
        # quantized UNet3D/temporal-conv mode (docs/quantization.md)
        TraceSpec(model="zeroscopev2xl", entry="txt2vid",
                  bucket=f"b1.{bucket}", mesh="single", dtype="int8",
                  build=build_single("int8")),
        TraceSpec(model="zeroscopev2xl", entry="txt2vid",
                  bucket=f"b2.{bucket}", mesh=sharded_tag,
                  dtype="bfloat16", build=build_sharded),
    ]
