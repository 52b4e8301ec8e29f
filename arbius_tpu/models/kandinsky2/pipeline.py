"""Kandinsky-2 txt2img pipeline: text → prior → decoder → MOVQ, in-process.

The reference's flagship mining path (kandinsky2 is its only enabled model
AND the boot self-test, `miner/src/index.ts:844-877`, :984-1001) as one
jitted XLA program per shape bucket. Same determinism contract as SD-1.5:
the per-task seed keys every stochastic draw via fold_in, buckets run at a
canonical batch, so output bytes depend only on (model build, input, seed).

Stage wiring follows the published two-pipeline graph so converted
checkpoints drive it 1:1 (kandinsky2/convert.py):

  text tower (+ projection)  → hidden states, EOT-pooled projected embed
  prior                      → CLIP-image embedding (normalized space;
                               de-normalized via the checkpoint's
                               clip_mean/clip_std stats)
  decoder UNet               → epsilon (the learned-variance half of the
                               8-channel output is discarded — samplers
                               here are deterministic)
  MOVQ                       → pixels

Template parity (`templates/kandinsky2.json`): prompt, negative_prompt
(unused by the prior's CFG-zero branch but accepted), w/h ∈ {768, 1024},
num_inference_steps, guidance_scale, seed; output out-1.png.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from arbius_tpu.models.kandinsky2.decoder import DecoderConfig, DecoderUNet
from arbius_tpu.models.kandinsky2.movq import MOVQConfig, MOVQDecoder
from arbius_tpu.models.kandinsky2.prior import (
    PriorConfig,
    PriorTransformer,
    prior_sample,
    prior_stats_init,
)
from arbius_tpu.models.sd15.text_encoder import TextEncoder, TextEncoderConfig
from arbius_tpu.models.sd15.tokenizer import ByteTokenizer
from arbius_tpu.models.sd15.vae import decode_to_images
from arbius_tpu.schedulers import get_sampler


@dataclass(frozen=True)
class Kandinsky2Config:
    # defaults are the published checkpoint shapes: open_clip bigG text
    # tower (1280-wide, plain gelu) + 1280-dim image embedding space
    prior: PriorConfig = PriorConfig()
    decoder: DecoderConfig = DecoderConfig()
    movq: MOVQConfig = MOVQConfig()
    text: TextEncoderConfig = TextEncoderConfig(width=1280, layers=32,
                                                heads=20, act="gelu")
    prior_steps: int = 25

    @classmethod
    def tiny(cls) -> "Kandinsky2Config":
        dec = DecoderConfig.tiny()
        # exercise the learned-variance slice even at toy size
        dec = dataclasses.replace(
            dec, unet=dataclasses.replace(dec.unet, out_channels=8))
        return cls(prior=PriorConfig.tiny(), decoder=dec,
                   movq=MOVQConfig.tiny(), text=TextEncoderConfig.tiny(),
                   prior_steps=2)


class TextProjection(nn.Module):
    """CLIP text_projection: EOT-pooled hidden state → embedding space."""
    dim: int

    @nn.compact
    def __call__(self, x):
        return nn.Dense(self.dim, use_bias=False, dtype=jnp.float32,
                        name="proj")(x)


class Kandinsky2Pipeline:
    """Stateless module bundle + jitted per-bucket executables."""

    MOVQ_FACTOR = 8

    def __init__(self, config: Kandinsky2Config | None = None, tokenizer=None,
                 mesh=None, precision: str = "bf16"):
        from arbius_tpu.quant import validate_mode

        self.config = config or Kandinsky2Config()
        self.mesh = mesh
        # precision mode (docs/quantization.md): "bf16" is the historic
        # program byte-for-byte; int8/fp8 take the factory-quantized
        # weight tree and dequantize in-program — own golden per mode
        self.precision = validate_mode(precision)
        if self.config.text.max_length < self.config.prior.text_len:
            raise ValueError(
                f"text max_length ({self.config.text.max_length}) must be "
                f">= prior text_len ({self.config.prior.text_len})")
        self.tokenizer = tokenizer or ByteTokenizer(
            max_length=self.config.text.max_length)
        self.text_encoder = TextEncoder(self.config.text)
        self.text_projection = TextProjection(self.config.prior.clip_dim)
        self.prior = PriorTransformer(self.config.prior)
        self.decoder = DecoderUNet(self.config.decoder)
        self.movq = MOVQDecoder(self.config.movq)
        self._buckets: dict[tuple, object] = {}
        self._coll_est: dict[tuple, dict] = {}  # per-bucket traffic estimate

    # -- params ----------------------------------------------------------
    def init_params(self, seed: int = 0, height: int = 64, width: int = 64,
                    dtype=None) -> dict:
        """One jitted init program; `dtype` folds the weights cast in so
        the full f32 tree is never resident (the ~3B tree is 12 GB f32 —
        a separate cast program OOMs a 16 GB chip; fused, XLA frees each
        f32 leaf at its convert)."""
        cfg = self.config
        lh, lw = height // self.MOVQ_FACTOR, width // self.MOVQ_FACTOR

        def _init(key):
            k1, k2, k3, k4, k5 = jax.random.split(key, 5)
            ids = jnp.zeros((1, cfg.text.max_length), jnp.int32)
            tok = jnp.zeros((1, cfg.prior.text_len, cfg.text.width))
            pooled = jnp.zeros((1, cfg.prior.clip_dim))
            embed = jnp.zeros((1, cfg.prior.clip_dim))
            lat = jnp.zeros((1, lh, lw, cfg.decoder.unet.in_channels))
            return {
                "text": self.text_encoder.init(k1, ids)["params"],
                "text_proj": self.text_projection.init(
                    k5, jnp.zeros((1, cfg.text.width)))["params"],
                "prior": self.prior.init(k2, embed, jnp.zeros((1,)), tok,
                                         pooled)["params"],
                "prior_stats": prior_stats_init(None, (2, cfg.prior.clip_dim)),
                "decoder": self.decoder.init(k3, lat, jnp.zeros((1,)),
                                             embed)["params"],
                "movq": self.movq.init(k4, lat)["params"],
            }

        from arbius_tpu.utils import with_cast

        return jax.jit(with_cast(_init, dtype))(jax.random.PRNGKey(seed))

    def place_params(self, params: dict, tp_rules=None) -> dict:
        if self.mesh is None:
            return params
        from arbius_tpu.parallel import DEFAULT_TP_RULES, shard_params

        return shard_params(params, self.mesh,
                            tp_rules if tp_rules is not None else DEFAULT_TP_RULES)

    def _place_batch(self, *arrays):
        # meshsolve.shard_batch: dp when the batch divides, else
        # replicated (under-filled buckets idle dp lanes, never error)
        if self.mesh is None:
            return arrays
        from arbius_tpu.parallel import meshsolve

        return meshsolve.shard_batch(self.mesh, *arrays)

    # -- compiled bucket -------------------------------------------------
    def compiled_bucket(self, batch: int, height: int, width: int,
                        steps: int, scheduler: str):
        return self._get_bucket(batch, height, width, steps, scheduler)[0]

    def bucket_tag(self, batch: int, height: int, width: int, steps: int,
                   scheduler: str) -> str:
        """One definition of this family's executable-cache tag — the
        warm sets and the AOT disk-warm scan join on it
        (docs/compile-cache.md). Non-default precision modes suffix it
        (".int8"/".fp8") so a quantized bucket never shares a warm
        signal with its bf16 twin; bf16 tags stay byte-identical."""
        from arbius_tpu.quant import mode_tag

        return "kandinsky2." + ".".join(
            str(k) for k in (batch, height, width, steps, scheduler)) \
            + mode_tag(self.precision)

    def _get_bucket(self, batch: int, height: int, width: int,
                    steps: int, scheduler: str, aot_args=None):
        """(fn, warm, tag) — cache lookup reported through the
        jit-cache metrics (docs/observability.md); `aot_args` opts into
        the AOT disk tier (docs/compile-cache.md)."""
        from arbius_tpu.obs import jit_cache_get

        key = (batch, height, width, steps, scheduler)
        return jit_cache_get(
            self._buckets, key,
            lambda: self._build_bucket(batch, height, width, steps,
                                       scheduler),
            tag=self.bucket_tag(*key), aot_args=aot_args)

    def _build_bucket(self, batch: int, height: int, width: int,
                      steps: int, scheduler: str):
        cfg = self.config
        sampler = get_sampler(scheduler, steps)
        lh, lw = height // self.MOVQ_FACTOR, width // self.MOVQ_FACTOR
        in_ch = cfg.decoder.unet.in_channels
        lat_shape = (batch, lh, lw, in_ch)
        text_len = cfg.prior.text_len
        eos_id = self.tokenizer.eos_id
        precision = self.precision

        def run(params, ids, guidance, seeds_lo, seeds_hi):
            if precision != "bf16":
                from arbius_tpu.quant import dequantize_tree

                # int8/fp8 kernels → f32 via their f32 scales (GRAPH407
                # contract); guarded so bf16 stays byte-identical
                params = dequantize_tree(params)
            # the program's blocks (obs/blocks.py): names on the HLO's
            # op_name paths, no change to the program
            with jax.named_scope("text_tower"):
                states = self.text_encoder.apply({"params": params["text"]},
                                                 ids)
                # EOT pooling: hidden state at the first EOS position, then
                # the projection into embedding space (CLIP
                # *WithProjection heads)
                first_eos = jnp.argmax((ids == eos_id).astype(jnp.int32),
                                       axis=1)
                pooled_pre = states[jnp.arange(states.shape[0]), first_eos]
                pooled = self.text_projection.apply(
                    {"params": params["text_proj"]}, pooled_pre)
                # attention mask: real tokens up to and including the EOT
                positions = jnp.arange(ids.shape[1])[None, :]
                mask = (positions <= first_eos[:, None]).astype(jnp.float32)

            tok = states[:, :text_len]
            keys = jax.vmap(
                lambda lo, hi: jax.random.fold_in(jax.random.PRNGKey(lo), hi)
            )(seeds_lo, seeds_hi)
            g = guidance.astype(jnp.float32)

            with jax.named_scope("prior"):
                embed = prior_sample(self.prior, params["prior"], tok, pooled,
                                     keys, g, steps=cfg.prior_steps,
                                     text_mask=mask[:, :text_len],
                                     clip_stats=params["prior_stats"])

            x = jax.vmap(lambda k: jax.random.normal(
                k, lat_shape[1:], jnp.float32))(keys)
            x = x * sampler.init_noise_sigma
            zero_embed = jnp.zeros_like(embed)
            g4 = g[:, None, None, None]

            def body(carry, i):
                x, state = carry
                xin = jnp.concatenate([x, x], axis=0) * sampler.input_scale[i]
                t = jnp.full((2 * batch,), sampler.timesteps[i])
                emb2 = jnp.concatenate([zero_embed, embed], axis=0)
                out = self.decoder.apply({"params": params["decoder"]},
                                         xin, t, emb2)
                # learned-variance half (if present) is dropped: the
                # deterministic samplers never consume it
                eps = out.astype(jnp.float32)[..., :in_ch]
                eps_u, eps_c = jnp.split(eps, 2, axis=0)
                eps = eps_u + g4 * (eps_c - eps_u)
                noise = jax.vmap(lambda k: jax.random.normal(
                    jax.random.fold_in(k, i), lat_shape[1:], jnp.float32))(keys)
                x, state = sampler.step(i, x, eps, state, noise)
                return (x, state), None

            with jax.named_scope("unet"):
                (x, _), _ = jax.lax.scan(body, (x, sampler.init_carry(x)),
                                         jnp.arange(sampler.num_model_calls))
            with jax.named_scope("movq"):
                pixels = self.movq.apply({"params": params["movq"]}, x)
                return decode_to_images(pixels)

        if self.mesh is None:
            # the exact pre-mesh program: goldens pin this byte-for-byte
            fn = jax.jit(run)
        else:
            # GSPMD batch/output specs; params inherit their boot-time
            # rule-table placement (docs/multichip.md)
            from arbius_tpu.ops.flash import on_mesh
            from arbius_tpu.parallel import meshsolve

            spec, _ = meshsolve.batch_specs(self.mesh, batch)
            fn = jax.jit(on_mesh(run, self.mesh),
                         in_shardings=(None, spec(2), spec(1), spec(1),
                                       spec(1)),
                         out_shardings=spec(4))
        return fn

    # -- public API ------------------------------------------------------
    def generate(self, params: dict, prompts: list[str],
                 negative_prompts: list[str] | None, seeds: list[int], *,
                 width: int = 768, height: int = 768,
                 num_inference_steps: int = 50,
                 guidance_scale: float | list[float] = 4.0,
                 scheduler: str = "DDIM",
                 as_device: bool = False) -> np.ndarray:
        batch = len(prompts)
        if len(seeds) != batch:
            raise ValueError("prompts/seeds must align")
        levels = len(self.config.decoder.unet.block_channels)
        granule = self.MOVQ_FACTOR * (2 ** (levels - 1))
        if height % granule or width % granule:
            raise ValueError(f"height/width must be multiples of {granule}")
        g = list(guidance_scale) if isinstance(guidance_scale, (list, tuple)) \
            else [guidance_scale] * batch
        ids = self.tokenizer.encode_batch(prompts)
        vocab = self.config.text.vocab_size
        if int(ids.max()) >= vocab:
            raise ValueError(
                f"tokenizer produced id >= vocab_size ({vocab}); "
                "tokenizer and text-encoder config are mismatched")
        seeds_arr = np.asarray(seeds, dtype=np.uint64)
        args = self._place_batch(
            jnp.asarray(ids),
            jnp.asarray(g, jnp.float32),
            jnp.asarray(seeds_arr & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray(seeds_arr >> np.uint64(32), jnp.uint32),
        )
        # args before the lookup: the AOT tier keys against the exact
        # dispatch operands (docs/compile-cache.md)
        fn, warm, tag = self._get_bucket(
            batch, height, width, num_inference_steps, scheduler,
            aot_args=lambda: (params, *args))
        from arbius_tpu.obs import timed_dispatch

        with timed_dispatch(warm, tag):
            images = fn(params, *args)
        if self.mesh is not None:
            from arbius_tpu.parallel import meshsolve
            from arbius_tpu.quant import storage_dtype

            meshsolve.record_bucket_estimate(
                self._coll_est,
                (batch, height, width, num_inference_steps, scheduler),
                self.mesh, images, batch, params=params,
                wire_dtype=storage_dtype(self.precision)
                if self.precision != "bf16" else None, tag=tag)
        if as_device:
            # async-dispatch handle: the solver's chunk pipeline encodes
            # the previous chunk while the chip crunches this one
            return images
        return np.asarray(images)


# mesh layouts this family ships (docs/multichip.md): same table as
# SD-1.5 — dp-only is bit-identical to mesh-off, dp×tp (DEFAULT_TP_RULES
# over the decoder/prior attention + FF kernels) is its own determinism
# class. One graphlint golden per layout below.
MESH_LAYOUTS: tuple[tuple[str, ...], ...] = (("dp",), ("dp", "tp"))


def trace_specs():
    """graphlint trace specs (models/trace_specs.py): the whole
    text→prior→decoder→MOVQ bucket program — one jitted graph, so one
    fingerprint covers both published sub-pipelines — single-device and
    under each shipped mesh layout (MESH_LAYOUTS, traced over
    `parallel.abstract_mesh` so no devices are involved)."""
    from arbius_tpu.models.trace_specs import TraceSpec
    from arbius_tpu.parallel import meshsolve
    from arbius_tpu.schedulers import sampler_tag

    def build_bucket(axes=(), precision="bf16"):
        def build():
            from arbius_tpu.quant import abstract_quantized

            p = Kandinsky2Pipeline(Kandinsky2Config.tiny(),
                                   mesh=meshsolve.golden_mesh(axes),
                                   precision=precision)
            batch = 2 if axes else 1
            shapes = jax.eval_shape(
                lambda: p.init_params(height=64, width=64))
            if precision != "bf16":
                shapes = abstract_quantized(shapes, precision)
            sds = jax.ShapeDtypeStruct
            length = p.config.text.max_length
            args = (shapes, sds((batch, length), jnp.int32),
                    sds((batch,), jnp.float32),
                    sds((batch,), jnp.uint32), sds((batch,), jnp.uint32))
            return p.compiled_bucket(batch, 64, 64, 2, "DDIM"), args

        return build

    return [
        TraceSpec(model="kandinsky2", entry="txt2img",
                  bucket=f"b1.64x64.{sampler_tag('DDIM', 2)}",
                  mesh="single", dtype="bfloat16", build=build_bucket()),
        # quantized mode (docs/quantization.md): its own pinned class
        TraceSpec(model="kandinsky2", entry="txt2img",
                  bucket=f"b1.64x64.{sampler_tag('DDIM', 2)}",
                  mesh="single", dtype="int8",
                  build=build_bucket(precision="int8")),
    ] + [
        TraceSpec(model="kandinsky2", entry="txt2img",
                  bucket=f"b2.64x64.{sampler_tag('DDIM', 2)}",
                  mesh=meshsolve.golden_layout_tag(axes), dtype="bfloat16",
                  build=build_bucket(axes))
        for axes in MESH_LAYOUTS
    ]
