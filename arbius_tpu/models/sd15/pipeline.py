"""SD-1.5 txt2img pipeline — the anythingv3 execution path, in-process.

Replaces the reference's HTTP hop to a cog container
(`miner/src/index.ts:852-876`) with a jit-compiled XLA program per shape
bucket. Determinism root: the per-task seed (taskid2seed) feeds a JAX PRNG
key; init latents and every ancestral noise draw derive from it via fold_in,
so a task id always produces the same bytes on the same model build.

Batching: `generate` takes a batch of tasks sharing one shape bucket
(width, height, steps, scheduler are the bucket key; the template enums make
this a small finite set). Per-sample guidance scales and seeds vary freely
within a batch. The runtime layer (arbius_tpu/runtime) groups queued tasks
into buckets and shards the batch axis over the device mesh.

Determinism vs batching: a task's output bytes must not depend on which
other tasks happened to share its batch. XLA guarantees identical bits for
identical compiled programs, but batch size is part of the program — so the
runtime always pads a bucket to its CANONICAL batch size (dp_size × the
bucket's per-chip batch) with dummy samples rather than compiling per
occupancy. One program per bucket ⇒ one determinism class per bucket.
"""
from __future__ import annotations


from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from arbius_tpu.models.sd15.text_encoder import TextEncoder, TextEncoderConfig
from arbius_tpu.models.sd15.tokenizer import ByteTokenizer
from arbius_tpu.models.sd15.unet import UNet2DCondition, UNetConfig
from arbius_tpu.models.sd15.vae import (
    SD_LATENT_SCALE,
    VAEConfig,
    VAEDecoder,
    decode_to_images,
)
from arbius_tpu.schedulers import get_sampler


@dataclass(frozen=True)
class SD15Config:
    unet: UNetConfig = UNetConfig()
    vae: VAEConfig = VAEConfig()
    text: TextEncoderConfig = TextEncoderConfig()

    @classmethod
    def tiny(cls) -> "SD15Config":
        return cls(UNetConfig.tiny(), VAEConfig.tiny(), TextEncoderConfig.tiny())


class SD15Pipeline:
    """Stateless module bundle + jitted per-bucket executables."""

    VAE_FACTOR = 8

    def __init__(self, config: SD15Config | None = None, tokenizer=None,
                 mesh=None, precision: str = "bf16"):
        from arbius_tpu.quant import validate_mode

        self.config = config or SD15Config()
        self.mesh = mesh  # jax.sharding.Mesh with a 'dp' axis, or None
        # precision mode (docs/quantization.md): "bf16" is THIS
        # pipeline's historic program byte-for-byte; int8/fp8 expect
        # checkpoint weights quantized at load (factory) and dequantize
        # them inside the bucket program — each mode its own golden
        self.precision = validate_mode(precision)
        if self.config.text.width != self.config.unet.context_dim:
            raise ValueError(
                f"text encoder width ({self.config.text.width}) must equal "
                f"unet context_dim ({self.config.unet.context_dim})")
        self.tokenizer = tokenizer or ByteTokenizer(
            max_length=self.config.text.max_length)
        self.unet = UNet2DCondition(self.config.unet)
        self.vae = VAEDecoder(self.config.vae)
        self.text_encoder = TextEncoder(self.config.text)
        # per-instance executable cache: dies with the pipeline (an lru_cache
        # on the method would pin self in a class-global cache)
        self._buckets: dict[tuple, object] = {}
        self._coll_est: dict[tuple, dict] = {}  # per-bucket traffic estimate

    # -- params ----------------------------------------------------------
    def init_params(self, seed: int = 0, height: int = 64, width: int = 64,
                    dtype=None) -> dict:
        """Deterministic parameter init (stands in for converted weights).

        The whole init is one jitted XLA program so parameters materialize
        directly on the accelerator: eager flax `.init` dispatches hundreds
        of small ops one-by-one (each its own compile and launch), and
        host-side init would need a multi-GB host→HBM transfer
        afterwards. Same bits either way
        (JAX PRNG is algorithmically deterministic under jit).

        `dtype` folds the weights cast into the SAME program via
        utils.with_cast (HBM-peak rationale in its docstring)."""
        from arbius_tpu.utils import with_cast

        lh, lw = height // self.VAE_FACTOR, width // self.VAE_FACTOR

        return jax.jit(with_cast(self._init_fn(lh, lw), dtype))(
            jax.random.PRNGKey(seed))

    def _init_fn(self, lh: int, lw: int):
        def _init(key):
            k1, k2, k3 = jax.random.split(key, 3)
            latents = jnp.zeros((1, lh, lw, self.config.unet.in_channels))
            ids = jnp.zeros((1, self.config.text.max_length), jnp.int32)
            ctx = jnp.zeros(
                (1, self.config.text.max_length, self.config.unet.context_dim))
            return {
                "unet": self.unet.init(k1, latents, jnp.zeros((1,)), ctx)["params"],
                "vae": self.vae.init(k2, latents)["params"],
                "text": self.text_encoder.init(k3, ids)["params"],
            }

        return _init

    def init_params_placed(self, seed: int = 0, height: int = 64,
                           width: int = 64, tp_rules=None) -> dict:
        """Fused init + mesh placement: ONE jitted program whose
        out_shardings are the rule table's shardings, so parameters
        materialize directly in their sharded layout. The per-leaf
        device_put path (init then shard_params) dispatched ~700 host
        transfers and took minutes for the 860M tree on a 1-core host;
        this is one XLA program. Same bits as init_params (JAX PRNG is
        deterministic under jit regardless of sharding)."""
        if self.mesh is None:
            return self.init_params(seed=seed, height=height, width=width)
        from arbius_tpu.parallel import DEFAULT_TP_RULES, sharding_tree

        if tp_rules is None:
            tp_rules = DEFAULT_TP_RULES
        lh, lw = height // self.VAE_FACTOR, width // self.VAE_FACTOR
        init = self._init_fn(lh, lw)
        key = jax.random.PRNGKey(seed)
        shapes = jax.eval_shape(init, key)
        out = sharding_tree(shapes, self.mesh, tp_rules)
        return jax.jit(init, out_shardings=out)(key)

    def place_params(self, params: dict, tp_rules=None) -> dict:
        """Shard params onto self.mesh: TP kernels by rule (the family's
        DEFAULT_TP_RULES unless overridden), everything else replicated.
        On a tp=1 mesh the rules degrade to replication, so the default
        is always safe — and on tp>1 it is required (replicating every
        param would waste the tp axis entirely)."""
        if self.mesh is None:
            return params
        from arbius_tpu.parallel import DEFAULT_TP_RULES, shard_params

        if tp_rules is None:
            tp_rules = DEFAULT_TP_RULES
        return shard_params(params, self.mesh, tp_rules)

    def _place_batch(self, *arrays):
        """Shard batch-leading arrays over the dp axis of the mesh
        (meshsolve.shard_batch: replicates instead when the batch does
        not divide dp, so an under-filled bucket runs with idle dp lanes
        rather than erroring)."""
        if self.mesh is None:
            return arrays
        from arbius_tpu.parallel import meshsolve

        return meshsolve.shard_batch(self.mesh, *arrays)

    # -- compiled bucket -------------------------------------------------
    def _bucket_fn(self, batch: int, height: int, width: int,
                   steps: int, scheduler: str):
        return self._get_bucket(batch, height, width, steps, scheduler)[0]

    def bucket_tag(self, batch: int, height: int, width: int, steps: int,
                   scheduler: str) -> str:
        """The ONE definition of this family's executable-cache tag —
        the jit-cache warm set, the AOT cache's disk-warm scan, and the
        scheduler's cross-life warm boost all join on this string
        (docs/compile-cache.md), so it may never be rebuilt ad hoc.
        Non-default precision modes suffix the tag (".int8"/".fp8") —
        a quantized bucket and its bf16 twin are different programs and
        must never share a warm signal; bf16 tags are byte-identical to
        the pre-quant node."""
        from arbius_tpu.quant import mode_tag

        return "sd15." + ".".join(
            str(k) for k in (batch, height, width, steps, scheduler)) \
            + mode_tag(self.precision)

    def _get_bucket(self, batch: int, height: int, width: int,
                    steps: int, scheduler: str, aot_args=None):
        """(fn, warm, tag) — the cached bucket executable, whether it
        was already built, and its cache tag; the lookup reports
        through the jit-cache metrics (docs/observability.md) so
        warm-executable reuse is fleet-visible. `aot_args` (the exact
        dispatch arguments, as a thunk) opts the lookup into the AOT
        disk tier when one is installed (docs/compile-cache.md)."""
        from arbius_tpu.obs import jit_cache_get

        key = (batch, height, width, steps, scheduler)
        return jit_cache_get(
            self._buckets, key,
            lambda: self._build_bucket(batch, height, width, steps,
                                       scheduler),
            tag=self.bucket_tag(*key), aot_args=aot_args)

    def _build_bucket(self, batch: int, height: int, width: int,
                      steps: int, scheduler: str):
        sampler = get_sampler(scheduler, steps)
        lh, lw = height // self.VAE_FACTOR, width // self.VAE_FACTOR
        lat_shape = (batch, lh, lw, self.config.unet.in_channels)
        precision = self.precision

        def run(params, ids_cond, ids_uncond, guidance, seeds_lo, seeds_hi):
            if precision != "bf16":
                from arbius_tpu.quant import dequantize_tree

                # int8/fp8 kernels → f32 via their explicit f32 scales
                # (GRAPH407 contract); the modules then cast to their
                # bf16 compute dtype exactly as with f32 checkpoints.
                # Guarded so the bf16 program stays byte-identical.
                params = dequantize_tree(params)
            # the program's blocks (obs/blocks.py): names on the HLO's
            # op_name paths, no change to the program
            with jax.named_scope("text_encoder"):
                ctx_c = self.text_encoder.apply({"params": params["text"]},
                                                ids_cond)
                ctx_u = self.text_encoder.apply({"params": params["text"]},
                                                ids_uncond)
                context = jnp.concatenate([ctx_u, ctx_c], axis=0)  # [2B, L, D]

            # full 53-bit taskid2seed space: low word keys, high word folded in
            keys = jax.vmap(
                lambda lo, hi: jax.random.fold_in(jax.random.PRNGKey(lo), hi)
            )(seeds_lo, seeds_hi)
            x = jax.vmap(
                lambda k: jax.random.normal(k, lat_shape[1:], jnp.float32))(keys)
            x = x * sampler.init_noise_sigma
            g = guidance.astype(jnp.float32)[:, None, None, None]

            def body(carry, i):
                x, state = carry
                xin = jnp.concatenate([x, x], axis=0) * sampler.input_scale[i]
                t = jnp.full((2 * batch,), sampler.timesteps[i])
                eps = self.unet.apply({"params": params["unet"]}, xin, t, context)
                eps_u, eps_c = jnp.split(eps.astype(jnp.float32), 2, axis=0)
                eps = eps_u + g * (eps_c - eps_u)
                noise = jax.vmap(lambda k: jax.random.normal(
                    jax.random.fold_in(k, i), lat_shape[1:], jnp.float32))(keys)
                x, state = sampler.step(i, x, eps, state, noise)
                return (x, state), None

            with jax.named_scope("unet"):
                (x, _), _ = jax.lax.scan(
                    body, (x, sampler.init_carry(x)),
                    jnp.arange(sampler.num_model_calls))
            with jax.named_scope("vae"):
                pixels = self.vae.apply({"params": params["vae"]},
                                        x / SD_LATENT_SCALE)
                return decode_to_images(pixels)

        if self.mesh is None:
            # the exact pre-mesh program: goldens pin this byte-for-byte
            fn = jax.jit(run)
        else:
            # GSPMD (docs/multichip.md): batch args dp-sharded, params
            # inherit their boot-time rule-table placement (None =
            # unspecified), output left dp-sharded — the gather happens
            # host-side in canonical order. XLA inserts the tp
            # collectives from the param shardings.
            from arbius_tpu.ops.flash import on_mesh
            from arbius_tpu.parallel import meshsolve

            spec, _ = meshsolve.batch_specs(self.mesh, batch)
            fn = jax.jit(
                on_mesh(run, self.mesh),
                in_shardings=(None, spec(2), spec(2), spec(1), spec(1),
                              spec(1)),
                out_shardings=spec(4))
        return fn

    # -- public API ------------------------------------------------------
    def compiled_bucket(self, batch: int, height: int, width: int,
                        steps: int, scheduler: str):
        """Public handle on a bucket executable: the jittable solve-step fn
        with signature (params, ids_cond, ids_uncond, guidance, seeds_lo,
        seeds_hi) -> uint8 images. Contract for external drivers."""
        return self._bucket_fn(batch, height, width, steps, scheduler)

    def generate(
        self,
        params: dict,
        prompts: list[str],
        negative_prompts: list[str],
        seeds: list[int],
        *,
        width: int = 512,
        height: int = 512,
        num_inference_steps: int = 20,
        guidance_scale: float | list[float] = 7.5,
        scheduler: str = "DDIM",
        as_device: bool = False,
    ) -> np.ndarray:
        """Run a shape bucket; returns uint8 images [B, H, W, 3].

        `as_device=True` returns the jax.Array WITHOUT forcing the
        device→host transfer: JAX dispatch is asynchronous, so the caller
        can queue the next bucket's dispatch and convert this result
        while the chip crunches it (the solver's codec/CID overlap —
        node/solver.py). Same bits either way."""
        batch = len(prompts)
        if len(negative_prompts) != batch or len(seeds) != batch:
            raise ValueError("prompts/negative_prompts/seeds must align")
        # latents must survive the UNet's downsample pyramid and re-align
        # with every skip connection on the way up
        levels = len(self.config.unet.block_channels)
        granule = self.VAE_FACTOR * (2 ** (levels - 1))
        if height % granule or width % granule:
            raise ValueError(f"height/width must be multiples of {granule}")
        g = list(guidance_scale) if isinstance(guidance_scale, (list, tuple)) \
            else [guidance_scale] * batch
        if len(g) != batch:
            raise ValueError("guidance_scale list must align with prompts")
        ids_c = self.tokenizer.encode_batch(prompts)
        ids_u = self.tokenizer.encode_batch(negative_prompts)
        vocab = self.config.text.vocab_size
        if int(ids_c.max()) >= vocab or int(ids_u.max()) >= vocab:
            raise ValueError(
                f"tokenizer produced id >= vocab_size ({vocab}); "
                "tokenizer and text-encoder config are mismatched")
        seeds_arr = np.asarray(seeds, dtype=np.uint64)
        args = self._place_batch(
            jnp.asarray(ids_c),
            jnp.asarray(ids_u),
            jnp.asarray(g, jnp.float32),
            jnp.asarray(seeds_arr & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray(seeds_arr >> np.uint64(32), jnp.uint32),
        )
        # args are built BEFORE the bucket lookup so the AOT tier can
        # key (and compile) against the exact dispatch operands
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
            return images
        return np.asarray(images)


# mesh layouts this family ships (docs/multichip.md): dp-only scales
# tasks bit-identically; dp×tp splits attention/MLP kernels via
# DEFAULT_TP_RULES and is its own determinism class. Each layout gets
# its own graphlint golden below — layout is data, like the rule table.
MESH_LAYOUTS: tuple[tuple[str, ...], ...] = (("dp",), ("dp", "tp"))


def trace_specs():
    """graphlint trace specs (models/trace_specs.py): the anythingv3
    bucket program at tiny topology, in both compute dtypes and under
    the two scheduler shapes (plain + ancestral-noise), all abstract —
    params via eval_shape, no weights, CPU-traceable in seconds. Each
    shipped mesh layout (MESH_LAYOUTS) traces over
    `parallel.abstract_mesh`, so the GSPMD sharding annotations land in
    the per-layout fingerprint with no physical devices involved."""
    import dataclasses

    from arbius_tpu.models.trace_specs import TraceSpec
    from arbius_tpu.parallel import meshsolve
    from arbius_tpu.schedulers import sampler_tag

    def build_bucket(dtype: str, steps: int, scheduler: str, axes=(),
                     precision: str = "bf16"):
        def build():
            from arbius_tpu.quant import abstract_quantized

            cfg = SD15Config.tiny()
            if dtype != "bfloat16":
                cfg = SD15Config(
                    unet=dataclasses.replace(cfg.unet, dtype=dtype),
                    vae=dataclasses.replace(cfg.vae, dtype=dtype),
                    text=dataclasses.replace(cfg.text, dtype=dtype))
            p = SD15Pipeline(cfg, mesh=meshsolve.golden_mesh(axes),
                             precision=precision)
            batch = 2 if axes else 1
            lh = 64 // p.VAE_FACTOR
            shapes = jax.eval_shape(p._init_fn(lh, lh),
                                    jax.random.PRNGKey(0))
            if precision != "bf16":
                # the quantized checkpoint tree: int8/fp8 kernels with
                # explicit f32 scales — what factory hands the runner
                shapes = abstract_quantized(shapes, precision)
            sds = jax.ShapeDtypeStruct
            length = cfg.text.max_length
            args = (shapes,
                    sds((batch, length), jnp.int32),
                    sds((batch, length), jnp.int32),
                    sds((batch,), jnp.float32),
                    sds((batch,), jnp.uint32), sds((batch,), jnp.uint32))
            return p.compiled_bucket(batch, 64, 64, steps, scheduler), args

        return build

    return [
        # quantized modes (docs/quantization.md): the anythingv3 bucket
        # with int8/fp8 checkpoint weights dequantized in-program — each
        # mode a pinned determinism class, keyed like a compute dtype
        TraceSpec(model="anythingv3", entry="txt2img",
                  bucket=f"b1.64x64.{sampler_tag('DDIM', 2)}",
                  mesh="single", dtype=mode,
                  build=build_bucket("bfloat16", 2, "DDIM",
                                     precision=mode))
        for mode in ("int8", "fp8")
    ] + [
        TraceSpec(model="anythingv3", entry="txt2img",
                  bucket=f"b1.64x64.{sampler_tag('DDIM', 2)}",
                  mesh="single", dtype=dtype,
                  build=build_bucket(dtype, 2, "DDIM"))
        for dtype in ("bfloat16", "float32")
    ] + [
        TraceSpec(model="anythingv3", entry="txt2img",
                  bucket=f"b1.64x64.{sampler_tag('K_EULER_ANCESTRAL', 2)}",
                  mesh="single", dtype="bfloat16",
                  build=build_bucket("bfloat16", 2, "K_EULER_ANCESTRAL")),
    ] + [
        TraceSpec(model="anythingv3", entry="txt2img",
                  bucket=f"b2.64x64.{sampler_tag('DDIM', 2)}",
                  mesh=meshsolve.golden_layout_tag(axes), dtype="bfloat16",
                  build=build_bucket("bfloat16", 2, "DDIM", axes))
        for axes in MESH_LAYOUTS
    ] + [
        # int8 × dp·tp: quantized kernels ride the tp rule table as
        # 1-byte shards — the layout (wire-byte win) the quantized
        # collective accounting meters (docs/quantization.md)
        TraceSpec(model="anythingv3", entry="txt2img",
                  bucket=f"b2.64x64.{sampler_tag('DDIM', 2)}",
                  mesh=meshsolve.golden_layout_tag(("dp", "tp")),
                  dtype="int8",
                  build=build_bucket("bfloat16", 2, "DDIM", ("dp", "tp"),
                                     "int8")),
    ]
