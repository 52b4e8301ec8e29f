"""textgen pipeline — deterministic LLM text serving, in-process.

The repo's first non-image family: a decoder-only LM whose WHOLE
generation — prefill, the autoregressive decode loop, and sampling —
is ONE jitted XLA program per shape bucket. The decode loop is a
`lax.scan` with the per-layer KV caches as explicit carry: no Python
step loop, no per-token dispatch, no retrace per length.

Shape buckets (docs/text-serving.md): a bucket is
(batch, prompt_bucket, decode_bucket, sampler). Prompts pad to the
prompt bucket edge with eos (ByteTokenizer discipline, NO attention
mask — padding is model input, exactly like image padding pixels), and
the loop always runs the full decode bucket; the solver truncates
host-side to each task's requested budget. Truncation is sound because
generation is causally prefix-stable: token i depends only on tokens
< i, so a longer decode bucket yields byte-identical prefixes. The
PROMPT bucket edge, by contrast, IS bytes-affecting (it changes the
positions everything sits at), which is why bucket edges are fleet-wide
determinism-class config (MiningConfig `textgen`), like canonical_batch.

Sampling: greedy is pure argmax over f32 logits. Seeded top-k restricts
to the `top_k` highest logits and draws categorically from a per-task
key chain — fold_in(PRNGKey(seed_lo), seed_hi) then fold_in(key, step)
per position, the same 53-bit taskid2seed threading the image families
use, so a task id always samples the same tokens on the same build.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from arbius_tpu.models.sd15.tokenizer import ByteTokenizer
from arbius_tpu.models.textgen.model import TextGenConfig, TextGenModel

# the deterministic byte tokenizer's control ids: raw UTF-8 bytes are
# ids 0..255, bos/eos sit above them (factory's tiny text tower uses
# the same pair)
BOS_ID = 257
EOS_ID = 258

SAMPLERS = ("greedy", "top_k")


def _fold_keys(seeds_lo, seeds_hi):
    """Per-task PRNG keys from the split 53-bit task seed: low word
    keys, high word folded in — identical derivation to the image
    pipelines, so seed handling stays one audited pattern."""
    return jax.vmap(
        lambda lo, hi: jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    )(seeds_lo, seeds_hi)


def tokens_to_bytes(ids, limit: int, eos_id: int = EOS_ID) -> bytes:
    """Host-side detokenize: the first `limit` generated ids, stopped
    at the first eos, non-byte ids (bos, unused vocab tail) dropped —
    the mapping must be total over anything the model can emit."""
    out = bytearray()
    for tok in np.asarray(ids)[:limit]:
        tok = int(tok)
        if tok == eos_id:
            break
        if 0 <= tok < 256:
            out.append(tok)
    return bytes(out)


class TextGenPipeline:
    """Stateless module bundle + jitted per-bucket executables."""

    BOS_ID = BOS_ID
    EOS_ID = EOS_ID
    # the bucket tag's prefix; a family built on this pipeline (the
    # bucket policy, decode loop, samplers and seed chain are shared —
    # models/trinity) names its own
    FAMILY = "textgen"

    def __init__(self, config: TextGenConfig | None = None, mesh=None,
                 precision: str = "bf16",
                 prompt_buckets: tuple = (32, 64),
                 decode_buckets: tuple = (16, 32),
                 top_k: int = 8):
        from arbius_tpu.quant import validate_mode

        self.config = config or TextGenConfig()
        self.mesh = mesh  # jax.sharding.Mesh with a 'dp' axis, or None
        self.precision = validate_mode(precision)
        self.prompt_buckets = tuple(sorted(int(b) for b in prompt_buckets))
        self.decode_buckets = tuple(sorted(int(b) for b in decode_buckets))
        if not self.prompt_buckets or not self.decode_buckets:
            raise ValueError("prompt_buckets and decode_buckets must be "
                             "non-empty")
        if self.prompt_buckets[0] < 3:
            raise ValueError("prompt bucket edges must be >= 3 "
                             "(bos + at least one byte + eos)")
        if self.decode_buckets[0] < 1:
            raise ValueError("decode bucket edges must be >= 1")
        need = self.prompt_buckets[-1] + self.decode_buckets[-1]
        if need > self.config.max_positions:
            raise ValueError(
                f"bucket edges need {need} positions but the model tops "
                f"out at {self.config.max_positions}")
        self.top_k = int(top_k)
        if not 1 <= self.top_k <= self.config.vocab_size:
            raise ValueError(
                f"top_k ({self.top_k}) must be in [1, vocab_size]")
        self.model = self._make_model()
        # per-instance executable cache (same rationale as sd15)
        self._buckets: dict[tuple, object] = {}
        self._coll_est: dict[tuple, dict] = {}
        self._tokenizers: dict[int, ByteTokenizer] = {}

    # -- the model behind the loop (what a family overrides) ---------------
    def _make_model(self):
        return TextGenModel(self.config)

    def _prefill(self, params, ids, total: int):
        """(params, ids[B, P], total) → (logits[B, V] f32 at the last
        prompt position, the decode loop's carry)."""
        return self.model.apply({"params": params}, ids, total,
                                method=TextGenModel.prefill)

    def _decode(self, params, tok, carry, pos):
        """One step at `pos` → (next-position logits, carry)."""
        return self.model.apply({"params": params}, tok, carry, pos,
                                method=TextGenModel.decode)

    def _outputs(self, tokens, carry):
        """What a bucket program returns, from the tokens [B, T] and
        the loop's final carry: the tokens alone here."""
        return tokens

    def kv_rows(self, prompt_bucket: int, decode_bucket: int) -> tuple:
        """(cache rows the carry holds for one sequence, summed over
        layers; what it would hold with every layer at full length)."""
        rows = self.config.layers * (prompt_bucket + decode_bucket)
        return rows, rows

    def attn_kernel(self, batch: int, prompt_bucket: int) -> tuple:
        """(prefill attention calls a bucket serves with a Pallas kernel,
        key blocks those calls walk, blocks of their unmasked grids at
        the same tiles): none here, prefill attention is XLA's."""
        return 0, 0, 0

    def bucket_attrs(self, batch: int, prompt_bucket: int,
                     decode_bucket: int) -> dict:
        """What the runner's `text.bucket` span says of a bucket beside
        its edges and batch (docs/observability.md): static, from the
        shapes — here the cache rows and the prefill kernel's counts; a
        family whose cache or attention is of another kind states its
        own quantities."""
        held, full = self.kv_rows(prompt_bucket, decode_bucket)
        calls, blocks, dense = self.attn_kernel(batch, prompt_bucket)
        return {"kv_rows": held, "kv_rows_full": full,
                "attn_kernel_calls": calls, "attn_blocks": blocks,
                "attn_blocks_dense": dense}

    # -- bucket policy ---------------------------------------------------
    def prompt_bucket_for(self, prompt: str) -> int:
        """Smallest configured prompt edge that fits bos+bytes+eos;
        over-long prompts truncate into the top edge (the tokenizer's
        deterministic truncation, not an error — mirrors the reference
        miner accepting arbitrary prompt strings)."""
        need = len(str(prompt).encode("utf-8")) + 2
        for edge in self.prompt_buckets:
            if need <= edge:
                return edge
        return self.prompt_buckets[-1]

    def decode_bucket_for(self, max_new_tokens: int) -> int:
        """Smallest configured decode edge covering the requested
        budget; oversized budgets clamp to the top edge (the config
        cap keeps them unreachable through hydration)."""
        n = max(1, int(max_new_tokens))
        for edge in self.decode_buckets:
            if n <= edge:
                return edge
        return self.decode_buckets[-1]

    def _tokenizer(self, prompt_bucket: int) -> ByteTokenizer:
        tok = self._tokenizers.get(prompt_bucket)
        if tok is None:
            tok = ByteTokenizer(max_length=prompt_bucket,
                                bos_id=self.BOS_ID, eos_id=self.EOS_ID)
            self._tokenizers[prompt_bucket] = tok
        return tok

    # -- params ----------------------------------------------------------
    def _init_fn(self):
        p = self.prompt_buckets[0]

        def _init(key):
            ids = jnp.zeros((1, p), jnp.int32)
            # prefill touches every parameter decode reads (shared
            # setup-style submodules), so one init covers both methods
            return self.model.init(key, ids, p + 1,
                                   method=TextGenModel.prefill)["params"]

        return _init

    def init_params(self, seed: int = 0, dtype=None, **_unused) -> dict:
        """Deterministic parameter init as ONE jitted program (same
        dispatch rationale as SD15Pipeline.init_params)."""
        from arbius_tpu.utils import with_cast

        return jax.jit(with_cast(self._init_fn(), dtype))(
            jax.random.PRNGKey(seed))

    def init_params_placed(self, seed: int = 0, tp_rules=None,
                           **_unused) -> dict:
        """Fused init + mesh placement (one program, sharded outputs);
        on this family's dp-only layouts the rule table degrades to
        replication, which is exactly right."""
        if self.mesh is None:
            return self.init_params(seed=seed)
        from arbius_tpu.parallel import DEFAULT_TP_RULES, sharding_tree

        if tp_rules is None:
            tp_rules = DEFAULT_TP_RULES
        init = self._init_fn()
        key = jax.random.PRNGKey(seed)
        shapes = jax.eval_shape(init, key)
        out = sharding_tree(shapes, self.mesh, tp_rules)
        return jax.jit(init, out_shardings=out)(key)

    def place_params(self, params: dict, tp_rules=None) -> dict:
        if self.mesh is None:
            return params
        from arbius_tpu.parallel import DEFAULT_TP_RULES, shard_params

        if tp_rules is None:
            tp_rules = DEFAULT_TP_RULES
        return shard_params(params, self.mesh, tp_rules)

    def _place_batch(self, *arrays):
        if self.mesh is None:
            return arrays
        from arbius_tpu.parallel import meshsolve

        return meshsolve.shard_batch(self.mesh, *arrays)

    # -- compiled bucket -------------------------------------------------
    def bucket_tag(self, batch: int, prompt_bucket: int,
                   decode_bucket: int, sampler: str) -> str:
        """The ONE definition of this family's executable-cache tag
        (docs/compile-cache.md) — jit-cache warm set, AOT disk scan and
        scheduler warm boost all join on it. Sequence edges and the
        sampler are program shape, so they are in the tag; precision
        modes suffix it exactly like the image families."""
        from arbius_tpu.quant import mode_tag

        return self.FAMILY + "." + ".".join(
            str(k) for k in (batch, prompt_bucket, decode_bucket,
                             sampler)) + mode_tag(self.precision)

    def _get_bucket(self, batch: int, prompt_bucket: int,
                    decode_bucket: int, sampler: str, aot_args=None):
        from arbius_tpu.obs import jit_cache_get

        key = (batch, prompt_bucket, decode_bucket, sampler)
        return jit_cache_get(
            self._buckets, key,
            lambda: self._build_bucket(batch, prompt_bucket,
                                       decode_bucket, sampler),
            tag=self.bucket_tag(*key), aot_args=aot_args)

    def _sampler_fn(self, sampler: str):
        """(logits[B, V] f32, keys[B], step) → int32 token ids [B].
        Greedy ignores the keys (argmax is seed-free); seeded top-k
        draws categorically over the k highest logits with the per-task
        key folded by step — PRNG threaded from inputs end to end
        (GRAPH406), never a literal key."""
        if sampler == "greedy":
            def sample(logits, keys, step):
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return sample
        top_k = self.top_k

        def sample(logits, keys, step):
            def one(key, row):
                vals, idx = jax.lax.top_k(row, top_k)
                choice = jax.random.categorical(
                    jax.random.fold_in(key, step), vals)
                return idx[choice]
            return jax.vmap(one)(keys, logits).astype(jnp.int32)
        return sample

    def _decode_loop(self, prompt_bucket: int, decode_bucket: int,
                     sampler: str):
        """The decode-loop body shared by the composed bucket program
        and the separately-goldened decode trace: lax.scan over steps
        1..T-1 with (kv, last_token) as carry. Step i embeds t_{i-1}
        at position P+i-1 and samples t_i; t0 (sampled from prefill's
        logits) rides in as the carry seed. One token a step; a family
        whose step yields more (models/joyai_flash: a drafted token
        verified beside the one before it) overrides this with a loop
        of its own over per-row positions."""
        p, t = prompt_bucket, decode_bucket
        sample = self._sampler_fn(sampler)

        def loop(params, kv, t0, keys):
            def body(carry, i):
                kv, tok = carry
                logits, kv = self._decode(params, tok, kv, p + i - 1)
                nxt = sample(logits, keys, i)
                return (kv, nxt), nxt

            (kv, _), rest = jax.lax.scan(body, (kv, t0),
                                         jnp.arange(1, t))
            return self._outputs(jnp.concatenate(
                [t0[:, None], jnp.moveaxis(rest, 0, 1)], axis=1), kv)

        return loop

    def prefill_program(self, batch: int, prompt_bucket: int,
                        decode_bucket: int):
        """The prefill determinism class, jitted standalone for its
        graphlint golden: (params, ids[B, P]) → (last-position logits,
        per-layer KV caches at the bucket's full length)."""
        total = prompt_bucket + decode_bucket

        def pre(params, ids):
            return self._prefill(params, ids, total)

        return jax.jit(pre)

    def decode_program(self, batch: int, prompt_bucket: int,
                       decode_bucket: int, sampler: str):
        """The decode-loop determinism class, jitted standalone for its
        graphlint golden: (params, kv, t0, seeds_lo, seeds_hi) →
        int32 tokens [B, T]."""
        if sampler not in SAMPLERS:
            raise ValueError(f"sampler must be one of {SAMPLERS}")
        loop = self._decode_loop(prompt_bucket, decode_bucket, sampler)

        def dec(params, kv, t0, seeds_lo, seeds_hi):
            return loop(params, kv, t0, _fold_keys(seeds_lo, seeds_hi))

        return jax.jit(dec)

    def _build_bucket(self, batch: int, prompt_bucket: int,
                      decode_bucket: int, sampler: str):
        p, t = prompt_bucket, decode_bucket
        total = p + t
        precision = self.precision
        sample = self._sampler_fn(sampler)
        loop = self._decode_loop(p, t, sampler)

        def run(params, ids, seeds_lo, seeds_hi):
            if precision != "bf16":
                from arbius_tpu.quant import dequantize_tree

                # int8/fp8 checkpoint kernels → f32 via explicit f32
                # scales (GRAPH407); guarded so the bf16 program stays
                # byte-identical to a never-quantized build
                params = dequantize_tree(params)
            keys = _fold_keys(seeds_lo, seeds_hi)
            # the program's blocks (obs/blocks.py): names on the HLO's
            # op_name paths, no change to the program
            with jax.named_scope("prefill"):
                logits0, kv = self._prefill(params, ids, total)
            with jax.named_scope("decode"):
                t0 = sample(logits0, keys, 0)
                return loop(params, kv, t0, keys)

        if self.mesh is None:
            return jax.jit(run)
        # dp-only GSPMD: batch args dp-sharded, params replicated by
        # their boot placement, tokens gathered host-side in canonical
        # order (docs/multichip.md)
        from arbius_tpu.parallel import meshsolve

        spec, _ = meshsolve.batch_specs(self.mesh, batch)
        return jax.jit(run,
                       in_shardings=(None, spec(2), spec(1), spec(1)),
                       out_shardings=spec(2))

    # -- public API ------------------------------------------------------
    def compiled_bucket(self, batch: int, prompt_bucket: int,
                        decode_bucket: int, sampler: str):
        """Public handle on a bucket executable: (params, ids[B, P],
        seeds_lo, seeds_hi) → int32 tokens [B, T]. Contract for
        external drivers and the trace specs."""
        return self._get_bucket(batch, prompt_bucket, decode_bucket,
                                sampler)[0]

    def generate(
        self,
        params: dict,
        prompts: list[str],
        seeds: list[int],
        *,
        prompt_bucket: int,
        decode_bucket: int,
        sampler: str = "greedy",
        as_device: bool = False,
    ):
        """Run a sequence bucket; returns int32 token ids [B, T] (or
        whatever the family's `_outputs` makes of them).

        `as_device=True` keeps the jax.Array un-transferred so the
        solver can overlap the next dispatch with detokenize/CID work,
        exactly like the image families. Same bits either way."""
        batch = len(prompts)
        if len(seeds) != batch:
            raise ValueError("prompts/seeds must align")
        if sampler not in SAMPLERS:
            raise ValueError(f"sampler must be one of {SAMPLERS}")
        p, t = int(prompt_bucket), int(decode_bucket)
        if p not in self.prompt_buckets:
            raise ValueError(
                f"prompt_bucket {p} is not a configured edge "
                f"{self.prompt_buckets}")
        if t not in self.decode_buckets:
            raise ValueError(
                f"decode_bucket {t} is not a configured edge "
                f"{self.decode_buckets}")
        ids = self._tokenizer(p).encode_batch([str(x) for x in prompts])
        seeds_arr = np.asarray(seeds, dtype=np.uint64)
        args = self._place_batch(
            jnp.asarray(ids),
            jnp.asarray(seeds_arr & 0xFFFFFFFF, jnp.uint32),
            jnp.asarray(seeds_arr >> np.uint64(32), jnp.uint32),
        )
        # args before lookup: the AOT tier keys on exact operands
        fn, warm, tag = self._get_bucket(
            batch, p, t, sampler, aot_args=lambda: (params, *args))
        from arbius_tpu.obs import timed_dispatch

        with timed_dispatch(warm, tag):
            tokens = fn(params, *args)
        if self.mesh is not None:
            from arbius_tpu.parallel import meshsolve
            from arbius_tpu.quant import storage_dtype

            meshsolve.record_bucket_estimate(
                self._coll_est, (batch, p, t, sampler), self.mesh,
                tokens, batch, params=params,
                wire_dtype=storage_dtype(self.precision)
                if self.precision != "bf16" else None, tag=tag)
        if as_device:
            return tokens
        return jax.tree_util.tree_map(np.asarray, tokens)


# dp-only for now: tokens scale bit-identically over the batch axis;
# a tp split of the decode loop would be a new determinism class and
# ships only with its own golden (docs/multichip.md)
MESH_LAYOUTS: tuple[tuple[str, ...], ...] = (("dp",),)


def trace_specs():
    """graphlint trace specs: prefill and the decode loop goldened as
    SEPARATE determinism classes (docs/text-serving.md), plus the
    composed bucket program single/dp2 and int8 — all abstract (params
    via eval_shape, KV shapes via eval_shape over the prefill program),
    CPU-traceable in seconds."""
    from arbius_tpu.models.trace_specs import TraceSpec
    from arbius_tpu.parallel import meshsolve

    P, T = 8, 4  # tiny trace bucket: topology is what the golden pins

    def make_pipe(axes=(), precision="bf16"):
        return TextGenPipeline(TextGenConfig.tiny(),
                               mesh=meshsolve.golden_mesh(axes),
                               precision=precision,
                               prompt_buckets=(P,), decode_buckets=(T,),
                               top_k=4)

    def abstract(pipe, batch, precision="bf16"):
        shapes = jax.eval_shape(pipe._init_fn(), jax.random.PRNGKey(0))
        if precision != "bf16":
            from arbius_tpu.quant import abstract_quantized

            shapes = abstract_quantized(shapes, precision)
        sds = jax.ShapeDtypeStruct
        return (shapes, sds((batch, P), jnp.int32),
                sds((batch,), jnp.uint32), sds((batch,), jnp.uint32))

    def build_prefill():
        pipe = make_pipe()
        shapes, ids, _, _ = abstract(pipe, 1)
        return pipe.prefill_program(1, P, T), (shapes, ids)

    def build_decode(sampler):
        def build():
            pipe = make_pipe()
            shapes, ids, lo, hi = abstract(pipe, 1)
            _, kv = jax.eval_shape(pipe.prefill_program(1, P, T),
                                   shapes, ids)
            t0 = jax.ShapeDtypeStruct((1,), jnp.int32)
            return (pipe.decode_program(1, P, T, sampler),
                    (shapes, kv, t0, lo, hi))

        return build

    def build_generate(axes=(), precision="bf16", sampler="greedy"):
        def build():
            pipe = make_pipe(axes, precision)
            batch = 2 if axes else 1
            shapes, ids, lo, hi = abstract(pipe, batch, precision)
            return (pipe.compiled_bucket(batch, P, T, sampler),
                    (shapes, ids, lo, hi))

        return build

    bucket = f"b1.p{P}.t{T}"
    return [
        TraceSpec(model="textgen", entry="prefill", bucket=bucket,
                  mesh="single", dtype="bfloat16", build=build_prefill),
        TraceSpec(model="textgen", entry="decode",
                  bucket=f"{bucket}.greedy", mesh="single",
                  dtype="bfloat16", build=build_decode("greedy")),
        # seeded top-k: the golden proves the PRNG chain is threaded
        # from the seed inputs (GRAPH406), not baked in as a literal
        TraceSpec(model="textgen", entry="decode",
                  bucket=f"{bucket}.top_k", mesh="single",
                  dtype="bfloat16", build=build_decode("top_k")),
        TraceSpec(model="textgen", entry="generate",
                  bucket=f"{bucket}.greedy", mesh="single",
                  dtype="bfloat16", build=build_generate()),
        TraceSpec(model="textgen", entry="generate",
                  bucket=f"{bucket}.greedy", mesh="single", dtype="int8",
                  build=build_generate(precision="int8")),
    ] + [
        TraceSpec(model="textgen", entry="generate",
                  bucket=f"b2.p{P}.t{T}.greedy",
                  mesh=meshsolve.golden_layout_tag(axes),
                  dtype="bfloat16", build=build_generate(axes))
        for axes in MESH_LAYOUTS
    ]
