"""Registry factory — MiningConfig → live ModelRegistry.

The reference's equivalent is `EnabledModels` + `getModelById`
(`miner/src/index.ts:781-877`, `models.ts:87-98`): a static table wiring
template → container invocation. Here each template name maps to its
in-process pipeline class; params come from an orbax checkpoint when the
model entry names one (the converted production weights) or from
deterministic random init otherwise (dev / throughput benches — same
FLOPs, no weights download).
"""
from __future__ import annotations

import logging
from dataclasses import replace

from arbius_tpu.node.config import ConfigError, MiningConfig, ModelConfig
from arbius_tpu.node.solver import (
    Kandinsky2Runner,
    ModelRegistry,
    RegisteredModel,
    RVMRunner,
    SD15Runner,
    Text2VideoRunner,
)
from arbius_tpu.templates.engine import load_template

log = logging.getLogger("arbius.factory")


def _needs_cast(params, dtype) -> bool:
    """Host-side dtype scan: does any floating leaf differ from `dtype`?
    Cheap (metadata only), and avoids compiling an identity cast program
    for correctly-stored checkpoints (the documented common case)."""
    import jax
    import jax.numpy as jnp

    target = jnp.dtype(dtype)
    return any(
        jnp.issubdtype(leaf.dtype, jnp.inexact) and leaf.dtype != target
        for leaf in jax.tree_util.tree_leaves(params))


def _params_for(pipe, m: ModelConfig):
    # boot-time param-program builds (cast / fused init) land in the
    # same arbius_compile_seconds histogram as the bucket executables
    # (docs/observability.md) when an obs context is ambient — a no-op
    # otherwise, like every obs helper
    from arbius_tpu.obs import compile_timer

    dtype = "bfloat16" if m.weights_dtype == "bfloat16" else None
    mesh = getattr(pipe, "mesh", None)
    if m.checkpoint:
        from arbius_tpu.utils import load_params

        params = load_params(m.checkpoint)
        import jax

        if dtype is not None and _needs_cast(params, dtype):
            from arbius_tpu.utils import cast_floating

            # one jitted program: eager per-leaf casts would dispatch one
            # op per leaf. Production checkpoints should be STORED in the
            # pinned dtype (convert-checkpoint --dtype) — _needs_cast skips the
            # program entirely then (an identity cast program emits a
            # 'donated buffer was not usable' warning per boot) — but when
            # it isn't, donation lets XLA free each f32 leaf at its
            # convert instead of holding both full trees live (the
            # 16 GB-chip OOM the random-init path fixes via with_cast)
            with compile_timer(f"boot.cast.{m.template}"):
                params = jax.jit(lambda p: cast_floating(p, dtype),
                                 donate_argnums=0)(params)
        elif mesh is None:
            # loaded leaves are host numpy arrays; commit them to the
            # device ONCE here (the cast program used to do this as a
            # side effect) — otherwise every solve re-uploads the full
            # weight tree through the jitted bucket call
            params = jax.device_put(params)
        if mesh is not None:
            # shard ONCE at boot via the family's rule table (one batched
            # device_put over the tree — docs/multichip.md): TP kernels
            # by rule, everything else replicated across the mesh. The
            # no-cast path shards STRAIGHT from the host tree — routing
            # through a whole-tree device_put first would park the full
            # unsharded tree on one chip (transient 2× residency at boot
            # for nothing). The cast path above still lands on one
            # device first; storing checkpoints in the pinned dtype (the
            # documented config) avoids that hop entirely.
            params = pipe.place_params(params)
        return _maybe_quantize(pipe, m, params)
    log.warning("model %s: no checkpoint configured, using random init",
                m.id)
    if mesh is not None and hasattr(pipe, "init_params_placed") \
            and dtype is None \
            and getattr(pipe, "precision", "bf16") == "bf16":
        # fused init + placement: one XLA program whose out_shardings
        # are the rule table's, so the unsharded tree never exists
        # (quantized modes take the init→quantize→place path below —
        # the quantized tree needs the quant-aware rule table)
        with compile_timer(f"boot.init.{m.template}"):
            return pipe.init_params_placed(seed=0)
    # dtype folds the cast into the init program: a separate cast program
    # holds BOTH trees live (f32 + bf16 — 18 GB for the ~3B kandinsky
    # tree) and OOMs a 16 GB chip; fused, each f32 leaf dies at its cast
    with compile_timer(f"boot.init.{m.template}"):
        params = pipe.init_params(seed=0, dtype=dtype)
    params = _maybe_quantize(pipe, m, params, placed=False)
    return pipe.place_params(params) if mesh is not None else params


def _maybe_quantize(pipe, m: ModelConfig, params, *, placed: bool = True):
    """Quantize the weight tree ONCE at load when the pipeline serves a
    quantized precision mode (docs/quantization.md): one jitted program
    (no donation — an int8 output can never alias its f32 source; XLA
    frees each full-width leaf at its last read inside the program),
    then re-placement through the quant-aware rule table when a mesh is
    up, so int8/fp8 kernels keep their tp split as 1-byte shards and
    the per-channel f32 scales split with them."""
    mode = getattr(pipe, "precision", "bf16")
    if mode == "bf16":
        return params
    from arbius_tpu.obs import compile_timer as _ct
    from arbius_tpu.quant import quantize_params

    with _ct(f"boot.quant.{m.template}"):
        params = quantize_params(params, mode)
    if placed and getattr(pipe, "mesh", None) is not None:
        params = pipe.place_params(params)
    return params


def _tokenizer_for(m: ModelConfig, text_cfg):
    """ModelConfig.tokenizer → live tokenizer (None = pipeline default).

    `clip_bpe` loads the standard CLIP vocab/merges from the configured
    local files — the pairing real converted CLIP weights need (byte-level
    ids feed garbage conditioning into a pretrained text tower)."""
    if m.tokenizer == "clip_bpe":
        from arbius_tpu.models.sd15 import CLIPBPETokenizer

        tok = CLIPBPETokenizer.from_files(m.vocab_path, m.merges_path)
        tok.max_length = text_cfg.max_length
        return tok
    return tiny_byte_tokenizer(text_cfg) if m.tiny else None


def _sd15(m: ModelConfig, mesh, mode: str = "bf16"):
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline

    cfg = SD15Config.tiny() if m.tiny else SD15Config()
    pipe = SD15Pipeline(cfg, tokenizer=_tokenizer_for(m, cfg.text), mesh=mesh,
                        precision=mode)
    return SD15Runner(pipe, _params_for(pipe, m))


def tiny_byte_tokenizer(text_cfg):
    """Byte tokenizer whose special ids fit a reduced-vocab text tower —
    the one way to build a tiny-config tokenizer."""
    from arbius_tpu.models.sd15 import ByteTokenizer

    return ByteTokenizer(max_length=text_cfg.max_length,
                         bos_id=257, eos_id=258)


def _kandinsky2(m: ModelConfig, mesh, mode: str = "bf16"):
    from arbius_tpu.models.kandinsky2 import Kandinsky2Config, Kandinsky2Pipeline

    cfg = Kandinsky2Config.tiny() if m.tiny else Kandinsky2Config()
    pipe = Kandinsky2Pipeline(cfg, tokenizer=_tokenizer_for(m, cfg.text),
                              mesh=mesh, precision=mode)
    return Kandinsky2Runner(pipe, _params_for(pipe, m))


def _video(m: ModelConfig, mesh, mode: str = "bf16"):
    from arbius_tpu.models.video import (
        Text2VideoConfig,
        Text2VideoPipeline,
        UNet3DConfig,
    )

    # build sharding-aware when the mesh shards frames; the model config
    # picks HOW the sharded temporal attention communicates (ring K/V
    # rotation vs ulysses all_to_all — SURVEY §2.6 long-context path)
    sp = mesh.shape.get("sp", 1) if mesh is not None else 1
    sp_axis = "sp" if sp > 1 else None
    if m.tiny:
        cfg = Text2VideoConfig.tiny(sp_axis=sp_axis, sp_strategy=m.sp_strategy)
    else:
        cfg = Text2VideoConfig(unet=UNet3DConfig(sp_axis=sp_axis,
                                                 sp_strategy=m.sp_strategy))
    if sp > 1 and m.sp_strategy == "ulysses":
        # fail at BOOT, not at first-task trace time: ulysses re-shards
        # frames onto heads, so sp must divide every temporal head count
        # (per-level ch // head_dim, plus the transformer_in stem)
        u = cfg.unet
        heads = {ch // u.head_dim for ch in u.block_channels} | {u.tin_heads}
        bad = sorted(h for h in heads if h % sp)
        if bad:
            raise ConfigError(
                f"model {m.id}: sp_strategy='ulysses' needs every temporal "
                f"head count divisible by sp={sp}, but this topology has "
                f"head counts {bad} — use sp_strategy='ring' (works for "
                "any head count) or a different sp width")
    pipe = Text2VideoPipeline(cfg, tokenizer=_tokenizer_for(m, cfg.text),
                              mesh=mesh, precision=mode)
    return Text2VideoRunner(pipe, _params_for(pipe, m))


def probe_resolver(shape: str, base=None):
    """cid→bytes resolver that synthesizes the deterministic probe clip
    for its own CID and defers everything else to `base`. Makes a
    file-input golden self-contained: a ModelConfig.golden carrying
    `probe_video: "TxHxW"` boot-self-tests without the clip pre-pinned
    in any store (codecs/probe.py — same bytes on every platform)."""
    from arbius_tpu.codecs import encode_mp4
    from arbius_tpu.codecs.probe import probe_clip
    from arbius_tpu.l0.base58 import b58encode
    from arbius_tpu.l0.cid import dag_of_file

    t, h, w = (int(x) for x in shape.lower().split("x"))
    blob = encode_mp4(probe_clip(t, h, w), fps=8)
    pcid = b58encode(dag_of_file(blob).cid)

    def resolve(cid):
        if cid == pcid:
            return blob
        return base(cid) if base is not None else None

    return resolve, pcid


def probe_golden_input(shape: str):
    """(resolver, raw-input) pair for recording a file-input golden
    against the deterministic probe clip. The ONE definition of what a
    probe-recorded vector's input looks like — record-golden (CLI) and
    bench's golden session both use it, so CPU- and TPU-recorded rows of
    the same shape can never drift apart structurally."""
    resolve_file, clip_cid = probe_resolver(shape)
    return resolve_file, {"input_video": clip_cid}


def _textgen(m: ModelConfig, mesh, mode: str, tg):
    """textgen builder — takes the fleet-wide sequence-bucket policy
    (cfg.textgen) on top of the common (model, mesh, mode) triple, so
    it sits in _TEXT_BUILDERS rather than in _BUILDERS."""
    from arbius_tpu.models.textgen import TextGenConfig, TextGenPipeline
    from arbius_tpu.node.solver import TextGenRunner

    cfg = TextGenConfig.tiny() if m.tiny else TextGenConfig()
    pipe = TextGenPipeline(cfg, mesh=mesh, precision=mode,
                           prompt_buckets=tuple(tg.prompt_buckets),
                           decode_buckets=tuple(tg.decode_buckets),
                           top_k=tg.top_k)
    return TextGenRunner(pipe, _params_for(pipe, m))


def _share_family(m: ModelConfig, mesh, mode: str, tg, config_cls,
                  pipe_cls):
    """TextGenRunner over a family that serves one chip's share of a
    model: the bucket policy is the template's own entry of
    cfg.textgen, and `share` says which experts, vocabulary rows and
    layers this chip holds (nothing: the whole published model)."""
    from arbius_tpu.node.solver import TextGenRunner

    try:
        cfg = config_cls.tiny(**tg.share) if m.tiny \
            else replace(config_cls.published(), **tg.share)
    except ValueError as e:
        raise ConfigError(f"textgen.share: {e}") from None
    pipe = pipe_cls(cfg, mesh=mesh, precision=mode,
                    prompt_buckets=tuple(tg.prompt_buckets),
                    decode_buckets=tuple(tg.decode_buckets),
                    top_k=tg.top_k)
    return TextGenRunner(pipe, _params_for(pipe, m))


def _trinity(m: ModelConfig, mesh, mode: str, tg):
    from arbius_tpu.models.trinity import TrinityConfig, TrinityPipeline

    return _share_family(m, mesh, mode, tg, TrinityConfig, TrinityPipeline)


def _deepseek_v32(m: ModelConfig, mesh, mode: str, tg):
    from arbius_tpu.models.deepseek_v32 import (
        DeepSeekV32Config,
        DeepSeekV32Pipeline,
    )

    return _share_family(m, mesh, mode, tg, DeepSeekV32Config,
                         DeepSeekV32Pipeline)


def _joyai_llm_flash(m: ModelConfig, mesh, mode: str, tg):
    """The one program this template is served by is the speculative
    one (models/joyai_flash/pipeline.py): nothing here or in the config
    picks another."""
    from arbius_tpu.models.joyai_flash import (
        JoyAIFlashConfig,
        JoyAIFlashPipeline,
    )

    return _share_family(m, mesh, mode, tg, JoyAIFlashConfig,
                         JoyAIFlashPipeline)


def _dots3_note(m: ModelConfig, mesh, mode: str, tg):
    from arbius_tpu.models.dots3 import Dots3NoteConfig, Dots3NotePipeline

    return _share_family(m, mesh, mode, tg, Dots3NoteConfig,
                         Dots3NotePipeline)


def _nemotron_h(m: ModelConfig, mesh, mode: str, tg):
    """Served, as joyai_llm_flash is, by the speculative program alone
    (models/nemotron_h/pipeline.py)."""
    from arbius_tpu.models.nemotron_h import (
        NemotronHConfig,
        NemotronHPipeline,
    )

    return _share_family(m, mesh, mode, tg, NemotronHConfig,
                         NemotronHPipeline)


# text templates: builders that take the template's sequence-bucket
# policy (cfg.textgen.for_template) on top of the common triple
_TEXT_BUILDERS = {"textgen": _textgen, "trinity": _trinity,
                  "deepseek_v32": _deepseek_v32,
                  "joyai_llm_flash": _joyai_llm_flash,
                  "dots3_note": _dots3_note, "nemotron_h": _nemotron_h}


def _rvm(m: ModelConfig, mesh, resolve_file):
    from arbius_tpu.models.rvm import RVMPipeline, RVMPipelineConfig

    probe = (m.golden or {}).get("probe_video")
    if probe:
        resolve_file, _ = probe_resolver(probe, base=resolve_file)
    cfg = RVMPipelineConfig.tiny() if m.tiny else RVMPipelineConfig()
    pipe = RVMPipeline(cfg)
    return RVMRunner(pipe, _params_for(pipe, m), resolve_file)


_BUILDERS = {
    "anythingv3": _sd15,
    "kandinsky2": _kandinsky2,
    "zeroscopev2xl": _video,
    "damo": _video,
}

# template → the pipeline module publishing that family's mesh contract
# as data (MESH_LAYOUTS, MESH_BATCH_HARD — docs/multichip.md). One row
# per mesh-capable _BUILDERS entry; robust_video_matting is absent on
# purpose (stateful ConvGRU frame stream, never meshed). This is THE
# family list meshsolve.check_mesh_contract audits against — a new
# template is mesh-blind until it gets a row here.
_MESH_CONTRACT_MODULES = {
    "anythingv3": "arbius_tpu.models.sd15.pipeline",
    "kandinsky2": "arbius_tpu.models.kandinsky2.pipeline",
    "zeroscopev2xl": "arbius_tpu.models.video.pipeline",
    "damo": "arbius_tpu.models.video.pipeline",
    "textgen": "arbius_tpu.models.textgen.pipeline",
}


def mesh_contracts(cfg: MiningConfig) -> dict:
    """Enabled mesh-capable templates → their pipeline modules, the
    contract table `meshsolve.check_mesh_contract` boot-audits (layout
    ∈ MESH_LAYOUTS, canonical_batch % dp)."""
    import importlib

    return {m.template: importlib.import_module(
                _MESH_CONTRACT_MODULES[m.template])
            for m in cfg.models
            if m.enabled and m.template in _MESH_CONTRACT_MODULES}


def build_registry(cfg: MiningConfig, *, mesh=None,
                   resolve_file=None) -> ModelRegistry:
    """Construct runners for every enabled model in the config.

    `resolve_file` (cid → bytes) is required only for file-input
    templates (robust_video_matting); leave None to skip those with a
    warning rather than fail the whole node.

    When `cfg.mesh` is set (and no explicit `mesh` is passed) the solve
    mesh is built here — validated against the visible device count with
    a boot-quality error — and every mesh-capable family's params are
    sharded onto it once via its rule table (docs/multichip.md).
    robust_video_matting stays single-device (stateful ConvGRU frame
    stream); the mesh is simply not passed to it.
    """
    if mesh is None and cfg.mesh is not None:
        from arbius_tpu.parallel import meshsolve

        mesh = meshsolve.boot_mesh(cfg.mesh)
        meshsolve.check_mesh_contract(mesh, mesh_contracts(cfg),
                                      cfg.canonical_batch)
    reg = ModelRegistry()
    for m in cfg.models:
        if not m.enabled:
            continue
        mode = cfg.precision.mode_for(m.template)
        if m.template == "robust_video_matting":
            if mode != "bf16":
                # boot error, mesh-style: the stateful ConvGRU matting
                # stream ships no quantized goldens, so a quantized
                # mode here would mine a determinism class nothing pins
                raise ConfigError(
                    f"precision mode {mode!r} is not shipped for "
                    "template robust_video_matting — the matting "
                    "family serves bf16 only (docs/quantization.md)")
            if resolve_file is None and not (m.golden or {}).get("probe_video"):
                log.warning("model %s: robust_video_matting needs a "
                            "resolve_file (or a probe_video golden); "
                            "skipping", m.id)
                continue
            runner = _rvm(m, mesh, resolve_file)
        elif m.template in _TEXT_BUILDERS:
            # carries the fleet-wide sequence-bucket policy, per text
            # template, on top of the common builder triple
            # (docs/text-serving.md)
            runner = _TEXT_BUILDERS[m.template](
                m, mesh, mode, cfg.textgen.for_template(m.template))
        elif m.template in _BUILDERS:
            runner = _BUILDERS[m.template](m, mesh, mode)
        else:
            log.warning("model %s: unknown template %r; skipping",
                        m.id, m.template)
            continue
        golden = None
        if m.golden is not None:
            golden = (dict(m.golden["input"]), int(m.golden["seed"]),
                      str(m.golden["cid"]))
        reg.register(RegisteredModel(
            id=m.id, template=load_template(m.template), runner=runner,
            min_fee=m.min_fee, allowed_owners=list(m.allowed_owners),
            golden=golden))
    return reg
