"""JoyAI-LLM-Flash (`joyai_llm_flash`) decoder — latent attention over
every causal key, all routed experts on the chip, and the multi-token
prediction module that drafts for it.

The architecture of jdopensource/JoyAI-LLM-Flash as its published
`config.json` states it (docs/text-serving.md lists each point): its
layers are DeepSeek-V3.2's with the indexer left out — pre-norm RMSNorm
blocks, a low-rank query, latent attention that keeps ONE 512-wide normed
latent and ONE 64-wide rotary key a position a layer, a leading dense
SwiGLU layer and then expert layers under a sigmoid router (`noaux_tc`
with one group: the group limit keeps every expert), one shared expert —
so those pieces are IMPORTED from models/deepseek_v32 and models/trinity
(`rope_pairs`, `route` through `_ffn`, `routed_experts`, the embedding
and the head), not copied. Plain rotary positions: no YaRN, so the
softmax scale is 192^-1/2.

What this family brings is the module after the last layer
(`num_nextn_predict_layers` 1, DeepSeek-V3's report section 2.2): for a
position i, the main model's last hidden state h_i (before the final
norm) and the NEXT token t_{i+1} go through two norms and one
4,096 → 2,048 projection into one more expert layer — its own latent
cache, rotary position i — and the main model's head reads a guess at
token i+2 off it. Serving with it is speculative decoding: the module
drafts, the model verifies, and a step yields one or two tokens
(models/joyai_flash/pipeline.py holds the loop).

Pure functions of an explicit param tree, every decode-side function
general in S, the positions a row runs in one pass:
  prefill(params, ids, total, cfg)       the prompt, the per-head form,
                                         a sequence and a block of rows
                                         at a time (deepseek_v32's walk
                                         or kernel under an all-ones
                                         selection); the module's cache
                                         rows 0 .. P-2 with it
  step(params, toks[B, S], caches, q[B], cfg)
                                         the main model on S consecutive
                                         positions a row, from the row's
                                         own q, in the LATENT form
  draft(params, toks[B, S], h[B, S, d], cache, q[B], cfg)
                                         the module on the same positions
A layer's cache is `[B, T, 576]` (`c_kv | k_pe`); a row writes its rows
at its own offset, and a query at position q + s sees rows 0 .. q + s:
what a rejected draft left at q + 1 is overwritten before any softmax
reaches it.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from arbius_tpu.models.deepseek_v32.model import (
    _block,
    _embed,
    _ffn,
    rope_pairs,
)
from arbius_tpu.models.trinity.model import (
    _dot,
    _logits,
    init_tree,
    rms_norm,
)
from arbius_tpu.ops.selected_flash import selected_attention

_NEG = -1e30
F32 = jnp.float32


@dataclass(frozen=True)
class JoyAIFlashConfig:
    vocab_size: int = 129280
    # the slice [lo, hi) of the vocabulary whose embedding and head rows
    # live here; ids, logits and sampling are over the slice
    vocab_rows: tuple = (0, 129280)
    hidden: int = 2048
    heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_ff: int = 7168
    expert_ff: int = 768
    num_experts: int = 256
    # the range [lo, hi) of each layer's routed experts held here
    experts_held: tuple = (0, 256)
    experts_per_token: int = 8
    route_scale: float = 2.5
    # one mlp kind a main layer: "dense" | "moe"; the module's one
    # expert layer is not in the list
    layers: tuple = ()
    rope_theta: float = 32000000.0
    eps: float = 1e-6
    dtype: str = "bfloat16"

    # what deepseek_v32's `route` and `rope_pairs` read beside the
    # fields above: one routing group, so the group limit keeps every
    # expert; no rotary scaling, so factor 1 leaves every frequency as
    # theta^(-2i/64) whatever the ramp says
    n_group = 1
    topk_group = 1
    rope_factor = 1.0
    rope_original = 4096
    beta_fast = 32.0
    beta_slow = 1.0

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))
        for name in ("vocab_rows", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        for kind in self.layers:
            if kind not in ("dense", "moe"):
                raise ValueError(f"unknown layer kind {kind!r}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        if not 2 <= self.experts_per_token <= self.num_experts:
            raise ValueError("experts_per_token must lie in "
                             "[2, num_experts]")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no "
                             f"range of the {self.num_experts} experts")
        lo, hi = self.vocab_rows
        if not 0 <= lo < hi <= self.vocab_size:
            raise ValueError(f"vocab_rows {self.vocab_rows} is no slice "
                             f"of the {self.vocab_size} ids")

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def max_positions(self) -> int:
        # no learned positions: the source's max_position_embeddings
        return 131072

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_vocab(self) -> int:
        return self.vocab_rows[1] - self.vocab_rows[0]

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Numbers a position a layer in the latent cache: c_kv | k_pe."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @classmethod
    def published(cls) -> "JoyAIFlashConfig":
        """JoyAI-LLM-Flash whole: 1 dense + 39 expert layers, and the
        module."""
        return cls(layers=("dense",) + ("moe",) * 39)

    @classmethod
    def tiny(cls, **over) -> "JoyAIFlashConfig":
        """The whole model at a size the CPU tests run: every mechanism
        (latent cache, all experts held, the module), no published
        width."""
        kw = dict(vocab_size=512, vocab_rows=(0, 512), hidden=32, heads=4,
                  q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=4, v_head_dim=8, dense_ff=64,
                  expert_ff=16, num_experts=16, experts_held=(0, 16),
                  experts_per_token=2, layers=("dense",) + ("moe",) * 4)
        kw.update(over)
        return cls(**kw)

    def cache_bytes(self, total: int) -> int:
        """Bytes the carry holds for one sequence of `total` positions:
        a latent row a position, every main layer and the module's."""
        return (len(self.layers) + 1) * total * self.cache_width \
            * self.jdtype.itemsize


# -- parameters --------------------------------------------------------------
def _layer_shapes(cfg: JoyAIFlashConfig, kind: str) -> dict:
    d, nh = cfg.hidden, cfg.heads

    def swiglu_p(ff, lead=()):
        return {"gate": {"kernel": lead + (d, ff)},
                "up": {"kernel": lead + (d, ff)},
                "down": {"kernel": lead + (ff, d)}}

    layer = {
        "attn_norm": {"scale": (d,)},
        "ffn_norm": {"scale": (d,)},
        "attn": {
            "wq_a": {"kernel": (d, cfg.q_lora_rank)},
            "q_norm": {"scale": (cfg.q_lora_rank,)},
            "wq_b": {"kernel": (cfg.q_lora_rank, nh * cfg.qk_head_dim)},
            "wkv_a": {"kernel": (d, cfg.cache_width)},
            "kv_norm": {"scale": (cfg.kv_lora_rank,)},
            "wkv_b": {"kernel": (
                cfg.kv_lora_rank,
                nh * (cfg.qk_nope_head_dim + cfg.v_head_dim))},
            "wo": {"kernel": (nh * cfg.v_head_dim, d)},
        },
    }
    if kind == "dense":
        layer["mlp"] = swiglu_p(cfg.dense_ff)
    else:
        layer["moe"] = {
            "router": {"kernel": (d, cfg.num_experts)},
            "expert_bias": (cfg.num_experts,),
            "shared": swiglu_p(cfg.expert_ff),
            "experts": swiglu_p(cfg.expert_ff, (cfg.n_held,)),
        }
    return layer


def param_shapes(cfg: JoyAIFlashConfig) -> dict:
    """{path: shape} of the tree a node loads — deepseek_v32's layout
    without the indexer, and `mtp`: the module's two norms (`enorm` on
    the token's embedding, `hnorm` on the hidden state), `eh_proj`
    [2d, d], its expert layer and the norm before the shared head. The
    embedding and the head are the main model's: the module has none."""
    d = cfg.hidden
    tree = {"embed": {"embedding": (cfg.n_vocab, d)},
            "final_norm": {"scale": (d,)},
            "head": {"kernel": (d, cfg.n_vocab)}}
    for i, kind in enumerate(cfg.layers):
        tree[f"layer_{i}"] = _layer_shapes(cfg, kind)
    tree["mtp"] = {"enorm": {"scale": (d,)}, "hnorm": {"scale": (d,)},
                   "eh_proj": {"kernel": (2 * d, d)},
                   "layer": _layer_shapes(cfg, "moe"),
                   "norm": {"scale": (d,)}}
    return tree


def init_params(cfg: JoyAIFlashConfig, key):
    """Seeded random tree (float32; the pipeline casts), by trinity's
    rules."""
    return init_tree(param_shapes(cfg), key)


def n_moe(cfg: JoyAIFlashConfig) -> int:
    return sum(1 for kind in cfg.layers if kind == "moe")


# -- latent attention --------------------------------------------------------
def _queries(h, ap, pos, cfg):
    """h[..., d] at positions pos[...] → q_nope[..., H, 128],
    q_pe[..., H, 64] (rotated)."""
    c_q = rms_norm(_dot(h, ap["wq_a"]["kernel"]), ap["q_norm"]["scale"],
                   cfg.eps)
    q = _dot(c_q, ap["wq_b"]["kernel"]).reshape(
        *h.shape[:-1], cfg.heads, cfg.qk_head_dim)
    dn = cfg.qk_nope_head_dim
    return q[..., :dn], rope_pairs(q[..., dn:], pos[..., None], cfg)


def _latent(h, ap, pos, cfg):
    """h[..., d] at positions pos[...] → the rows the cache keeps,
    [..., 576] = kv_norm(c_kv) | rope(k_pe)."""
    kv = _dot(h, ap["wkv_a"]["kernel"])
    c = cfg.kv_lora_rank
    return jnp.concatenate(
        [rms_norm(kv[..., :c], ap["kv_norm"]["scale"], cfg.eps),
         rope_pairs(kv[..., c:], pos, cfg)], axis=-1)


def latent_attention(q_nope, q_pe, lat, keep, ap, cfg):
    """The latent form for S queries a row: q_nope[B, S, H, 128],
    q_pe[B, S, H, 64] on the cache lat[B, T, 576] under keep[B, S, T] —
    W_UK folded into the query, softmax over the kept rows, the weighted
    sum of latents, W_UV after, then `wo` → [B, S, d]. The cache is read
    once for all S."""
    nh, dn, dv = cfg.heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    c = cfg.kv_lora_rank
    wkv_b = ap["wkv_b"]["kernel"].reshape(c, nh, dn + dv)
    q_lat = jnp.einsum("bshd,chd->bshc", q_nope, wkv_b[..., :dn],
                       preferred_element_type=F32).astype(lat.dtype)
    q = jnp.concatenate([q_lat, q_pe], axis=-1)           # [B, S, H, 576]
    s = jnp.einsum("bshc,btc->bsht", q, lat,
                   preferred_element_type=F32) * cfg.softmax_scale
    s = jnp.where(keep[:, :, None], s, _NEG)
    att = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
    o_lat = jnp.einsum("bsht,btc->bshc", att, lat[..., :c],
                       preferred_element_type=F32).astype(lat.dtype)
    o = jnp.einsum("bshc,chd->bshd", o_lat, wkv_b[..., dn:],
                   preferred_element_type=F32).astype(lat.dtype)
    return _dot(o.reshape(*o.shape[:2], nh * dv), ap["wo"]["kernel"])


def _write_rows(cache, rows, q):
    """cache[B, T, ...] with rows[B, S, ...] written at each row's own
    offset q[B] .. q[B] + S - 1."""
    return jax.vmap(lambda c, r, at: jax.lax.dynamic_update_slice(
        c, r, (at,) + (0,) * (c.ndim - 1)))(cache, rows.astype(cache.dtype),
                                            q)


def _layer_step(x, lp, kind, lat, q, cfg):
    """One block on x[B, S, d], a row's positions q[B] .. q[B] + S - 1 →
    (x', the cache with those rows written, held)."""
    b, s, d = x.shape
    pos = q[:, None] + jnp.arange(s)                          # [B, S]
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["attn_norm"]["scale"], cfg.eps)
        q_nope, q_pe = _queries(h, lp["attn"], pos, cfg)
        lat = _write_rows(lat, _latent(h, lp["attn"], pos, cfg), q)
        keep = jnp.arange(lat.shape[1]) <= pos[..., None]     # [B, S, T]
        x = x + latent_attention(q_nope, q_pe, lat, keep, lp["attn"], cfg)
    y, held = _ffn(x.reshape(b * s, d), lp, kind, cfg)
    return y.reshape(b, s, d), lat, held


# -- the main model ----------------------------------------------------------
def step(params, toks, caches, q, cfg: JoyAIFlashConfig):
    """toks[B, S] int32, a row's at its positions q[B] .. q[B] + S - 1 →
    (logits[B, S, V'] f32 for the positions after them, the hidden
    states h[B, S, d] before the final norm, caches, held). S = 1 is a
    one-token decode step; S = 2 verifies a draft: the second query sees
    the first's row."""
    x = _embed(params, toks, cfg)
    held = jnp.zeros((), jnp.int32)
    new = []
    for i, kind in enumerate(cfg.layers):
        x, lat, n = _layer_step(x, params[f"layer_{i}"], kind, caches[i],
                                q, cfg)
        held = held + n
        new.append(lat)
    return _logits(params, x, cfg), x, tuple(new), held


# -- the module --------------------------------------------------------------
def _mtp_in(params, toks, h, cfg):
    """The module's input at positions whose NEXT tokens are `toks` and
    whose main hidden states are `h`: eh_proj · [enorm(Emb(t)) ;
    hnorm(h)]."""
    mp = params["mtp"]
    both = jnp.concatenate(
        [rms_norm(_embed(params, toks, cfg), mp["enorm"]["scale"], cfg.eps),
         rms_norm(h, mp["hnorm"]["scale"], cfg.eps)], axis=-1)
    return _dot(both, mp["eh_proj"]["kernel"])


def draft(params, toks, h, cache, q, cfg: JoyAIFlashConfig):
    """The module on a row's positions q[B] .. q[B] + S - 1: toks[B, S]
    are the tokens AFTER those positions, h[B, S, d] the main model's
    hidden states AT them → (logits[B, S, V'] f32 for the tokens two
    past each position, cache, held)."""
    mp = params["mtp"]
    with jax.named_scope("draft"):
        x, cache, held = _layer_step(_mtp_in(params, toks, h, cfg),
                                     mp["layer"], "moe", cache, q, cfg)
        return _logits({"final_norm": mp["norm"], "head": params["head"]},
                       x, cfg), cache, held


# -- prefill -----------------------------------------------------------------
def _prefill_layer(x, lp, kind, cfg: JoyAIFlashConfig):
    """One block on one sequence x[P, d] → (x', latent[P, 576], held):
    the per-head form, keys and values expanded from the latents once a
    layer, a block of query rows at a time over the key blocks up to its
    diagonal — deepseek_v32's prefill attention with every causal key
    selected."""
    p = x.shape[0]
    nh, dn = cfg.heads, cfg.qk_nope_head_dim
    blk = _block(p, nh)
    pos = jnp.arange(p)
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["attn_norm"]["scale"], cfg.eps)
        latent = _latent(h, lp["attn"], pos, cfg)
        c = cfg.kv_lora_rank
        attend = selected_attention(
            _dot(latent[:, :c], lp["attn"]["wkv_b"]["kernel"]), nh, dn,
            scale=cfg.softmax_scale)
        k_pe = latent[:, c:]
    rows = jnp.arange(blk)
    keep = jnp.ones((blk, p), bool)

    def block(i):
        r0 = i * blk
        with jax.named_scope("attention"):
            xb = jax.lax.dynamic_slice_in_dim(x, r0, blk)
            hb = jax.lax.dynamic_slice_in_dim(h, r0, blk)
            q_nope, q_pe = _queries(hb, lp["attn"], r0 + rows, cfg)
            o = attend(q_nope, q_pe, k_pe, keep, i, rows,
                       r0 + rows[:, None])
            xb = xb + _dot(o, lp["attn"]["wo"]["kernel"])
        return _ffn(xb, lp, kind, cfg)

    out, held = jax.lax.map(block, jnp.arange(p // blk))
    return out.reshape(p, cfg.hidden), latent, held.sum(dtype=jnp.int32)


def _prefill_piece(params, ids, total: int, cfg: JoyAIFlashConfig):
    """One sequence ids[P] → (last hidden [d], the main layers' caches
    and the module's, `total` rows each, held). The module is ONE layer,
    so over the prompt only its cache rows are ever read again: its
    input at positions 0 .. P-2 (whose next tokens the prompt holds) goes
    through its `wkv_a` alone; row P-1 waits for the first sampled
    token."""
    p = ids.shape[0]
    x = _embed(params, ids, cfg)
    held = jnp.zeros((), jnp.int32)
    caches = []
    for i, kind in enumerate(cfg.layers):
        x, latent, n = _prefill_layer(x, params[f"layer_{i}"], kind, cfg)
        held = held + n
        caches.append(jnp.pad(latent, ((0, total - p), (0, 0))))
    lp = params["mtp"]["layer"]
    with jax.named_scope("draft"):
        xm = _mtp_in(params, ids[1:], x[:-1], cfg)
        latent = _latent(rms_norm(xm, lp["attn_norm"]["scale"], cfg.eps),
                         lp["attn"], jnp.arange(p - 1), cfg)
    return x[-1], tuple(caches), \
        jnp.pad(latent, ((0, total - p + 1), (0, 0))), held


def prefill(params, ids, total: int, cfg: JoyAIFlashConfig):
    """ids[B, P] → (logits[B, V'] f32 at the last prompt position,
    carry). carry = (the main layers' caches [B, T, 576], the module's
    cache with rows 0 .. P-2 filled, the last prompt position's hidden
    state [B, d], int32 [assignments, held])."""
    b, p = ids.shape
    last, caches, mtp_cache, held = jax.lax.map(
        lambda row: _prefill_piece(params, row, total, cfg), ids)
    made = jnp.int32(b * p * cfg.experts_per_token * n_moe(cfg))
    stats = jnp.stack([made, held.sum(dtype=jnp.int32)])
    return _logits(params, last, cfg), (caches, mtp_cache, last, stats)
