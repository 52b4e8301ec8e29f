"""dots3-note-prev (`dots3_note`) decoder — one chip's share, with two
latent attentions of their own shapes side by side.

The architecture of dots-studio/dots3-note-prev as its published
`config.json` states it (docs/text-serving.md lists each point): its
FULL layers are DeepSeek-V3.2's latent attention with the lightning
indexer (128 heads, a 576-wide latent, top-2048 keys a query); its
SLIDING layers run a second latent attention of their own shape — 64
heads, a 1,024-wide latent, query/key heads of 192 + 64 — over the last
513 positions, with no indexer. Both kinds gate each head's output by
a sigmoid of the attention's normed input (`headwise`) and rescale the
low-rank query and latent (`apply_mla_qkv_lora_rescale`). A leading
dense SwiGLU layer, then expert layers: a sigmoid router over all 256
experts (`noaux_tc` with one group), top-8, one shared expert.

The full layers ARE deepseek_v32's (models/deepseek_v32/model.py): its
`_prefill_attention`, `_decode_layer_attention`, `_queries`, `_keys`,
`_decode_attention`, the selection, `_ffn` and `route`, called with the
layer kind's shape (`MLA`) where that module reads its config. The
sliding layers call the same projections with their own shape, prefill
in the per-head form through `ops.selected_flash.window_attention` and
decode in the latent form. Trinity's rule holds (models/trinity): the
config says which routed experts and vocabulary rows THIS chip holds.

Same split API as the other text families: `prefill(params, ids,
total, cfg)` and `decode(params, tok, carry, pos, cfg)`. The carry
holds, a layer, either a full layer's latent cache `[B, T, 576]` and
indexer key cache `[B, T, 128]`, or a sliding layer's RING of latent
rows `[B, 513, 1088]` (c_kv 1024 | k_pe 64) written at `pos mod 513` —
and the int32 pair of the routers' assignments.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from arbius_tpu.models.deepseek_v32.model import (
    _block,
    _decode_attention,
    _decode_layer_attention,
    _embed,
    _ffn,
    _head_gate,
    _keys,
    _prefill_attention,
    _prefill_ffn,
    _queries,
)
from arbius_tpu.models.trinity.model import (
    _dot,
    _logits,
    _ring_fill,
    init_tree,
    rms_norm,
)
from arbius_tpu.ops.selected_flash import window_attention

# the published layer pattern after the leading layer (a full one)
PERIOD = ("full", "sliding", "sliding", "sliding")


@dataclass(frozen=True)
class MLA:
    """One latent attention's shape: what deepseek_v32's attention
    functions read off their `cfg`. `window`: the keys a query reaches,
    its own included (None: every causal key, under the indexer's
    selection of `index_topk`)."""
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    q_scale: float
    kv_scale: float
    eps: float
    window: int | None = None
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    # plain rotary (`rope_scaling` null): at factor 1 deepseek_v32's
    # YaRN table is theta^(-2i/d) whatever the ramp says
    rope_factor = 1.0
    rope_original = 4096
    beta_fast = 32.0
    beta_slow = 1.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self) -> int:
        """Numbers a position in a layer's latent cache: c_kv | k_pe."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """qk_head_dim^-1/2: no YaRN, so no m²."""
        return self.qk_head_dim ** -0.5


@dataclass(frozen=True)
class Dots3NoteConfig:
    vocab_size: int = 152064
    # the slice [lo, hi) of the vocabulary whose embedding and head rows
    # live here; ids, logits and sampling are over the slice
    vocab_rows: tuple = (0, 152064)
    hidden: int = 5120
    # full layers: deepseek_v32's latent attention and indexer
    heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    rope_theta: float = 80000000.0
    # sliding layers: a latent attention of their own over `window` keys
    swa_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    window: int = 513
    dense_ff: int = 13824
    expert_ff: int = 1536
    num_experts: int = 256
    # the range [lo, hi) of each layer's routed experts held here
    experts_held: tuple = (0, 256)
    experts_per_token: int = 8
    route_scale: float = 1.0
    # one (mlp, attention) kind a layer: mlp "dense" | "moe", attention
    # "full" | "sliding"
    layers: tuple = ()
    eps: float = 1e-5
    dtype: str = "bfloat16"

    # what deepseek_v32's `route` reads beside the fields above: one
    # routing group, so the group limit keeps every expert
    n_group = 1
    topk_group = 1

    def __post_init__(self):
        object.__setattr__(self, "layers",
                           tuple(tuple(k) for k in self.layers))
        for name in ("vocab_rows", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        for mlp, attn in self.layers:
            if mlp not in ("dense", "moe") or attn not in ("full",
                                                           "sliding"):
                raise ValueError(f"unknown layer kind {(mlp, attn)!r}")
        if self.qk_rope_head_dim != self.swa_qk_rope_head_dim \
                or self.qk_rope_head_dim % 2 \
                or self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("qk_rope_head_dim must be even, the same in "
                             "both kinds and no wider than index_head_dim")
        if self.window < 1:
            raise ValueError(f"window {self.window} holds no key")
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
        return 524288

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_vocab(self) -> int:
        return self.vocab_rows[1] - self.vocab_rows[0]

    def attn(self, kind: str) -> MLA:
        """The attention shape of a layer kind, with its LoRA rescale
        (hidden / rank)^1/2 for the query and for the latent."""
        if kind == "full":
            return MLA(self.heads, self.q_lora_rank, self.kv_lora_rank,
                       self.qk_nope_head_dim, self.qk_rope_head_dim,
                       self.v_head_dim, self.rope_theta,
                       math.sqrt(self.hidden / self.q_lora_rank),
                       math.sqrt(self.hidden / self.kv_lora_rank), self.eps,
                       None, self.index_heads, self.index_head_dim,
                       self.index_topk)
        return MLA(self.swa_heads, self.swa_q_lora_rank,
                   self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                   self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                   self.swa_rope_theta,
                   math.sqrt(self.hidden / self.swa_q_lora_rank),
                   math.sqrt(self.hidden / self.swa_kv_lora_rank), self.eps,
                   self.window)

    def count(self, attn: str) -> int:
        return sum(1 for _, a in self.layers if a == attn)

    @staticmethod
    def pattern(moe: int) -> tuple:
        """The leading dense full layer, then `moe` expert layers whose
        attention kinds cycle through PERIOD."""
        return (("dense", "full"),) + tuple(
            ("moe", PERIOD[i % len(PERIOD)]) for i in range(moe))

    @classmethod
    def published(cls) -> "Dots3NoteConfig":
        """dots3-note-prev whole: 1 dense + 45 expert layers, 13 full and
        33 sliding (`layer_types`)."""
        return cls(layers=cls.pattern(45))

    @classmethod
    def tiny(cls, **over) -> "Dots3NoteConfig":
        """The whole model at a size the CPU tests run: every mechanism
        (both attention shapes — the sliding one the wider latent and
        head, as published —, a window of 5 and a selection of 4 that
        both bite from a few positions, all experts held), rotary
        frequencies of each kind far apart, no published width."""
        kw = dict(vocab_size=512, vocab_rows=(0, 512), hidden=32, heads=4,
                  q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=4, v_head_dim=8, index_heads=2,
                  index_head_dim=8, index_topk=4, rope_theta=100.0,
                  swa_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=24,
                  swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
                  swa_v_head_dim=8, swa_rope_theta=10.0, window=5,
                  dense_ff=64, expert_ff=16, num_experts=16,
                  experts_held=(0, 16), experts_per_token=2,
                  layers=cls.pattern(4))
        kw.update(over)
        return cls(**kw)

    def cache_rows(self, attn: str, total: int) -> int:
        """Rows a layer of this attention kind keeps for a sequence of
        `total` positions: all of them, or the ring."""
        return total if attn == "full" else min(self.window, total)

    def cache_bytes(self, total: int) -> tuple[int, int, int]:
        """(bytes the sliding layers' rings hold for one sequence of
        `total` positions, bytes the full layers' latent and indexer
        caches hold, bytes the sliding layers would hold at full
        length), in the serving dtype."""
        item = self.jdtype.itemsize
        ring = self.attn("sliding").cache_width * item * self.count(
            "sliding")
        full = (self.attn("full").cache_width + self.index_head_dim) \
            * item * self.count("full") * total
        return ring * self.cache_rows("sliding", total), full, ring * total


# -- parameters --------------------------------------------------------------
def _attn_shapes(cfg: Dots3NoteConfig, a: MLA) -> dict:
    d, nh = cfg.hidden, a.heads
    return {
        "wq_a": {"kernel": (d, a.q_lora_rank)},
        "q_norm": {"scale": (a.q_lora_rank,)},
        "wq_b": {"kernel": (a.q_lora_rank, nh * a.qk_head_dim)},
        "wkv_a": {"kernel": (d, a.cache_width)},
        "kv_norm": {"scale": (a.kv_lora_rank,)},
        "wkv_b": {"kernel": (a.kv_lora_rank,
                             nh * (a.qk_nope_head_dim + a.v_head_dim))},
        "wo": {"kernel": (nh * a.v_head_dim, d)},
        "gate": {"kernel": (d, nh)},
    }


def param_shapes(cfg: Dots3NoteConfig) -> dict:
    """{path: shape} of the tree a node loads — deepseek_v32's layout,
    each layer's attention at its kind's shape with its headwise `gate`
    [d, H], the indexer in full layers only; stacked expert kernels lead
    with the experts held."""
    d = cfg.hidden

    def swiglu_p(ff, lead=()):
        return {"gate": {"kernel": lead + (d, ff)},
                "up": {"kernel": lead + (d, ff)},
                "down": {"kernel": lead + (ff, d)}}

    tree = {"embed": {"embedding": (cfg.n_vocab, d)},
            "final_norm": {"scale": (d,)},
            "head": {"kernel": (d, cfg.n_vocab)}}
    for i, (mlp, attn) in enumerate(cfg.layers):
        a = cfg.attn(attn)
        layer = {"attn_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)},
                 "attn": _attn_shapes(cfg, a)}
        if attn == "full":
            layer["indexer"] = {
                "wq_b": {"kernel": (a.q_lora_rank,
                                    a.index_heads * a.index_head_dim)},
                "wk": {"kernel": (d, a.index_head_dim)},
                "k_norm": {"scale": (a.index_head_dim,),
                           "bias": (a.index_head_dim,)},
                "weights_proj": {"kernel": (d, a.index_heads)},
            }
        if mlp == "dense":
            layer["mlp"] = swiglu_p(cfg.dense_ff)
        else:
            layer["moe"] = {
                "router": {"kernel": (d, cfg.num_experts)},
                "expert_bias": (cfg.num_experts,),
                "shared": swiglu_p(cfg.expert_ff),
                "experts": swiglu_p(cfg.expert_ff, (cfg.n_held,)),
            }
        tree[f"layer_{i}"] = layer
    return tree


def init_params(cfg: Dots3NoteConfig, key):
    """Seeded random tree (float32; the pipeline casts), by trinity's
    rules."""
    return init_tree(param_shapes(cfg), key)


def n_moe(cfg: Dots3NoteConfig) -> int:
    return sum(1 for m, _ in cfg.layers if m == "moe")


# -- the sliding layers ------------------------------------------------------
def _window_prefill(x, lp, a: MLA):
    """The attention half of a sliding layer on one sequence x[P, d] →
    (x', latent[P, 1088]): queries and keys of every head in the
    per-head form — nope | rope joined, the rotary key broadcast to every
    head — over the band of `a.window` keys, gated, through `wo`."""
    p = x.shape[0]
    nh, dn, c = a.heads, a.qk_nope_head_dim, a.kv_lora_rank
    pos = jnp.arange(p)
    with jax.named_scope("window_attention"):
        h = rms_norm(x, lp["attn_norm"]["scale"], a.eps)
        q_nope, q_pe, _, _ = _queries(h, lp, pos, a)
        latent, _ = _keys(h, lp, pos, a)
        kv = _dot(latent[:, :c], lp["attn"]["wkv_b"]["kernel"]).reshape(
            p, nh, dn + a.v_head_dim)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(latent[:, None, c:],
                                            (p, nh, a.qk_rope_head_dim))],
            axis=-1)
        o = window_attention(jnp.concatenate([q_nope, q_pe], axis=-1), k,
                             kv[..., dn:], window=a.window,
                             scale=a.softmax_scale, block=_block(p, nh))
        o = _head_gate(o, h, lp["attn"], nh)
        return x + _dot(o, lp["attn"]["wo"]["kernel"]), latent


def _window_decode(x, lp, ring, pos, at, a: MLA):
    """The attention half of a sliding layer's decode step on x[B, d] at
    position `pos` → (x', ring): this position's latent row written at
    slot `pos mod rows`; a slot j then holds position pos - ((pos - j) mod
    rows), which lies inside the window by construction, valid once it is
    >= 0; the latent form over the valid slots, gated."""
    b, rows = x.shape[0], ring.shape[1]
    with jax.named_scope("window_attention"):
        h = rms_norm(x, lp["attn_norm"]["scale"], a.eps)
        q_nope, q_pe, _, _ = _queries(h, lp, at, a)
        row, _ = _keys(h, lp, at, a)
        ring = jax.lax.dynamic_update_slice(
            ring, row[:, None].astype(ring.dtype), (0, pos % rows, 0))
        j = jnp.arange(rows)
        valid = jnp.broadcast_to(pos - ((pos - j) % rows) >= 0, (b, rows))
        return x + _decode_attention(q_nope, q_pe, ring, valid, lp["attn"],
                                     a, h), ring


# -- prefill and decode ------------------------------------------------------
def _prefill_piece(params, ids, total: int, cfg: Dots3NoteConfig):
    """One sequence ids[P] → (last hidden [d], per-layer caches —
    (latent, k_i) of `total` rows for a full layer, (ring,) for a sliding
    one —, held)."""
    p = ids.shape[0]
    x = _embed(params, ids, cfg)
    held = jnp.zeros((), jnp.int32)
    caches = []
    for i, (mlp, attn) in enumerate(cfg.layers):
        lp = params[f"layer_{i}"]
        a = cfg.attn(attn)
        if attn == "full":
            x, latent, k_i = _prefill_attention(x, lp, a)
            caches.append(tuple(jnp.pad(c, ((0, total - p), (0, 0)))
                                for c in (latent, k_i)))
        else:
            x, latent = _window_prefill(x, lp, a)
            caches.append((_ring_fill(latent[None],
                                      cfg.cache_rows(attn, total))[0],))
        x, n = _prefill_ffn(x, lp, mlp, cfg)
        held = held + n
    return x[-1], tuple(caches), held


def prefill(params, ids, total: int, cfg: Dots3NoteConfig):
    """ids[B, P] → (logits[B, V'] f32 at the last prompt position, carry).

    The batch is walked a sequence at a time (`lax.map`); a full layer's
    attention a block of query rows at a time and a sliding layer's in
    one banded call; the FFN a chunk of rows at a time. carry = (per-layer
    caches, int32 [assignments, held])."""
    b, p = ids.shape
    last, caches, held = jax.lax.map(
        lambda row: _prefill_piece(params, row, total, cfg), ids)
    made = jnp.int32(b * p * cfg.experts_per_token * n_moe(cfg))
    stats = jnp.stack([made, held.sum(dtype=jnp.int32)])
    return _logits(params, last, cfg), (caches, stats)


def decode(params, tok, carry, pos, cfg: Dots3NoteConfig):
    """tok[B] int32 at position `pos` → (logits[B, V'] f32 for the next
    position, carry). A full layer is deepseek_v32's step (its latent and
    indexer key at row `pos`, the selection, the latent form); a sliding
    layer writes its ring and attends over the window."""
    caches, stats = carry
    b = tok.shape[0]
    x = _embed(params, tok, cfg)
    held = jnp.zeros((), jnp.int32)
    at = jnp.full((b,), pos)
    new = []
    for i, (mlp, attn) in enumerate(cfg.layers):
        lp = params[f"layer_{i}"]
        a = cfg.attn(attn)
        if attn == "full":
            x, lat, k_i = _decode_layer_attention(x, lp, *caches[i], pos,
                                                  at, a)
            new.append((lat, k_i))
        else:
            x, ring = _window_decode(x, lp, *caches[i], pos, at, a)
            new.append((ring,))
        x, n = _ffn(x, lp, mlp, cfg)
        held = held + n
    made = jnp.int32(b * cfg.experts_per_token * n_moe(cfg))
    stats = stats + jnp.stack([made, held])
    return _logits(params, x, cfg), (tuple(new), stats)
