"""DeepSeek-V3.2-Exp (`deepseek_v32`) decoder — one chip's share, with a
latent cache and a learned selection of keys.

The architecture of deepseek-ai/DeepSeek-V3.2-Exp as its published
`inference/model.py` computes it (docs/text-serving.md lists each
point): pre-norm RMSNorm blocks; a low-rank query (`wq_a` → RMSNorm →
`wq_b`); latent attention — a layer keeps, a position, ONE 512-wide
normed latent and ONE 64-wide rotary key shared by all heads, 576
numbers where per-head K and V rows would be 40,960; a lightning
indexer — 64 small heads score every earlier position against the
query, and attention's softmax runs over the `index_topk` best of them
alone; YaRN rotary frequencies with the softmax scale that goes with
them; a leading run of dense SwiGLU layers and then expert layers with
a sigmoid router under a group limit (8 groups of experts, the 4 best
groups by their two best scores, then top-k inside them), one shared
expert.

What makes it this repo's is Trinity's rule (models/trinity/model.py):
the config says which routed experts and which vocabulary rows THIS
chip holds; the router routes over all `num_experts`, the expert layer
computes its own experts' part (`trinity.routed_experts`, the same
function for both models) and no code stands in for the absent chips.

Same split API as the other text families, pure functions of an
explicit param tree: `prefill(params, ids, total, cfg)` and
`decode(params, tok, carry, pos, cfg)`. The carry holds, a layer, the
latent cache `[B, T, 576]` (`c_kv | k_pe`) and the indexer's key cache
`[B, T, 128]` — no per-head row anywhere — and the int32 pair of the
routers' assignments.

Two formulations of one attention, read off the call:
  prefill  the PER-HEAD form: keys and values expanded from the latents
           (`wkv_b`), one sequence at a time, a block of query rows at a
           time over the key blocks up to its diagonal, under a running
           max / normaliser / accumulator, so neither a sequence's
           activations nor a whole score matrix ever sit beside the
           weights — by `ops.selected_flash.selected_attention`, which
           reads off the backend and the static shape whether the XLA
           walk or the Pallas kernel attends;
  decode   the LATENT form: `W_UK` folded into the query, scores and the
           weighted sum taken on the cache's 576- and 512-wide rows,
           `W_UV` applied after. Equal in exact arithmetic.
Both mask the softmax to the selected keys, `select_topk`: the exact
set `lax.top_k` returns (ties to the lower position), found without a
sort by bisecting on the scores' bit patterns.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from arbius_tpu.models.trinity.model import (
    _dot,
    _logits,
    expert_tile,
    init_tree,
    rms_norm,
    routed_experts,
    swiglu,
)
from arbius_tpu.ops.selected_flash import selected_attention

_NEG = -1e30
F32 = jnp.float32

# the most bytes one block of float32 attention scores [heads, rows,
# keys] may take in prefill; rows = keys = `_block`'s answer
_SCORE_BYTES = 2 ** 27
# the most bytes the routed experts' row buffers and combine may take for
# one prefill FFN chunk; rows = `_ffn_rows`'s answer
_FFN_BYTES = 2 ** 31


@dataclass(frozen=True)
class DeepSeekV32Config:
    vocab_size: int = 129280
    # the slice [lo, hi) of the vocabulary whose embedding and head rows
    # live here; ids, logits and sampling are over the slice
    vocab_rows: tuple = (0, 129280)
    hidden: int = 7168
    heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    dense_ff: int = 18432
    expert_ff: int = 2048
    num_experts: int = 256
    # the range [lo, hi) of each layer's routed experts held here
    experts_held: tuple = (0, 256)
    experts_per_token: int = 8
    n_group: int = 8
    topk_group: int = 4
    route_scale: float = 2.5
    # one mlp kind a layer: "dense" | "moe"
    layers: tuple = ()
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale_all_dim: float = 1.0
    eps: float = 1e-6
    dtype: str = "bfloat16"

    # what the shared attention functions multiply the query (after
    # `wq_b`) and the normed latent (before `wkv_b`, and as cached) by:
    # none here; models/dots3's `apply_mla_qkv_lora_rescale`
    q_scale = None
    kv_scale = None

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))
        for name in ("vocab_rows", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        for kind in self.layers:
            if kind not in ("dense", "moe"):
                raise ValueError(f"unknown layer kind {kind!r}")
        if self.qk_rope_head_dim % 2 or \
                self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("qk_rope_head_dim must be even and no wider "
                             "than index_head_dim (the indexer rotates "
                             "its first qk_rope_head_dim dims)")
        if self.num_experts % self.n_group or \
                not 1 <= self.topk_group <= self.n_group:
            raise ValueError("n_group must divide num_experts and "
                             "topk_group lie in [1, n_group]")
        per_group = self.num_experts // self.n_group
        if per_group < 2 or \
                self.experts_per_token > self.topk_group * per_group:
            raise ValueError("a group needs two experts to be scored by, "
                             "and the kept groups experts_per_token to "
                             "choose from")
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
        return 163840

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
        """qk_head_dim^-1/2 times YaRN's m², m = 0.1·mscale_all_dim·
        ln(factor) + 1."""
        m = 0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * m * m

    @classmethod
    def published(cls) -> "DeepSeekV32Config":
        """DeepSeek-V3.2-Exp whole: 3 dense + 58 expert layers (the
        multi-token prediction module is not part of serving here)."""
        return cls(layers=("dense",) * 3 + ("moe",) * 58)

    @classmethod
    def tiny(cls, **over) -> "DeepSeekV32Config":
        """The whole model at a size the CPU tests run: every mechanism
        (latent cache, indexer with a selection that bites from 4 keys,
        grouped router, all experts held), no published width."""
        kw = dict(vocab_size=512, vocab_rows=(0, 512), hidden=32, heads=4,
                  q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                  qk_rope_head_dim=4, v_head_dim=8, index_heads=2,
                  index_head_dim=8, index_topk=4, dense_ff=64,
                  expert_ff=16, num_experts=16, experts_held=(0, 16),
                  experts_per_token=2, n_group=4, topk_group=2,
                  rope_original=8, layers=("dense",) + ("moe",) * 4)
        kw.update(over)
        return cls(**kw)

    def cache_bytes(self, total: int) -> tuple[int, int]:
        """(bytes the carry holds for one sequence of `total` positions
        — latent and indexer caches, every layer; bytes per-head K and V
        rows of every head would take), in the serving dtype."""
        item = self.jdtype.itemsize
        held = self.cache_width + self.index_head_dim
        per_head = self.heads * (self.qk_head_dim + self.v_head_dim)
        n = len(self.layers) * total * item
        return n * held, n * per_head

    def attn_pairs(self, prompt: int, decode: int) -> tuple[int, int]:
        """((query, key) pairs the selection leaves to attention's
        softmax, pairs the causal mask leaves), one sequence, summed over
        layers: the prompt's rows and the decode steps that run (the
        last sampled id is never fed back)."""
        n = prompt + decode - 1
        k = self.index_topk
        causal = n * (n + 1) // 2
        kept = causal if n <= k else k * (k + 1) // 2 + (n - k) * k
        return len(self.layers) * kept, len(self.layers) * causal


# -- parameters --------------------------------------------------------------
def param_shapes(cfg: DeepSeekV32Config) -> dict:
    """{path: shape} of the tree a node loads — `kernel` for every
    matrix, `scale` for every norm gain, the indexer's LayerNorm with a
    `bias`; stacked expert kernels lead with the experts held."""
    d, nh = cfg.hidden, cfg.heads

    def swiglu_p(ff, lead=()):
        return {"gate": {"kernel": lead + (d, ff)},
                "up": {"kernel": lead + (d, ff)},
                "down": {"kernel": lead + (ff, d)}}

    tree = {"embed": {"embedding": (cfg.n_vocab, d)},
            "final_norm": {"scale": (d,)},
            "head": {"kernel": (d, cfg.n_vocab)}}
    for i, kind in enumerate(cfg.layers):
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
            "indexer": {
                "wq_b": {"kernel": (cfg.q_lora_rank,
                                    cfg.index_heads * cfg.index_head_dim)},
                "wk": {"kernel": (d, cfg.index_head_dim)},
                "k_norm": {"scale": (cfg.index_head_dim,),
                           "bias": (cfg.index_head_dim,)},
                "weights_proj": {"kernel": (d, cfg.index_heads)},
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
        tree[f"layer_{i}"] = layer
    return tree


def init_params(cfg: DeepSeekV32Config, key):
    """Seeded random tree (float32; the pipeline casts), by trinity's
    rules: kernels N(0, 1/fan_in), embeddings N(0, 0.02²), gains 1,
    biases 0."""
    return init_tree(param_shapes(cfg), key)


# -- rotary positions --------------------------------------------------------
def yarn_freqs(cfg: DeepSeekV32Config) -> np.ndarray:
    """The qk_rope_head_dim / 2 rotary frequencies under YaRN: a pair
    that turns more than beta_fast times in the original context keeps
    its frequency, one that turns less than beta_slow times is slowed
    `rope_factor` times, with a linear ramp over the pairs between."""
    dim = cfg.qk_rope_head_dim
    i = np.arange(dim // 2, dtype=np.float64)
    f = cfg.rope_theta ** (-2.0 * i / dim)

    def corr(turns):
        return dim * math.log(cfg.rope_original / (2 * math.pi * turns)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(corr(cfg.beta_fast)), 0)
    high = min(math.ceil(corr(cfg.beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / cfg.rope_factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def _angles(pos, cfg):
    ang = jnp.asarray(pos, F32)[..., None] * yarn_freqs(cfg)
    return jnp.cos(ang), jnp.sin(ang)


def rope_pairs(x, pos, cfg: DeepSeekV32Config):
    """Rotary positions on x[..., D], ADJACENT pairs (2i, 2i+1) rotated
    (the latent attention's convention); `pos` broadcasts against x's
    leading axes."""
    cos, sin = _angles(pos, cfg)
    xf = x.astype(F32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    a, b = xf[..., 0], xf[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rope_halves(x, pos, cfg: DeepSeekV32Config):
    """The same angles with the two HALVES rotated (the indexer's
    convention)."""
    cos, sin = _angles(pos, cfg)
    half = x.shape[-1] // 2
    a, b = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).astype(x.dtype)


# -- the selection -----------------------------------------------------------
def select_topk(scores, k: int):
    """scores[R, S] float32 → bool [R, S]: the `min(k, S)` largest of
    each row, ties to the lower position — the set `lax.top_k` returns,
    without its sort. The k-th largest value is found by bisecting on the
    scores' bit patterns (32 counts over the row; a float's bits, the
    magnitude flipped under a set sign, order as the floats do); what
    lies above it is in, and of what equals it the first positions that
    fill the k."""
    s = scores.shape[-1]
    if k >= s:
        return jnp.ones(scores.shape, bool)
    u32 = jnp.uint32
    scores = jnp.where(scores == 0, 0.0, scores)        # -0.0 ties with 0.0
    bits = jax.lax.bitcast_convert_type(scores.astype(F32), u32)
    key = jnp.where(bits >> 31 == 1, ~bits, bits | u32(1 << 31))

    def bit(i, v):
        cand = v | (u32(1) << (u32(31) - i.astype(u32)))
        n = (key >= cand[:, None]).sum(axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, v)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], u32))
    above = key > kth[:, None]
    tie = key == kth[:, None]
    need = k - above.sum(axis=-1, dtype=jnp.int32)
    rank = jnp.cumsum(tie, axis=-1, dtype=jnp.int32) - tie
    return above | (tie & (rank < need[:, None]))


# -- blocks ------------------------------------------------------------------
def layer_norm(x, p, eps):
    xf = x.astype(F32)
    mean = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(F32) + p["bias"].astype(F32)).astype(
        x.dtype)


def route(x, p, cfg: DeepSeekV32Config):
    """x[T, d] → (chosen[T, k] expert ids over ALL experts, w[T, k] f32),
    group-limited: b = sigmoid scores + bias; a group of consecutive
    experts is scored by the sum of its two largest b; the `topk_group`
    best groups are kept and the chosen are the top-k of b inside them.
    `lax.top_k` puts the lower index first among equals, so a tie goes
    to the lower group and to the lower expert id on every backend. The
    weights are the chosen's scores WITHOUT the bias, normalised and
    scaled."""
    s = jax.nn.sigmoid(jnp.dot(x, p["router"]["kernel"],
                               preferred_element_type=F32))
    b = s + p["expert_bias"].astype(F32)
    t = b.shape[0]
    per = cfg.num_experts // cfg.n_group
    two, _ = jax.lax.top_k(b.reshape(t, cfg.n_group, per), 2)
    _, groups = jax.lax.top_k(two.sum(axis=-1), cfg.topk_group)
    kept = (groups[..., None] == jnp.arange(cfg.n_group)).any(axis=1)
    kept = jnp.repeat(kept, per, axis=-1)                     # [T, E]
    _, chosen = jax.lax.top_k(jnp.where(kept, b, -jnp.inf),
                              cfg.experts_per_token)
    sc = jnp.take_along_axis(s, chosen, axis=-1)
    return chosen, sc / sc.sum(axis=-1, keepdims=True) * cfg.route_scale


def moe(x, p, cfg: DeepSeekV32Config):
    """x[T, d] → (shared(x) + held experts' part, held assignments)."""
    with jax.named_scope("routed_experts"):
        chosen, w = route(x, p, cfg)
        y, n_held = routed_experts(x, chosen, w, p["experts"], cfg)
    return swiglu(x, p["shared"]) + y, n_held


def _ffn(x, lp, kind, cfg):
    """The layer's second half on x[T, d]; returns (x', held)."""
    h = rms_norm(x, lp["ffn_norm"]["scale"], cfg.eps)
    if kind == "dense":
        return x + swiglu(h, lp["mlp"]), jnp.zeros((), jnp.int32)
    y, n_held = moe(h, lp["moe"], cfg)
    return x + y, n_held


def _queries(h, lp, pos, cfg):
    """h[T, d] at positions pos[T] → q_nope[T, H, 128], q_pe[T, H, 64]
    (rotated), the indexer's q_i[T, Hi, 128] (first dims rotated) and
    its head weights w[T, Hi] float32; (q_nope, q_pe, None, None) for a
    layer with no indexer. `cfg` is the attention's shape (this model's
    config, or a `models.dots3.model.MLA`)."""
    ap = lp["attn"]
    t = h.shape[0]
    c_q = rms_norm(_dot(h, ap["wq_a"]["kernel"]), ap["q_norm"]["scale"],
                   cfg.eps)
    q = _dot(c_q, ap["wq_b"]["kernel"]).reshape(t, cfg.heads,
                                                cfg.qk_head_dim)
    if cfg.q_scale is not None:
        q = q * cfg.q_scale
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_pe = rope_pairs(q[..., cfg.qk_nope_head_dim:], pos[:, None], cfg)
    if "indexer" not in lp:
        return q_nope, q_pe, None, None
    ip = lp["indexer"]
    q_i = _dot(c_q, ip["wq_b"]["kernel"]).reshape(
        t, cfg.index_heads, cfg.index_head_dim)
    r = cfg.qk_rope_head_dim
    q_i = jnp.concatenate(
        [rope_halves(q_i[..., :r], pos[:, None], cfg), q_i[..., r:]], -1)
    w = jnp.dot(h, ip["weights_proj"]["kernel"],
                preferred_element_type=F32) \
        * (cfg.index_heads ** -0.5 * cfg.index_head_dim ** -0.5)
    return q_nope, q_pe, q_i, w


def _keys(h, lp, pos, cfg):
    """h[T, d] at positions pos[T] → the rows the two caches keep:
    latent[T, 576] = kv_norm(c_kv) | rope(k_pe), and the indexer's
    k_i[T, 128] = LayerNorm, first dims rotated (None for a layer with
    no indexer)."""
    ap = lp["attn"]
    kv = _dot(h, ap["wkv_a"]["kernel"])
    c = cfg.kv_lora_rank
    c_kv = rms_norm(kv[..., :c], ap["kv_norm"]["scale"], cfg.eps)
    if cfg.kv_scale is not None:
        c_kv = c_kv * cfg.kv_scale
    latent = jnp.concatenate([c_kv, rope_pairs(kv[..., c:], pos, cfg)],
                             axis=-1)
    if "indexer" not in lp:
        return latent, None
    ip = lp["indexer"]
    k_i = layer_norm(_dot(h, ip["wk"]["kernel"]), ip["k_norm"], cfg.eps)
    r = cfg.qk_rope_head_dim
    k_i = jnp.concatenate(
        [rope_halves(k_i[..., :r], pos, cfg), k_i[..., r:]], axis=-1)
    return latent, k_i


def _index_scores(q_i, w, k_i):
    """I[t, s] = Σ_j w[t, j] · relu(q_i[t, j] · k_i[s]), float32 (the
    operands widened first: the product is the same, and the CPU
    backend has no bfloat16 dot inside the fusion it makes of this)."""
    dots = jnp.einsum("thd,sd->ths", q_i.astype(F32), k_i.astype(F32))
    return (jax.nn.relu(dots) * w[..., None]).sum(axis=1)


def _block(p: int, heads: int) -> int:
    """Rows of a prefill block (query rows and key rows alike) for a
    prompt of `p` positions, from the static shapes alone: the largest
    divisor of p whose float32 score block [heads, rows, rows] stays
    within `_SCORE_BYTES` — 512 at 128 heads and a 16,384 edge."""
    most = max(1, math.isqrt(_SCORE_BYTES // (4 * heads)))
    return max(b for b in range(1, min(p, most) + 1) if p % b == 0)


def _routed_bytes(rows: int, cfg: DeepSeekV32Config) -> int:
    """Bytes `routed_experts` sets aside for `rows` tokens: its two row
    buffers, each sized for every assignment held and every held expert
    a ragged tail, and the gathered [rows, k, d] before the combine."""
    k = cfg.experts_per_token
    tile = expert_tile(rows, cfg)
    buf = (-(-rows * k // tile) + cfg.n_held) * tile
    return (2 * buf + rows * k) * cfg.hidden * cfg.jdtype.itemsize


def _ffn_rows(p: int, cfg: DeepSeekV32Config) -> int:
    """Rows of a prefill FFN chunk for a prompt of `p` positions, from the
    static shapes alone: the largest multiple of `_block`'s rows that
    divides p and whose routed temporaries stay within `_FFN_BYTES` —
    4,096 at the cell's shapes (16,384 positions, 16 of 256 experts held,
    8 a token), where calls of 512 rows would read every held expert's
    kernels 32 times a sequence; `_block`'s rows where none fits."""
    blk = _block(p, cfg.heads)
    fits = [r for r in range(blk, p + 1, blk)
            if p % r == 0 and _routed_bytes(r, cfg) <= _FFN_BYTES]
    return max(fits, default=blk)


def _head_gate(o, h, ap, heads: int):
    """o[..., H·dv] with each head's output times sigmoid(h·W_g)[..., H]
    (models/dots3's headwise gate, read off the attention's normed input
    h), or o itself where the layer has no `gate`."""
    if "gate" not in ap:
        return o
    g = jax.nn.sigmoid(jnp.dot(h, ap["gate"]["kernel"],
                               preferred_element_type=F32))
    o = o.reshape(*o.shape[:-1], heads, -1)
    return (o.astype(F32) * g[..., None]).astype(o.dtype).reshape(
        *o.shape[:-2], -1)


def _prefill_attention(x, lp, cfg):
    """The attention half of a block on one sequence x[P, d] → (x',
    latent[P, 576], k_i[P, 128]): a `_block` of query rows at a time over
    the key blocks up to its diagonal, under the selection. `cfg` is the
    attention's shape (models/dots3 passes its full layers' `MLA`)."""
    p = x.shape[0]
    nh, dn = cfg.heads, cfg.qk_nope_head_dim
    blk = _block(p, nh)
    n_blk = p // blk
    k_sel = min(cfg.index_topk, p)
    pos = jnp.arange(p)
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["attn_norm"]["scale"], cfg.eps)
        latent, k_i = _keys(h, lp, pos, cfg)
        c = cfg.kv_lora_rank
        # keys and values of every head, expanded from the latents once
        # a layer (the per-head form); the rotary key stays one row for
        # all
        attend = selected_attention(
            _dot(latent[:, :c], lp["attn"]["wkv_b"]["kernel"]), nh, dn,
            scale=cfg.softmax_scale)
        k_pe = latent[:, c:]
    rows = jnp.arange(blk)

    def block(i):
        r0 = i * blk
        with jax.named_scope("attention"):
            xb = jax.lax.dynamic_slice_in_dim(x, r0, blk)
            hb = jax.lax.dynamic_slice_in_dim(h, r0, blk)
            q_nope, q_pe, q_i, w = _queries(hb, lp, r0 + rows, cfg)
            qpos = r0 + rows[:, None]

            def causal(j):
                return j * blk + rows[None, :] <= qpos

            if k_sel < p:
                # the index scores of this block's rows against every
                # key block up to the diagonal, then the selection over
                # the row
                def score(j, acc):
                    kb = jax.lax.dynamic_slice_in_dim(k_i, j * blk, blk)
                    sc = jnp.where(causal(j), _index_scores(q_i, w, kb),
                                   -jnp.inf)
                    return jax.lax.dynamic_update_slice_in_dim(acc, sc,
                                                               j * blk, 1)

                with jax.named_scope("indexer"):
                    index = jax.lax.fori_loop(
                        0, i + 1, score, jnp.full((blk, p), -jnp.inf, F32))
                    keep = select_topk(index, k_sel)
            else:
                keep = jnp.ones((blk, p), bool)

            o = attend(q_nope, q_pe, k_pe, keep, i, rows, qpos)
            o = _head_gate(o, hb, lp["attn"], nh)
            return xb + _dot(o, lp["attn"]["wo"]["kernel"])

    x = jax.lax.map(block, jnp.arange(n_blk)).reshape(p, x.shape[1])
    return x, latent, k_i


def _prefill_ffn(x, lp, kind, cfg):
    """The FFN half of a block on one sequence x[P, d] → (x', held), a
    `_ffn_rows` chunk at a time (a token's FFN reads its own row alone,
    so the chunk is free of the score blocks' bound, and a larger one
    routes more rows to each expert's tile)."""
    p, d = x.shape
    n = _ffn_rows(p, cfg)
    out, held = jax.lax.map(lambda xc: _ffn(xc, lp, kind, cfg),
                            x.reshape(p // n, n, d))
    return out.reshape(p, d), held.sum(dtype=jnp.int32)


def _prefill_layer(x, lp, kind, cfg: DeepSeekV32Config):
    """One block on one sequence x[P, d] → (x', latent[P, 576],
    k_i[P, 128], held), in two passes: attention a `_block` of query rows
    at a time, then the FFN a `_ffn_rows` chunk at a time."""
    x, latent, k_i = _prefill_attention(x, lp, cfg)
    x, held = _prefill_ffn(x, lp, kind, cfg)
    return x, latent, k_i, held


def _prefill_piece(params, ids, total: int, cfg: DeepSeekV32Config):
    """One sequence ids[P] → (last hidden [d], ((latent, k_i), ...)
    caches of `total` rows, held)."""
    p = ids.shape[0]
    x = _embed(params, ids, cfg)
    held = jnp.zeros((), jnp.int32)
    caches = []
    for i, kind in enumerate(cfg.layers):
        x, latent, k_i, n = _prefill_layer(x, params[f"layer_{i}"], kind,
                                           cfg)
        held = held + n
        caches.append(tuple(jnp.pad(c, ((0, total - p), (0, 0)))
                            for c in (latent, k_i)))
    return x[-1], tuple(caches), held


def _embed(params, ids, cfg: DeepSeekV32Config):
    """This chip's share of the embedding: rows of its slice, zeros for
    ids that live on another chip."""
    lo, _ = cfg.vocab_rows
    local = ids - lo
    mine = (local >= 0) & (local < cfg.n_vocab)
    x = params["embed"]["embedding"][jnp.clip(local, 0, cfg.n_vocab - 1)]
    return jnp.where(mine[..., None], x,
                     jnp.zeros((), x.dtype)).astype(cfg.jdtype)


def n_moe(cfg: DeepSeekV32Config) -> int:
    return sum(1 for kind in cfg.layers if kind == "moe")


def prefill(params, ids, total: int, cfg: DeepSeekV32Config):
    """ids[B, P] → (logits[B, V'] f32 at the last prompt position, carry).

    The batch is walked a sequence at a time (`lax.map`) and a sequence's
    layer a block of rows at a time through attention and a chunk of rows
    at a time through the FFN, so one block's or one chunk's temporaries
    — not a sequence's, not the batch's — sit beside the weights. carry =
    (per-layer (latent [B, T, 576], k_i [B, T, 128]) caches, int32
    [assignments, held])."""
    b, p = ids.shape
    last, caches, held = jax.lax.map(
        lambda row: _prefill_piece(params, row, total, cfg), ids)
    made = jnp.int32(b * p * cfg.experts_per_token * n_moe(cfg))
    stats = jnp.stack([made, held.sum(dtype=jnp.int32)])
    return _logits(params, last, cfg), (caches, stats)


def _decode_attention(q_nope, q_pe, lat, keep, ap, cfg, h=None):
    """The latent form on the cache lat[B, T, 576] under keep[B, T]:
    W_UK folded into the query, softmax over the kept rows, the weighted
    sum of latents, W_UV after, each head gated by the attention's normed
    input h[B, d] where the layer has a `gate` (`_head_gate`)."""
    nh, dn, dv = cfg.heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    c = cfg.kv_lora_rank
    wkv_b = ap["wkv_b"]["kernel"].reshape(c, nh, dn + dv)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, wkv_b[..., :dn],
                       preferred_element_type=F32).astype(lat.dtype)
    q = jnp.concatenate([q_lat, q_pe], axis=-1)             # [B, H, 576]
    s = jnp.einsum("bhc,btc->bht", q, lat,
                   preferred_element_type=F32) * cfg.softmax_scale
    s = jnp.where(keep[:, None], s, _NEG)
    att = jax.nn.softmax(s, axis=-1).astype(lat.dtype)
    o_lat = jnp.einsum("bht,btc->bhc", att, lat[..., :c],
                       preferred_element_type=F32).astype(lat.dtype)
    o = jnp.einsum("bhc,chd->bhd", o_lat, wkv_b[..., dn:],
                   preferred_element_type=F32).astype(lat.dtype)
    o = _head_gate(o.reshape(o.shape[0], nh * dv), h, ap, nh)
    return _dot(o, ap["wo"]["kernel"])


def _decode_layer_attention(x, lp, lat, k_i, pos, at, cfg):
    """The attention half of a decode step on x[B, d] at position `pos`
    (`at`: it for every row) → (x', lat, k_i): this position's latent and
    indexer key written at row `pos`, the selection over every row up to
    it, attention over it in the latent form. `cfg` is the attention's
    shape, as `_prefill_attention`'s."""
    b = x.shape[0]
    t = lat.shape[1]
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["attn_norm"]["scale"], cfg.eps)
        q_nope, q_pe, q_i, w = _queries(h, lp, at, cfg)
        row, k_row = _keys(h, lp, at, cfg)
        lat = jax.lax.dynamic_update_slice(
            lat, row[:, None].astype(lat.dtype), (0, pos, 0))
        k_i = jax.lax.dynamic_update_slice(
            k_i, k_row[:, None].astype(k_i.dtype), (0, pos, 0))
        keep = jnp.broadcast_to(jnp.arange(t) <= pos, (b, t))
        if cfg.index_topk < t:
            with jax.named_scope("indexer"):
                index = jax.vmap(lambda q, wt, k: _index_scores(
                    q[None], wt[None], k)[0])(q_i, w, k_i)
                keep &= select_topk(jnp.where(keep, index, -jnp.inf),
                                    cfg.index_topk)
        x = x + _decode_attention(q_nope, q_pe, lat, keep, lp["attn"], cfg,
                                  h)
    return x, lat, k_i


def decode(params, tok, carry, pos, cfg: DeepSeekV32Config):
    """tok[B] int32 at position `pos` → (logits[B, V'] f32 for the next
    position, carry). A layer writes this position's latent and indexer
    key at row `pos`, scores every row up to it, keeps the selection and
    attends over it in the latent form."""
    caches, stats = carry
    b = tok.shape[0]
    x = _embed(params, tok, cfg)
    held = jnp.zeros((), jnp.int32)
    at = jnp.full((b,), pos)
    new = []
    for i, kind in enumerate(cfg.layers):
        lp = params[f"layer_{i}"]
        x, lat, k_i = _decode_layer_attention(x, lp, *caches[i], pos, at,
                                              cfg)
        x, n = _ffn(x, lp, kind, cfg)
        held = held + n
        new.append((lat, k_i))
    made = jnp.int32(b * cfg.experts_per_token * n_moe(cfg))
    stats = stats + jnp.stack([made, held])
    return _logits(params, x, cfg), (tuple(new), stats)
