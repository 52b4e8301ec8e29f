"""Trinity (`afmoe`) decoder — one chip's share, with two kinds of cache.

The architecture of arcee-ai/Trinity-Large-Preview as `transformers`'
`modeling_afmoe.py` computes it (docs/text-serving.md lists each point):
muP-scaled embeddings, sandwich RMSNorm blocks, grouped-query attention
with q/k RMSNorm, a sigmoid output gate and rotary positions in the
SLIDING layers only (full layers carry no positions), a leading run of
dense SwiGLU layers and then expert layers: a sigmoid router over all
experts, top-k of score + bias, normalised and scaled weights, one
shared expert.

What makes it this repo's: the config says which routed experts and
which vocabulary rows THIS chip holds (`experts_held`, `vocab_rows`).
The router always routes over all `num_experts`; the expert layer
computes its own experts' part of the result for the tokens sent to
them and nothing else — no code stands in for the absent chips, and on
one chip the layer runs without its exchange. With everything held the
same code is the whole model (the tiny CPU tests).

Same split API as TextGenModel, pure functions of an explicit param
tree: `prefill(params, ids, total)` and `decode(params, tok, carry,
pos)`. The carry holds TWO kinds of cache: a full-attention layer
keeps every row (prompt + decode), a sliding layer keeps `window` rows
as a ring written at `pos mod window`; and an int32 pair counting the
routers' (token, choice) assignments and how many fell on held experts.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from arbius_tpu.ops import grouped
from arbius_tpu.ops.causal_flash import causal_attention

_NEG = -1e30
F32 = jnp.float32

# published layer pattern: three sliding layers then one full one
PERIOD = ("sliding", "sliding", "sliding", "full")


@dataclass(frozen=True)
class TrinityConfig:
    vocab_size: int = 200192
    # the slice [lo, hi) of the vocabulary whose embedding and head rows
    # live here; ids, logits and sampling are over the slice
    vocab_rows: tuple = (0, 200192)
    hidden: int = 3072
    heads: int = 48
    kv_heads: int = 8
    head_dim: int = 128
    dense_ff: int = 12288
    expert_ff: int = 3072
    num_experts: int = 256
    # the range [lo, hi) of each layer's routed experts held here
    experts_held: tuple = (0, 256)
    experts_per_token: int = 4
    route_scale: float = 2.448
    window: int = 4096
    # one (mlp, attention) kind a layer: mlp "dense" | "moe", attention
    # "sliding" | "full"
    layers: tuple = ()
    rope_theta: float = 10000.0
    eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layers",
                           tuple(tuple(k) for k in self.layers))
        for name in ("vocab_rows", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.heads % self.kv_heads:
            raise ValueError("heads must be a multiple of kv_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary halves)")
        for mlp, attn in self.layers:
            if mlp not in ("dense", "moe") or attn not in ("sliding",
                                                           "full"):
                raise ValueError(f"unknown layer kind {(mlp, attn)!r}")
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
        return 262144

    @property
    def group(self) -> int:
        return self.heads // self.kv_heads

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_vocab(self) -> int:
        return self.vocab_rows[1] - self.vocab_rows[0]

    @staticmethod
    def pattern(dense: int, moe: int) -> tuple:
        """`dense` leading dense layers then `moe` expert layers, the
        attention kinds cycling through PERIOD from layer 0."""
        mlps = ("dense",) * dense + ("moe",) * moe
        return tuple((m, PERIOD[i % len(PERIOD)])
                     for i, m in enumerate(mlps))

    @classmethod
    def published(cls) -> "TrinityConfig":
        """Trinity-Large-Preview whole: 6 dense + 54 expert layers."""
        return cls(layers=cls.pattern(6, 54))

    @classmethod
    def tiny(cls, **over) -> "TrinityConfig":
        """The whole model at a size the CPU tests run: every mechanism
        (both caches, grouped heads, all experts held), no published
        width."""
        kw = dict(vocab_size=512, vocab_rows=(0, 512), hidden=32, heads=4,
                  kv_heads=2, head_dim=8, dense_ff=64, expert_ff=16,
                  num_experts=8, experts_held=(0, 8), experts_per_token=2,
                  window=8, layers=cls.pattern(1, 4))
        kw.update(over)
        return cls(**kw)

    def cache_rows(self, attn: str, total: int) -> int:
        """Rows a layer of this attention kind keeps for a sequence of
        `total` positions: all of them, or the ring."""
        return total if attn == "full" else min(self.window, total)

    def kv_rows(self, total: int) -> tuple[int, int]:
        """(rows the carry holds, rows it would hold with every layer at
        full length), summed over layers, for one sequence."""
        held = sum(self.cache_rows(attn, total) for _, attn in self.layers)
        return held, total * len(self.layers)


# -- parameters --------------------------------------------------------------
def param_shapes(cfg: TrinityConfig) -> dict:
    """{path: shape} of the tree a node loads — leaf names are the
    checkpoint format (`kernel` for every matrix, `scale` for every
    norm gain; stacked expert kernels lead with the experts held)."""
    d, hd = cfg.hidden, cfg.head_dim
    q = cfg.heads * hd
    kv = cfg.kv_heads * hd

    def swiglu(ff, lead=()):
        return {"gate": {"kernel": lead + (d, ff)},
                "up": {"kernel": lead + (d, ff)},
                "down": {"kernel": lead + (ff, d)}}

    tree = {"embed": {"embedding": (cfg.n_vocab, d)},
            "final_norm": {"scale": (d,)},
            "head": {"kernel": (d, cfg.n_vocab)}}
    for i, (mlp, _) in enumerate(cfg.layers):
        layer = {
            "input_norm": {"scale": (d,)},
            "post_attn_norm": {"scale": (d,)},
            "pre_mlp_norm": {"scale": (d,)},
            "post_mlp_norm": {"scale": (d,)},
            "attn": {"q": {"kernel": (d, q)}, "k": {"kernel": (d, kv)},
                     "v": {"kernel": (d, kv)}, "gate": {"kernel": (d, q)},
                     "o": {"kernel": (q, d)},
                     "q_norm": {"scale": (hd,)},
                     "k_norm": {"scale": (hd,)}},
        }
        if mlp == "dense":
            layer["mlp"] = swiglu(cfg.dense_ff)
        else:
            layer["moe"] = {
                "router": {"kernel": (d, cfg.num_experts)},
                "expert_bias": (cfg.num_experts,),
                "shared": swiglu(cfg.expert_ff),
                "experts": swiglu(cfg.expert_ff, (cfg.n_held,)),
            }
        tree[f"layer_{i}"] = layer
    return tree


def init_params(cfg: TrinityConfig, key):
    """Seeded random tree for this config (`init_tree`)."""
    return init_tree(param_shapes(cfg), key)


def init_tree(shapes: dict, key):
    """Seeded random tree (float32; the pipeline casts) for a
    {path: shape} layout: kernels N(0, 1/fan_in), embeddings
    N(0, 0.02²) as the source initialises them, gains 1, biases 0."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(key, i)
        if name == "kernel":
            fan_in = shape[-2]
            leaves.append(jax.random.normal(k, shape, F32)
                          / math.sqrt(fan_in))
        elif name == "embedding":
            leaves.append(jax.random.normal(k, shape, F32) * 0.02)
        elif name == "scale":
            leaves.append(jnp.ones(shape, F32))
        else:
            leaves.append(jnp.zeros(shape, F32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# -- blocks ------------------------------------------------------------------
def _dot(x, w, out=None):
    """x[..., K] · w[K, N]; products accumulate in float32 on every
    backend, `out` is the type they are rounded to (the operands')."""
    return jnp.dot(x, w, preferred_element_type=F32).astype(
        out or x.dtype)


def rms_norm(x, scale, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(F32)).astype(x.dtype)


def rope(x, pos, theta):
    """Rotary positions, the two halves rotated, on x[..., D]; `pos`
    broadcasts against x's leading axes (a scalar, or [S, 1] for
    x[B, S, heads, D])."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[..., None] * inv          # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def swiglu(x, p):
    g, u = _dot(x, p["gate"]["kernel"]), _dot(x, p["up"]["kernel"])
    h = (jax.nn.silu(g.astype(F32)) * u.astype(F32)).astype(x.dtype)
    return _dot(h, p["down"]["kernel"])


def relu2(x, p):
    """The non-gated expert of models/nemotron_h: (relu(x·W_up)²)·W_down,
    the square taken in float32 and rounded to the operands' type."""
    u = _dot(x, p["up"]["kernel"])
    h = jnp.square(jax.nn.relu(u.astype(F32))).astype(x.dtype)
    return _dot(h, p["down"]["kernel"])


# an expert's form: its kernels, in the order the products read them,
# and the function of one expert (`ops.grouped.grouped_mlp` is the same
# form over a call's used tiles)
EXPERT_FORMS = {"swiglu": (("gate", "up", "down"), swiglu),
                "relu2": (("up", "down"), relu2)}


def route(x, p, cfg: TrinityConfig):
    """x[T, d] → (chosen[T, k] expert ids over ALL experts, w[T, k] f32).

    Scores and weights are float32. The chosen are the top-k of score +
    bias; `lax.top_k` puts the lower index first among equal values, so
    a tie goes to the lower expert id on every backend."""
    s = jax.nn.sigmoid(jnp.dot(x, p["router"]["kernel"],
                               preferred_element_type=F32))
    _, chosen = jax.lax.top_k(s + p["expert_bias"].astype(F32),
                              cfg.experts_per_token)
    sc = jnp.take_along_axis(s, chosen, axis=-1)
    w = sc / (sc.sum(axis=-1, keepdims=True) + 1e-20) * cfg.route_scale
    return chosen, w


def expert_tile(tokens: int, cfg: TrinityConfig) -> int:
    """Rows a grouped-expert tile for `tokens` routed at once, from the
    static shape alone: the power of two that holds twice the rows an
    expert expects (tokens · k / experts), so that most groups are one
    tile and an expert's kernels stream once, between 8 rows (a decode
    step's handful) and 512 (past that the MXU gains nothing and the
    ragged tail only grows)."""
    expect = tokens * cfg.experts_per_token / cfg.num_experts
    return min(512, max(8, 1 << math.ceil(math.log2(max(1.0, 2 * expect)))))


def grouped_walk(tokens: int, cfg: TrinityConfig) -> bool:
    """Whether a `routed_experts` call of `tokens` rows walks its tiles
    with the grouped product here (`ops.grouped.kernel_serves`), from the
    static shape alone: its tile rows, and the held experts it can reach
    — no more than the held assignments it expects (tokens · k · held /
    experts), nor than the experts held."""
    reach = min(cfg.n_held, -(-tokens * cfg.experts_per_token * cfg.n_held
                              // cfg.num_experts))
    return grouped.kernel_serves(expert_tile(tokens, cfg), reach)


def routed_experts(x, chosen, w, experts, cfg: TrinityConfig,
                   form: str = "swiglu"):
    """The held experts' part of Σ_i w_i · expert_i(x), and how many of
    the (token, choice) assignments fell on held experts. `form` is the
    experts' (`EXPERT_FORMS`): SwiGLU's gate, up and down kernels, or
    squared ReLU's up and down (models/nemotron_h).

    Grouped products over the stacked [held, d, f] kernels with work
    proportional to the load, in prefill and in a decode step alike:
    held assignments are sorted by expert, each expert's group padded to
    whole tiles (`expert_tile` rows, from the token count), and only the
    USED tiles are walked, so an expert no token was sent to is never
    read. The walk follows the static shape (`grouped_walk`): on the TPU,
    for small tiles in a call that reaches many experts (a decode step
    or a prefill block of joyai_llm_flash, every expert held), one
    grouped product a kernel (`ops.grouped.grouped_dot`), each grid step
    one tile of one expert; elsewhere a `fori_loop`, one expert's three
    kernels a tile.
    Dispatch and combine are gathers (no scatter at all); the combine
    is float32."""
    t, k = chosen.shape
    d = x.shape[-1]
    lo, _ = cfg.experts_held
    h = cfg.n_held
    a = t * k
    i32 = jnp.int32
    tile = expert_tile(t, cfg)

    e = chosen.reshape(a).astype(i32) - lo
    held = (e >= 0) & (e < h)
    key = jnp.where(held, e, h)                       # not held → group h
    onehot = (key[:, None] == jnp.arange(h, dtype=i32)[None]).astype(i32)
    counts = onehot.sum(axis=0)                       # [h]
    local = jnp.minimum(key, h - 1)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                               local[:, None], axis=1)[:, 0]
    tiles_e = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles_e)
    tile_start = tile_end - tiles_e
    n_tiles = tile_end[-1]
    # static bound: every assignment held, every group a ragged tail
    max_tiles = -(-a // tile) + h
    rows = max_tiles * tile
    row = tile_start[local] * tile + rank             # [a], where held

    order = jnp.argsort(key, stable=True).astype(i32)
    group_start = jnp.cumsum(counts) - counts
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(max_tiles, dtype=i32),
                         side="right").astype(i32), h - 1)
    slot = jnp.arange(rows, dtype=i32)
    se = tile_expert[slot // tile]
    off = slot - tile_start[se] * tile
    valid = (slot // tile < n_tiles) & (off < counts[se])
    src = order[jnp.clip(group_start[se] + off, 0, a - 1)]
    tok = jnp.where(valid, src // k, t)               # t: the zero row
    xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[tok]

    names, mlp = EXPERT_FORMS[form]
    kernels = {n: experts[n]["kernel"] for n in names}

    if grouped_walk(t, cfg):
        # every group whole tiles: each grid step is one tile of one
        # expert. The expert function's roundings; rows past the used
        # tiles are never written, so the zero row is appended after the
        # products
        out = jnp.concatenate([
            grouped.grouped_mlp(xs, kernels, tiles_e * tile, tile, form),
            jnp.zeros((1, d), x.dtype)])
    else:
        def body(j, out):
            ex = tile_expert[j]
            xt = jax.lax.dynamic_slice(xs, (j * tile, 0), (tile, d))
            p = {n: {"kernel": jax.lax.dynamic_index_in_dim(
                     kern, ex, 0, keepdims=False)}
                 for n, kern in kernels.items()}
            return jax.lax.dynamic_update_slice(out, mlp(xt, p),
                                                (j * tile, 0))

        out = jax.lax.fori_loop(0, n_tiles, body,
                                jnp.zeros((rows + 1, d), x.dtype))
    y = out[jnp.where(held, row, rows)].reshape(t, k, d)
    y = (y.astype(F32) * w[..., None]).sum(axis=1)
    return y.astype(x.dtype), held.sum(dtype=i32)


def moe(x, p, cfg: TrinityConfig):
    """x[T, d] → (shared(x) + held experts' part, held assignments)."""
    with jax.named_scope("routed_experts"):
        chosen, w = route(x, p, cfg)
        y, n_held = routed_experts(x, chosen, w, p["experts"], cfg)
    return swiglu(x, p["shared"]) + y, n_held


def _mlp(x, lp, mlp_kind, cfg):
    """The layer's second half on x[..., d]; returns (x', held)."""
    h = rms_norm(x, lp["pre_mlp_norm"]["scale"], cfg.eps)
    if mlp_kind == "dense":
        y, n_held = swiglu(h, lp["mlp"]), jnp.zeros((), jnp.int32)
    else:
        flat = h.reshape(-1, cfg.hidden)
        y, n_held = moe(flat, lp["moe"], cfg)
        y = y.reshape(h.shape)
    return x + rms_norm(y, lp["post_mlp_norm"]["scale"], cfg.eps), n_held


def _qkvg(h, ap, cfg, pos, attn_kind):
    """h[..., S?, d] → q[..., KV, G, D], k, v[..., KV, D], gate[..., H·D];
    q/k RMS-normed over each head, rotary in sliding layers only."""
    lead = h.shape[:-1]
    q = _dot(h, ap["q"]["kernel"]).reshape(*lead, cfg.heads, cfg.head_dim)
    k = _dot(h, ap["k"]["kernel"]).reshape(*lead, cfg.kv_heads,
                                           cfg.head_dim)
    v = _dot(h, ap["v"]["kernel"]).reshape(*lead, cfg.kv_heads,
                                           cfg.head_dim)
    g = _dot(h, ap["gate"]["kernel"])
    q = rms_norm(q, ap["q_norm"]["scale"], cfg.eps)
    k = rms_norm(k, ap["k_norm"]["scale"], cfg.eps)
    if attn_kind == "sliding":
        # pos [S] (prefill) or scalar (decode); heads sit between the
        # position axis and D
        p = pos[..., None] if jnp.ndim(pos) else pos
        q, k = rope(q, p, cfg.rope_theta), rope(k, p, cfg.rope_theta)
    q = q.reshape(*lead, cfg.kv_heads, cfg.group, cfg.head_dim)
    return q, k, v, g


def _attn_out(o, g, ap, cfg):
    """o[..., KV, G, D] gated by sigmoid(g[..., H·D]), through Wo."""
    o = o.reshape(*o.shape[:-3], cfg.heads * cfg.head_dim)
    o = (o.astype(F32) * jax.nn.sigmoid(g.astype(F32))).astype(o.dtype)
    return _dot(o, ap["o"]["kernel"])


def _ring_fill(x, rows: int):
    """Prompt rows x[B, P, ...] → the layer's cache [B, rows, ...]: a
    full layer (rows >= P) keeps them in place; a ring shorter than the
    prompt keeps the last `rows`, position p at slot p mod rows."""
    p = x.shape[1]
    if rows >= p:
        pad = [(0, 0), (0, rows - p)] + [(0, 0)] * (x.ndim - 2)
        return jnp.pad(x, pad)
    return jnp.roll(x[:, p - rows:], (p - rows) % rows, axis=1)


def _prefill_piece(params, ids, total: int, cfg: TrinityConfig):
    """One sequence ids[1, P] → (last hidden [1, d], ((k, v), ...)
    caches, held)."""
    dt = cfg.jdtype
    p = ids.shape[1]
    x = _embed(params, ids, cfg)
    pos = jnp.arange(p)
    held = jnp.zeros((), jnp.int32)
    kv = []
    for i, (mlp_kind, attn_kind) in enumerate(cfg.layers):
        lp = params[f"layer_{i}"]
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["input_norm"]["scale"], cfg.eps)
            q, k, v, g = _qkvg(h, lp["attn"], cfg, pos, attn_kind)
            # the path is read off the call (ops/causal_flash.py): the
            # Pallas kernel on a TPU at long prompts, else the XLA walk.
            # No GSPMD program reaches it — trinity ships no mesh layout;
            # one would need `ops.flash.on_mesh`'s treatment (ROADMAP D10)
            o = causal_attention(
                q, k, v,
                window=cfg.window if attn_kind == "sliding" else None)
            a = _attn_out(o, g, lp["attn"], cfg)
            x = x + rms_norm(a, lp["post_attn_norm"]["scale"], cfg.eps)
        x, n = _mlp(x, lp, mlp_kind, cfg)
        held = held + n
        rows = cfg.cache_rows(attn_kind, total)
        kv.append((_ring_fill(k.astype(dt), rows),
                   _ring_fill(v.astype(dt), rows)))
    return x[:, -1], tuple(kv), held


def _embed(params, ids, cfg: TrinityConfig):
    """This chip's share of the muP-scaled embedding: rows of its slice,
    zeros for ids that live on another chip."""
    lo, _ = cfg.vocab_rows
    table = params["embed"]["embedding"]
    local = ids - lo
    mine = (local >= 0) & (local < cfg.n_vocab)
    x = table[jnp.clip(local, 0, cfg.n_vocab - 1)]
    x = jnp.where(mine[..., None], x, jnp.zeros((), x.dtype))
    return (x * math.sqrt(cfg.hidden)).astype(cfg.jdtype)


def _logits(params, x, cfg: TrinityConfig):
    """x[B, d] → float32 logits over this chip's vocabulary rows."""
    h = rms_norm(x, params["final_norm"]["scale"], cfg.eps)
    return jnp.dot(h, params["head"]["kernel"],
                   preferred_element_type=F32)


def n_moe(cfg: TrinityConfig) -> int:
    return sum(1 for m, _ in cfg.layers if m == "moe")


def prefill(params, ids, total: int, cfg: TrinityConfig):
    """ids[B, P] → (logits[B, V'] f32 at the last prompt position, carry).

    The batch is walked a sequence at a time (`lax.map`), so the
    temporaries of one sequence — not of the batch — sit beside the
    weights. carry = (per-layer (k, v) caches, int32 [assignments,
    held])."""
    b, p = ids.shape
    last, kv, held = jax.lax.map(
        lambda row: _prefill_piece(params, row[None], total, cfg), ids)
    kv = jax.tree_util.tree_map(
        lambda c: c.reshape(b, *c.shape[2:]), kv)
    made = jnp.int32(b * p * cfg.experts_per_token * n_moe(cfg))
    stats = jnp.stack([made, held.sum(dtype=jnp.int32)])
    return _logits(params, last.reshape(b, cfg.hidden), cfg), (kv, stats)


def _decode_attention(q, k_cache, v_cache, valid):
    """q[B, KV, G, D] over the cache rows [B, S, KV, D] that `valid`
    [S] marks; the cache is read once, in its own type."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bkgd,bskd->bkgs", q, k_cache,
                        preferred_element_type=F32) * scale
    logits = jnp.where(valid[None, None, None], logits, _NEG)
    att = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bkgs,bskd->bkgd", att, v_cache)


def decode(params, tok, carry, pos, cfg: TrinityConfig):
    """tok[B] int32 at position `pos` → (logits[B, V'] f32 for the next
    position, carry). A layer writes this position's K/V at row `pos`
    (full) or `pos mod rows` (ring); a ring slot j then holds position
    pos - ((pos - j) mod rows), valid once that is >= 0 — every slot of
    a ring is inside the window by construction."""
    kv, stats = carry
    b = tok.shape[0]
    x = _embed(params, tok, cfg)
    held = jnp.zeros((), jnp.int32)
    new_kv = []
    for i, (mlp_kind, attn_kind) in enumerate(cfg.layers):
        lp = params[f"layer_{i}"]
        k_cache, v_cache = kv[i]
        rows = k_cache.shape[1]
        with jax.named_scope("attention"):
            h = rms_norm(x, lp["input_norm"]["scale"], cfg.eps)
            q, k, v, g = _qkvg(h, lp["attn"], cfg, pos, attn_kind)
            slot = pos % rows if attn_kind == "sliding" else pos
            k_cache = jax.lax.dynamic_update_slice(
                k_cache, k[:, None].astype(k_cache.dtype), (0, slot, 0, 0))
            v_cache = jax.lax.dynamic_update_slice(
                v_cache, v[:, None].astype(v_cache.dtype), (0, slot, 0, 0))
            j = jnp.arange(rows)
            if attn_kind == "sliding":
                valid = pos - ((pos - j) % rows) >= 0
            else:
                valid = j <= pos
            o = _decode_attention(q, k_cache, v_cache, valid)
            a = _attn_out(o, g, lp["attn"], cfg)
            x = x + rms_norm(a, lp["post_attn_norm"]["scale"], cfg.eps)
        x, n = _mlp(x, lp, mlp_kind, cfg)
        held = held + n
        new_kv.append((k_cache, v_cache))
    made = jnp.int32(b * cfg.experts_per_token * n_moe(cfg))
    stats = stats + jnp.stack([made, held])
    return _logits(params, x, cfg), (tuple(new_kv), stats)
