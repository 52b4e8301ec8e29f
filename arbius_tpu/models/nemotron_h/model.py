"""Nemotron 3 Super (`nemotron_h`) decoder — Mamba-2 state-space layers
beside grouped-query attention, latent-space experts, and the multi-token
prediction module that drafts for it.

The architecture of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B as its
published `config.json` states it (docs/text-serving.md lists each
point). A layer is ONE mixer or ONE feed-forward under a pre-norm,
x += f(RMSNorm(x)), the kinds in `hybrid_override_pattern`:

  M  Mamba-2: in_proj → z[8192] | xBC[10240] | dt[128]; xBC through a
     depthwise causal conv1d (kernel 4, with bias) and SiLU, split into
     x[128 heads × 64], B[8 groups × 128], C[8 × 128], head h reading
     group h // 16; dt = softplus(dt + dt_bias), A = -exp(A_log); a head's
     state s[64 × 128]: s_t = exp(dt_t·A)·s_{t-1} + dt_t·x_t⊗B_t,
     y_t = s_t·C_t + D·x_t; then RMSNorm over groups of 1024 of
     y·SiLU(z), times its gain, and out_proj 8192 → 4096
  *  attention: 32 query heads over 2 KV heads of 128, softmax scale
     128^-1/2, no rotary embedding
  E  LatentMoE: a sigmoid router over all 512 experts on the full width
     (trinity's `route`: top-22 of score + bias, normalised, × 5); the
     routed experts in a 1,024-wide latent space — h·W_down, then
     Σ w_i · relu(u·U_i)²·D_i over the experts held here (trinity's
     `routed_experts` in its squared-ReLU form), then ·W_up — and one
     shared relu² expert of width 5,376 on the full width

and after the last layer the module (`mtp_hybrid_override_pattern` "*E"):
x_i = eh_proj([enorm(Emb(t_{i+1})); hnorm(h_i)]) through one attention
layer and one expert layer of its own, its norm and the main model's
head: a guess at token i + 2 (models/joyai_flash's module, with this
model's layers).

What this family brings is the first carry in which two kinds of state
live side by side: a Mamba layer keeps, per row, a float32 SSM state
[128, 64, 128] and the conv's window of its last 3 inputs; an attention
layer keeps K and V rows. Prefill runs the state-space layers in the
chunked (SSD) form, `chunk_size` positions a chunk, the state passed from
chunk to chunk; a decode step runs S positions a row and stores the
state after the FIRST, with the second position's increment beside it
(`MambaCache`): a recurrent state cannot be rewound by overwriting a
row, and whether a row keeps the state after q or after q + 1 is known
only once the speculative loop (models/joyai_flash/pipeline.py) has
sampled the logits. `commit` records the choice, and the next step folds
the increment in where it was taken (`settle`), in the same pass as its
own first update: one state a layer is written a step.

Pure functions of an explicit param tree, every decode-side function
general in S:
  prefill(params, ids, total, cfg)            the prompt, a sequence at a
                                              time; the module's cache rows
                                              0 .. P-2 with it
  step(params, toks[B, S], caches, q[B], cfg)  the main model on S
                                              positions a row from its own
                                              q, the Mamba layers' state
                                              after the first
  commit(caches, took[B], cfg)                the state after q + took,
                                              folded by the next step
  draft(params, toks[B, S], h[B, S, d], cache, q[B], cfg)
                                              the module on the same
                                              positions
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp

from arbius_tpu.models.deepseek_v32.model import _embed
from arbius_tpu.models.joyai_flash.model import _mtp_in, _write_rows
from arbius_tpu.models.trinity.model import (
    _dot,
    _logits,
    init_tree,
    relu2,
    rms_norm,
    route,
    routed_experts,
)
from arbius_tpu.ops.causal_flash import causal_attention

_NEG = -1e30
F32 = jnp.float32

# the published `hybrid_override_pattern`: 40 Mamba-2 layers, 40 expert
# layers and 8 attention layers, one period of 11 five in a row
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
KINDS = {"M": "mamba", "E": "moe", "*": "attention"}


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    # the slice [lo, hi) of the vocabulary whose embedding and head rows
    # live here; ids, logits and sampling are over the slice
    vocab_rows: tuple = (0, 131072)
    hidden: int = 4096
    # Mamba-2
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    heads: int = 32
    kv_heads: int = 2
    head_dim: int = 128
    # LatentMoE
    latent: int = 1024
    expert_ff: int = 2688
    shared_ff: int = 5376
    num_experts: int = 512
    # the range [lo, hi) of each layer's routed experts held here
    experts_held: tuple = (0, 512)
    experts_per_token: int = 22
    route_scale: float = 5.0
    # one kind a main layer: "mamba" | "attention" | "moe"; the module's
    # two layers are not in the list
    layers: tuple = ()
    eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))
        for name in ("vocab_rows", "experts_held"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        for kind in self.layers:
            if kind not in KINDS.values():
                raise ValueError(f"unknown layer kind {kind!r}")
        if self.heads % self.kv_heads:
            raise ValueError("heads must be a multiple of kv_heads")
        if self.mamba_heads % self.n_groups:
            raise ValueError("mamba_heads must be a multiple of n_groups")
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
        return 262144

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_vocab(self) -> int:
        return self.vocab_rows[1] - self.vocab_rows[0]

    @property
    def group(self) -> int:
        """Query heads an attention KV head serves."""
        return self.heads // self.kv_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the conv: x, B and C."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def heads_per_group(self) -> int:
        return self.mamba_heads // self.n_groups

    @staticmethod
    def pattern(letters: str) -> tuple:
        """`hybrid_override_pattern`'s letters → layer kinds."""
        return tuple(KINDS[c] for c in letters)

    @classmethod
    def published(cls) -> "NemotronHConfig":
        """Nemotron 3 Super whole: 88 layers, and the module."""
        return cls(layers=cls.pattern(PUBLISHED_PATTERN))

    @classmethod
    def tiny(cls, **over) -> "NemotronHConfig":
        """The whole model at a size the CPU tests run: every mechanism
        (two Mamba layers, attention, latent experts, all held, the
        module, chunks of 4 positions), no published width."""
        kw = dict(vocab_size=512, vocab_rows=(0, 512), hidden=32,
                  mamba_heads=4, mamba_head_dim=8, ssm_state=8, n_groups=2,
                  chunk_size=4, heads=4, kv_heads=2, head_dim=8, latent=16,
                  expert_ff=16, shared_ff=24, num_experts=8,
                  experts_held=(0, 8), experts_per_token=2,
                  layers=cls.pattern("MEM*E"))
        kw.update(over)
        return cls(**kw)

    def count(self, kind: str) -> int:
        return sum(1 for k in self.layers if k == kind)

    def ssm_state_bytes(self) -> int:
        """Bytes the carry holds for one sequence's state-space layers,
        whatever its length (`MambaCache`), a layer: a float32 state of
        every head; the pending increment — float32 exp(dt·A) a head,
        dt·x a head's channel, B a group's state —; the byte of `took`;
        and `conv_kernel` conv inputs (the window and the next one)."""
        h, pd, n = self.mamba_heads, self.mamba_head_dim, self.ssm_state
        state = h * pd * n * 4
        pending = (h + h * pd + self.n_groups * n) * 4 + 1
        conv = self.conv_kernel * self.conv_dim * self.jdtype.itemsize
        return self.count("mamba") * (state + pending + conv)

    def kv_bytes(self, total: int) -> int:
        """Bytes of K and V rows for one sequence of `total` positions:
        every attention layer's and the module's."""
        return (self.count("attention") + 1) * total * 2 * self.kv_heads \
            * self.head_dim * self.jdtype.itemsize


# -- parameters --------------------------------------------------------------
def _mamba_shapes(cfg: NemotronHConfig) -> dict:
    d, di, c, h = cfg.hidden, cfg.d_inner, cfg.conv_dim, cfg.mamba_heads
    return {"norm": {"scale": (d,)},
            "mixer": {"in_proj": {"kernel": (d, di + c + h)},
                      "conv": {"kernel": (cfg.conv_kernel, c)},
                      "conv_bias": (c,),
                      "dt_bias": (h,), "A_log": (h,), "D": (h,),
                      "norm": {"scale": (di,)},
                      "out_proj": {"kernel": (di, d)}}}


def _attention_shapes(cfg: NemotronHConfig) -> dict:
    d, hd = cfg.hidden, cfg.head_dim
    return {"norm": {"scale": (d,)},
            "attn": {"q": {"kernel": (d, cfg.heads * hd)},
                     "k": {"kernel": (d, cfg.kv_heads * hd)},
                     "v": {"kernel": (d, cfg.kv_heads * hd)},
                     "o": {"kernel": (cfg.heads * hd, d)}}}


def _moe_shapes(cfg: NemotronHConfig) -> dict:
    d, lat = cfg.hidden, cfg.latent
    return {"norm": {"scale": (d,)},
            "moe": {"router": {"kernel": (d, cfg.num_experts)},
                    "expert_bias": (cfg.num_experts,),
                    "latent_down": {"kernel": (d, lat)},
                    "latent_up": {"kernel": (lat, d)},
                    "shared": {"up": {"kernel": (d, cfg.shared_ff)},
                               "down": {"kernel": (cfg.shared_ff, d)}},
                    "experts": {
                        "up": {"kernel": (cfg.n_held, lat, cfg.expert_ff)},
                        "down": {"kernel": (cfg.n_held, cfg.expert_ff,
                                            lat)}}}}


_SHAPES = {"mamba": _mamba_shapes, "attention": _attention_shapes,
           "moe": _moe_shapes}


def param_shapes(cfg: NemotronHConfig) -> dict:
    """{path: shape} of the tree a node loads (`kernel` for every matrix,
    `scale` for every norm gain; stacked expert kernels lead with the
    experts held), and `mtp`: the module's two input norms, `eh_proj`
    [2d, d], its attention and expert layers and the norm before the
    shared head."""
    d = cfg.hidden
    tree = {"embed": {"embedding": (cfg.n_vocab, d)},
            "final_norm": {"scale": (d,)},
            "head": {"kernel": (d, cfg.n_vocab)}}
    for i, kind in enumerate(cfg.layers):
        tree[f"layer_{i}"] = _SHAPES[kind](cfg)
    tree["mtp"] = {"enorm": {"scale": (d,)}, "hnorm": {"scale": (d,)},
                   "eh_proj": {"kernel": (2 * d, d)},
                   "attention": _attention_shapes(cfg),
                   "moe": _moe_shapes(cfg),
                   "norm": {"scale": (d,)}}
    return tree


def ssm_init(cfg: NemotronHConfig, key) -> dict:
    """A Mamba layer's A_log, dt_bias and D as the published model
    initialises them: A ~ U(1, 16); dt = exp(U(log dt_min, log dt_max)),
    at least `time_step_floor`, stored as softplus⁻¹(dt); D = 1."""
    ka, kd = jax.random.split(key)
    h = cfg.mamba_heads
    a = jax.random.uniform(ka, (h,), F32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(kd, (h,), F32,
                                    math.log(cfg.time_step_min),
                                    math.log(cfg.time_step_max)))
    dt = jnp.maximum(dt, cfg.time_step_floor)
    return {"A_log": jnp.log(a), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((h,), F32)}


def init_params(cfg: NemotronHConfig, key):
    """Seeded random tree (float32; the pipeline casts): trinity's rules,
    and the state-space parameters by `ssm_init`."""
    tree = init_tree(param_shapes(cfg), key)
    for i, kind in enumerate(cfg.layers):
        if kind == "mamba":
            tree[f"layer_{i}"]["mixer"].update(
                ssm_init(cfg, jax.random.fold_in(key, 1 << 20 | i)))
    return tree


def n_moe(cfg: NemotronHConfig) -> int:
    return cfg.count("moe")


# -- Mamba-2 -----------------------------------------------------------------
def _mamba_in(h, mp, cfg):
    """h[..., d] (normed) → z[..., d_inner], xBC[..., conv_dim] before
    the conv, dt[..., H] before its bias."""
    out = _dot(h, mp["in_proj"]["kernel"])
    di, c = cfg.d_inner, cfg.conv_dim
    return out[..., :di], out[..., di:di + c], out[..., di + c:]


def _conv(buf, mp, n: int, cfg):
    """The depthwise causal conv over buf[..., n + K - 1, C] (the K - 1
    inputs before the first position, then n positions' own) → SiLU of
    it [..., n, C] in buf's type; products and sums float32."""
    w = mp["conv"]["kernel"].astype(F32)
    y = mp["conv_bias"].astype(F32)
    for i in range(cfg.conv_kernel):
        y = y + w[i] * buf[..., i:i + n, :].astype(F32)
    return jax.nn.silu(y).astype(buf.dtype)


def _ssm_inputs(xbc, dt_raw, mp, cfg):
    """The conv's output and dt before its bias → x[..., G, R, P],
    B, C [..., G, N] in xbc's type, dt[..., G, R] and A[G, R] float32 (R
    heads a group)."""
    g, r, n = cfg.n_groups, cfg.heads_per_group, cfg.ssm_state
    di = cfg.d_inner
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(*lead, g, r, cfg.mamba_head_dim)
    b = xbc[..., di:di + g * n].reshape(*lead, g, n)
    c = xbc[..., di + g * n:].reshape(*lead, g, n)
    dt = jax.nn.softplus(dt_raw.astype(F32) + mp["dt_bias"].astype(F32))
    a = -jnp.exp(mp["A_log"].astype(F32))
    return x, b, c, dt.reshape(*lead, g, r), a.reshape(g, r)


def _mamba_out(y, z, mp, cfg):
    """y[..., d_inner] float32 → out_proj(w · RMSNorm(y · SiLU(z))), the
    norm over each group of d_inner / n_groups channels."""
    g = y * jax.nn.silu(z.astype(F32))
    gs = g.reshape(*g.shape[:-1], cfg.n_groups, -1)
    gs = gs * jax.lax.rsqrt(jnp.mean(gs * gs, axis=-1, keepdims=True)
                            + cfg.eps)
    out = (gs.reshape(g.shape) * mp["norm"]["scale"].astype(F32)).astype(
        cfg.jdtype)
    return _dot(out, mp["out_proj"]["kernel"])


def ssd_chunked(x, dt, a, b, c, chunk: int):
    """The state-space recurrence over one sequence in the chunked form:
    x[P, G, R, Pd], dt[P, G, R] float32, a[G, R], b/c[P, G, N] →
    (y[P, G, R, Pd] float32 without the D skip, the state after position
    P - 1 [G, R, Pd, N] float32). A prompt that is no whole number of
    chunks is padded with dt = 0, which leaves the state as it is.

    Within a chunk y_t = Σ_{s<=t} (C_t·B_s) exp(Σ_{s<i<=t} a_i) dt_s x_s,
    a product over the chunk's positions; a chunk's own state is
    Σ_s exp(Σ_{s<i<=L-1} a_i) dt_s x_s ⊗ B_s; the states pass from chunk
    to chunk (`lax.scan`), each read back by the next chunk's C under its
    decay. The products take x, B, C in their type with float32 sums; the
    weighted scores and the weighted x are rounded to x's type for them;
    the state and its read-back are float32."""
    p = x.shape[0]
    nc = -(-p // chunk)
    pad = nc * chunk - p

    def chunks(v):
        v = jnp.pad(v, [(0, pad)] + [(0, 0)] * (v.ndim - 1))
        return v.reshape(nc, chunk, *v.shape[1:])

    xc, dtc, bc, cc = chunks(x), chunks(dt), chunks(b), chunks(c)
    acum = jnp.cumsum(dtc * a, axis=1)                    # [nc, L, G, R]
    # within a chunk: the causal scores under their decays, times dt_s
    cb = jnp.einsum("clgn,csgn->cgls", cc, bc, preferred_element_type=F32)
    at = jnp.moveaxis(acum, 1, -1)                        # [nc, G, R, L]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, at[..., :, None] - at[..., None, :],
                              -jnp.inf))                  # [nc, G, R, l, s]
    m = (cb[:, :, None] * decay * jnp.moveaxis(dtc, 1, -1)[..., None, :])
    y = jnp.einsum("cgrls,csgrp->clgrp", m.astype(x.dtype), xc,
                   preferred_element_type=F32)
    # each chunk's own state, then the states carried over
    to_end = jnp.exp(acum[:, -1:] - acum) * dtc           # [nc, L, G, R]
    xw = (xc.astype(F32) * to_end[..., None]).astype(x.dtype)
    own = jnp.einsum("csgrp,csgn->cgrpn", xw, bc, preferred_element_type=F32)

    def carry(state, chunk_in):
        decay_all, mine = chunk_in
        return decay_all[..., None, None] * state + mine, state

    g, r, pd = x.shape[1:]
    last, starts = jax.lax.scan(
        carry, jnp.zeros((g, r, pd, b.shape[-1]), F32),
        (jnp.exp(acum[:, -1]), own))
    y = y + jnp.einsum("clgn,cgrpn->clgrp", cc.astype(F32), starts,
                       precision=jax.lax.Precision.HIGHEST) \
        * jnp.exp(acum)[..., None]
    return y.reshape(nc * chunk, g, r, pd)[:p], last


def _mamba_prefill(x, lp, cfg):
    """A Mamba layer on one sequence x[P, d] → (x', the state after the
    last position [H, Pd, N] float32, the conv's window [K - 1, C])."""
    mp = lp["mixer"]
    p = x.shape[0]
    with jax.named_scope("mamba_mixer"):
        z, xbc, dt_raw = _mamba_in(rms_norm(x, lp["norm"]["scale"], cfg.eps),
                                   mp, cfg)
        buf = jnp.pad(xbc, ((cfg.conv_kernel - 1, 0), (0, 0)))
        xs, b, c, dt, a = _ssm_inputs(_conv(buf, mp, p, cfg), dt_raw, mp,
                                      cfg)
        y, state = ssd_chunked(xs, dt, a, b, c, cfg.chunk_size)
        y = y + mp["D"].astype(F32).reshape(a.shape)[..., None] \
            * xs.astype(F32)
        x = x + _mamba_out(y.reshape(p, cfg.d_inner), z, mp, cfg)
    return x, state.reshape(cfg.mamba_heads, cfg.mamba_head_dim,
                            cfg.ssm_state), buf[p:]


class MambaCache(NamedTuple):
    """A Mamba layer's entry of the carry, per row: the state after the
    first position the last step ran, and what the row needs to keep the
    state after the second instead — that position's increment and
    whether the row took it. The fold waits for the next step.

    state  [B, H, Pd, N] float32, s_q: the state after position q
    decay  [B, H] float32, exp(dt·A) at position q + 1
    dtx    [B, H, Pd] float32, dt·x at q + 1
    b      [B, G, N] float32, B at q + 1
    took   [B] bool: the row keeps the state after q + 1, not after q
    conv   [B, K, C]: the conv's window after q, then its input at q + 1

    A one-position step and prefill leave no increment: decay 1, no
    input, took False."""
    state: jax.Array
    decay: jax.Array
    dtx: jax.Array
    b: jax.Array
    took: jax.Array
    conv: jax.Array


def _mamba_cache(state, window, cfg, nxt=None):
    """A `MambaCache` from the state after q [..., H, Pd, N], the conv's
    window after q [..., K - 1, C] and, where the step ran a position
    after it, nxt = (exp(dt·A) [..., G, R], dt·x [..., G, R, Pd], B
    [..., G, N], its conv input [..., 1, C]), for any leading shape;
    `took` False."""
    lead = state.shape[:-3]
    h, pd = cfg.mamba_heads, cfg.mamba_head_dim
    if nxt is None:
        nxt = (jnp.ones((*lead, h), F32), jnp.zeros((*lead, h, pd), F32),
               jnp.zeros((*lead, cfg.n_groups, cfg.ssm_state), F32),
               jnp.zeros((*lead, 1, window.shape[-1]), window.dtype))
    decay, dtx, b, row = nxt
    return MambaCache(state, decay.reshape(*lead, h),
                      dtx.reshape(*lead, h, pd), b,
                      jnp.zeros(lead, bool),
                      jnp.concatenate([window, row], axis=-2))


def settle(cache: MambaCache, cfg):
    """The state [B, H, Pd, N] and conv window [B, K - 1, C] a row keeps:
    after q + took. The fold is the second position's own update, the
    float32 expression the step runs for it."""
    g, r = cfg.n_groups, cfg.heads_per_group
    bsz = cache.state.shape[0]
    st = cache.state.reshape(bsz, g, r, cfg.mamba_head_dim, cfg.ssm_state)
    folded = cache.decay.reshape(bsz, g, r)[..., None, None] * st \
        + cache.dtx.reshape(bsz, g, r, -1)[..., None] \
        * cache.b[:, :, None, None, :]
    took = cache.took[:, None, None]
    st = jnp.where(took[..., None, None], folded, st)
    window = jnp.where(took, cache.conv[:, 1:], cache.conv[:, :-1])
    return st.reshape(cache.state.shape), window


def _mamba_step(x, lp, cache: MambaCache, cfg):
    """A Mamba layer on x[B, S, d], S positions a row from the state and
    conv window the cache settles to → (x', the `MambaCache` after the
    first position, with the second's increment pending). The state
    after a later position feeds its y and is never stored: one state a
    layer is written a step."""
    mp = lp["mixer"]
    bsz, s, _ = x.shape
    with jax.named_scope("mamba_mixer"):
        state, window = settle(cache, cfg)
        z, xbc, dt_raw = _mamba_in(rms_norm(x, lp["norm"]["scale"], cfg.eps),
                                   mp, cfg)
        buf = jnp.concatenate([window, xbc.astype(window.dtype)], axis=1)
        xs, b, c, dt, a = _ssm_inputs(_conv(buf, mp, s, cfg), dt_raw, mp,
                                      cfg)
        xs, b, c = (v.astype(F32) for v in (xs, b, c))
        decay = [jnp.exp(dt[:, i] * a) for i in range(s)]
        dtx = [dt[:, i, ..., None] * xs[:, i] for i in range(s)]
        sts = [state.reshape(bsz, *a.shape, cfg.mamba_head_dim,
                             cfg.ssm_state)]
        for i in range(s):
            sts.append(decay[i][..., None, None] * sts[-1] + dtx[i][..., None]
                       * b[:, i, :, None, None, :])
        ys = [(st * c[:, i, :, None, None, :]).sum(axis=-1)
              for i, st in enumerate(sts[1:])]
        y = jnp.stack(ys, axis=1) \
            + mp["D"].astype(F32).reshape(a.shape)[..., None] * xs
        x = x + _mamba_out(y.reshape(bsz, s, cfg.d_inner), z, mp, cfg)
        k = cfg.conv_kernel
        nxt = (decay[1], dtx[1], b[:, 1], buf[:, k:k + 1]) if s > 1 else None
    return x, _mamba_cache(sts[1].reshape(state.shape), buf[:, 1:k], cfg,
                           nxt)


def commit(caches, took, cfg: NemotronHConfig):
    """The caches of a two-position step once its draft is decided: a
    Mamba layer records, per row, that it keeps the state and the conv
    window after position q + took (took [B] bool: after q + 1 where the
    row took both positions, after q elsewhere) — the next step folds the
    second position's increment in (`settle`), so nothing is written
    here; attention's rows stand as written."""
    return tuple(c._replace(took=took) if kind == "mamba" else c
                 for kind, c in zip(cfg.layers, caches))


# -- attention ---------------------------------------------------------------
def _queries(h, ap, cfg):
    lead = h.shape[:-1]
    return _dot(h, ap["q"]["kernel"]).reshape(*lead, cfg.kv_heads,
                                              cfg.group, cfg.head_dim)


def _keys(h, ap, cfg):
    """h[..., d] (normed) → k, v [..., KV, D]: no rotary embedding."""
    lead = h.shape[:-1]
    return tuple(_dot(h, ap[n]["kernel"]).reshape(*lead, cfg.kv_heads,
                                                  cfg.head_dim)
                 for n in ("k", "v"))


def _attn_prefill(x, lp, total: int, cfg):
    """An attention layer on one sequence x[P, d] → (x', (k, v) [total,
    KV, D] with rows 0 .. P-1 filled); causal over grouped KV heads
    through ops/causal_flash.py (its Pallas kernel on the TPU from 2,048
    positions)."""
    p = x.shape[0]
    ap = lp["attn"]
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["norm"]["scale"], cfg.eps)
        k, v = _keys(h, ap, cfg)
        o = causal_attention(_queries(h, ap, cfg)[None], k[None], v[None])[0]
        x = x + _dot(o.reshape(p, cfg.heads * cfg.head_dim),
                     ap["o"]["kernel"])
    pad = ((0, total - p), (0, 0), (0, 0))
    return x, (jnp.pad(k, pad), jnp.pad(v, pad))


def _attn_step(x, lp, cache, q, cfg):
    """An attention layer on x[B, S, d], a row's positions q[B] ..
    q[B] + S - 1 → (x', (k, v) with those rows written). A query at
    position q + s sees rows 0 .. q + s: what a rejected draft left at
    q + 1 is overwritten before any softmax reaches it."""
    b, s, _ = x.shape
    k_cache, v_cache = cache
    ap = lp["attn"]
    pos = q[:, None] + jnp.arange(s)                          # [B, S]
    with jax.named_scope("attention"):
        h = rms_norm(x, lp["norm"]["scale"], cfg.eps)
        k, v = _keys(h, ap, cfg)
        k_cache = _write_rows(k_cache, k, q)
        v_cache = _write_rows(v_cache, v, q)
        sc = jnp.einsum("bskgd,btkd->bskgt", _queries(h, ap, cfg), k_cache,
                        preferred_element_type=F32) * cfg.head_dim ** -0.5
        keep = jnp.arange(k_cache.shape[1]) <= pos[..., None]  # [B, S, T]
        sc = jnp.where(keep[:, :, None, None], sc, _NEG)
        att = jax.nn.softmax(sc, axis=-1).astype(v_cache.dtype)
        o = jnp.einsum("bskgt,btkd->bskgd", att, v_cache,
                       preferred_element_type=F32).astype(v_cache.dtype)
        x = x + _dot(o.reshape(b, s, cfg.heads * cfg.head_dim),
                     ap["o"]["kernel"])
    return x, (k_cache, v_cache)


# -- LatentMoE ---------------------------------------------------------------
def _moe(x, lp, cfg):
    """An expert layer on rows x[T, d] → (x', held assignments): the
    router on the full width, the held experts in the latent space
    between `latent_down` and `latent_up`, the shared expert on the full
    width."""
    mp = lp["moe"]
    h = rms_norm(x, lp["norm"]["scale"], cfg.eps)
    with jax.named_scope("latent_moe"):
        u = _dot(h, mp["latent_down"]["kernel"])
        with jax.named_scope("routed_experts"):
            chosen, w = route(h, mp, cfg)
            y, held = routed_experts(u, chosen, w, mp["experts"], cfg,
                                     form="relu2")
        y = _dot(y, mp["latent_up"]["kernel"])
    return x + relu2(h, mp["shared"]) + y, held


def _moe_rows(x, lp, cfg):
    """`_moe` on x[..., d] of any leading shape."""
    y, held = _moe(x.reshape(-1, cfg.hidden), lp, cfg)
    return y.reshape(x.shape), held


# -- the main model ----------------------------------------------------------
def step(params, toks, caches, q, cfg: NemotronHConfig):
    """toks[B, S] int32, a row's at its positions q[B] .. q[B] + S - 1 →
    (logits[B, S, V'] f32 for the positions after them, the hidden states
    h[B, S, d] before the final norm, caches, held). A Mamba layer's
    entry of the caches comes back as a `MambaCache`: the state after the
    first position, the second's increment pending and `took` False until
    `commit` says otherwise (`_mamba_step`); an attention layer's as its
    rows written."""
    x = _embed(params, toks, cfg)
    held = jnp.zeros((), jnp.int32)
    new = []
    for i, kind in enumerate(cfg.layers):
        lp = params[f"layer_{i}"]
        if kind == "mamba":
            x, cache = _mamba_step(x, lp, caches[i], cfg)
            new.append(cache)
        elif kind == "attention":
            x, kv = _attn_step(x, lp, caches[i], q, cfg)
            new.append(kv)
        else:
            x, n = _moe_rows(x, lp, cfg)
            held = held + n
            new.append(())
    return _logits(params, x, cfg), x, tuple(new), held


# -- the module --------------------------------------------------------------
def draft(params, toks, h, cache, q, cfg: NemotronHConfig):
    """The module on a row's positions q[B] .. q[B] + S - 1: toks[B, S]
    are the tokens AFTER those positions, h[B, S, d] the main model's
    hidden states AT them → (logits[B, S, V'] f32 for the tokens two
    past each position, its (k, v) cache, held)."""
    mp = params["mtp"]
    with jax.named_scope("draft"):
        x, cache = _attn_step(_mtp_in(params, toks, h, cfg), mp["attention"],
                              cache, q, cfg)
        x, held = _moe_rows(x, mp["moe"], cfg)
        return _logits({"final_norm": mp["norm"], "head": params["head"]},
                       x, cfg), cache, held


# -- prefill -----------------------------------------------------------------
def _prefill_piece(params, ids, total: int, cfg: NemotronHConfig):
    """One sequence ids[P] → (last hidden [d], the main layers' caches,
    the module's (k, v), held). The module's first layer is attention,
    so over the prompt only its K and V rows are ever read again: its
    input at positions 0 .. P-2 (whose next tokens the prompt holds)
    goes through its `k` and `v` alone; row P-1 waits for the first
    sampled token."""
    p = ids.shape[0]
    x = _embed(params, ids, cfg)
    held = jnp.zeros((), jnp.int32)
    caches = []
    for i, kind in enumerate(cfg.layers):
        lp = params[f"layer_{i}"]
        if kind == "mamba":
            x, state, window = _mamba_prefill(x, lp, cfg)
            caches.append(_mamba_cache(state, window, cfg))
        elif kind == "attention":
            x, kv = _attn_prefill(x, lp, total, cfg)
            caches.append(kv)
        else:
            x, n = _moe(x, lp, cfg)
            held = held + n
            caches.append(())
    ap = params["mtp"]["attention"]
    with jax.named_scope("draft"):
        xm = _mtp_in(params, ids[1:], x[:-1], cfg)
        k, v = _keys(rms_norm(xm, ap["norm"]["scale"], cfg.eps), ap["attn"],
                     cfg)
    pad = ((0, total - p + 1), (0, 0), (0, 0))
    return x[-1], tuple(caches), (jnp.pad(k, pad), jnp.pad(v, pad)), held


def prefill(params, ids, total: int, cfg: NemotronHConfig):
    """ids[B, P] → (logits[B, V'] f32 at the last prompt position,
    carry). The batch is walked a sequence at a time (`lax.map`), so
    in_proj's output and the chunked scan's temporaries of one sequence
    — not of the batch — sit beside the weights. carry = (the main
    layers' caches: a Mamba layer's `MambaCache` (the state after the
    last prompt position, no increment pending), an attention layer's
    (k, v) [B, T, KV, D], an expert layer's (); the module's (k, v) with
    rows 0 .. P-2 filled; the last prompt position's hidden state [B, d];
    int32 [assignments, held])."""
    b, p = ids.shape
    last, caches, mtp_cache, held = jax.lax.map(
        lambda row: _prefill_piece(params, row, total, cfg), ids)
    made = jnp.int32(b * p * cfg.experts_per_token * n_moe(cfg))
    stats = jnp.stack([made, held.sum(dtype=jnp.int32)])
    return _logits(params, last, cfg), (caches, mtp_cache, last, stats)
