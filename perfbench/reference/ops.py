"""Plain float32 building blocks of the references, and the FLOP count.

Everything here is `jax.numpy` at float32 with matmul precision "highest":
no kernels, no scan over layers or steps, no cache, no batching tricks. Attention is exact
softmax attention; long query sequences are processed in blocks of rows
(rows are independent, so the result is the same) so that a 9216² score
matrix never has to exist at once.

The FLOP count is of this algorithm, not of the program under test: each
of `dense`, `conv` and `attend` adds its multiply-adds (×2) to the active
`FlopCount` when one is open, and `count(kind, flops)` adds work that is
none of the three. Running a reference forward under
`jax.eval_shape` inside `count_flops()` therefore counts one forward from
shapes alone, without computing anything. Elementwise work (norms,
activations, softmax) is not counted, as is usual for model FLOP/s.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ATTN_ROW_BLOCK = 1152  # query rows per block once Sq exceeds it

_COUNT: list = []
_WEIGHTS: list = []   # the control's weight precision while a trace is open


class FlopCount:
    """Matmul FLOPs by kind, and every attention call's shape: whole
    calls as (b, h, sq, sk, d), and apart from them the calls whose mask
    leaves `pairs` of the sq·sk (query, key) pairs, with that count."""

    def __init__(self):
        self.dense = 0.0
        self.conv = 0.0
        self.attn = 0.0
        self.attn_calls: list[tuple[int, int, int, int, int]] = []
        self.masked_attn_calls: list[tuple[int, int, int, int, int, int]] = []
        self.other: dict[str, float] = {}

    @property
    def total(self) -> float:
        return self.dense + self.conv + self.attn + sum(self.other.values())


@contextlib.contextmanager
def count_flops():
    c = FlopCount()
    _COUNT.append(c)
    try:
        yield c
    finally:
        _COUNT.pop()


def count(kind: str, flops: float) -> None:
    """Work that is none of dense, conv or attend, under a name of its
    own (routed experts: only the tokens sent to the experts held here)."""
    if _COUNT:
        _COUNT[-1].other[kind] = _COUNT[-1].other.get(kind, 0.0) + flops


def dense_flops(rows: int, k: int, n: int) -> float:
    return 2.0 * rows * k * n


def conv_flops(batch: int, out_h: int, out_w: int, kh: int, kw: int,
               cin: int, cout: int) -> float:
    return 2.0 * batch * out_h * out_w * kh * kw * cin * cout


def attention_flops(b: int, h: int, sq: int, sk: int, d: int,
                    pairs: int | None = None) -> float:
    """QK^T and PV of exact attention on [B,H,S,D]: 2 matmuls, over the
    `pairs` (query, key) pairs a mask leaves of each head's sq·sk."""
    return 4.0 * b * h * (sq * sk if pairs is None else pairs) * d


def attention_bytes(b: int, h: int, sq: int, sk: int, d: int,
                    itemsize: int = 2) -> float:
    """The least traffic of exact attention: read Q, K, V, write O once."""
    return float(itemsize) * b * h * d * (2 * sq + 2 * sk)


def f32(x):
    return jnp.asarray(x).astype(jnp.float32)


@contextlib.contextmanager
def weights_as(mode: str | None):
    """The control: while open, `dense` and `conv` compute in `mode`
    ("fp8"): the kernel rounded to float8_e4m3 with one float32 scale per
    output channel and the input with one scale for the tensor, products
    accumulated in float32 — a step below bfloat16 that a later PR would
    be tempted by. Everything else stays float32."""
    _WEIGHTS.append(mode)
    try:
        yield
    finally:
        _WEIGHTS.pop()


def low_precision(w, mode: str, per_channel: bool = True):
    axes = tuple(range(w.ndim - 1)) if per_channel else None
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
    if mode == "fp8":
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"no such control precision: {mode!r}")


def traced_with(fn, weights: str | None):
    """`fn`, traced with the control's weight precision open."""
    def traced(*args):
        with weights_as(weights):
            return fn(*args)
    return traced


def kernel(p):
    w = f32(p["kernel"])
    if _WEIGHTS and _WEIGHTS[-1]:
        w = low_precision(w, _WEIGHTS[-1])
    return w


def operand(x):
    if _WEIGHTS and _WEIGHTS[-1]:
        x = low_precision(x, _WEIGHTS[-1], per_channel=False)
    return x


def dense(x, p, bias: bool = True):
    """x[..., K] @ kernel[K, N] (+ bias)."""
    w = kernel(p)
    if _COUNT:
        _COUNT[-1].dense += dense_flops(
            math.prod(x.shape[:-1]), w.shape[0], w.shape[1])
    y = jnp.matmul(operand(x), w, precision=HIGHEST)
    if bias and "bias" in p:
        y = y + f32(p["bias"])
    return y


def conv(x, p, stride: int = 1):
    """NHWC convolution with an HWIO kernel, 'same'-style padding k//2:
    the sum over the kernel's taps of a matmul on the shifted input, the
    taps in a loop. (One `lax.conv` at "highest" took the TPU's compiler up
    to minutes a shape, and the taps unrolled made the programs nine times
    as long; a tap is a plain float32 matmul.)"""
    w = kernel(p)
    kh, kw, cin, cout = w.shape
    b, h, wd, _ = x.shape
    oh = (h + 2 * (kh // 2) - kh) // stride + 1
    ow = (wd + 2 * (kw // 2) - kw) // stride + 1
    if _COUNT:
        _COUNT[-1].conv += conv_flops(b, oh, ow, kh, kw, cin, cout)
    xp = jnp.pad(operand(x), ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2),
                              (0, 0)))
    taps = w.reshape(kh * kw, cin, cout)
    span = (b, stride * (oh - 1) + 1, stride * (ow - 1) + 1, cin)

    def tap(n, y):
        window = jax.lax.dynamic_slice(xp, (0, n // kw, n % kw, 0), span)
        return y + jnp.matmul(window[:, ::stride, ::stride], taps[n],
                              precision=HIGHEST)

    y = jnp.broadcast_to(f32(p["bias"]), (b, oh, ow, cout))
    return jax.lax.fori_loop(0, kh * kw, tap, y)


def group_norm(x, p, groups: int = 32, eps: float = 1e-5):
    """GroupNorm over NHWC (or [B,S,C]) with gcd(C, groups) groups."""
    c = x.shape[-1]
    g = math.gcd(c, groups)
    shp = x.shape
    xg = x.reshape(shp[0], -1, g, c // g)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + eps)
    return xg.reshape(shp) * f32(p["scale"]) + f32(p["bias"])


def layer_norm(x, p, eps: float = 1e-5):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * f32(p["scale"]) + f32(p["bias"])


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu(x):
    return jax.nn.gelu(x, approximate=False)


def quick_gelu(x):
    return x * jax.nn.sigmoid(1.702 * x)


def sinusoidal(t, dim: int, max_period: float = 10000.0):
    """[cos, sin] timestep embedding (flip_sin_to_cos), [B] -> [B, dim]."""
    half = dim // 2
    freqs = jnp.exp(-np.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    args = f32(t)[:, None] * freqs[None, :]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    if dim % 2:
        emb = jnp.pad(emb, ((0, 0), (0, 1)))
    return emb


def attend(q, k, v, mask=None, pairs=None):
    """Exact softmax attention on [B,H,S,D]; `mask` is additive and
    broadcastable to [B,H,Sq,Sk]. `pairs`: how many of a head's Sq·Sk
    (query, key) pairs the mask leaves (causal: S(S+1)/2), where the
    caller wants only those counted; the result is the same."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if _COUNT:
        _COUNT[-1].attn += attention_flops(b, h, sq, sk, d, pairs)
        if pairs is None:
            _COUNT[-1].attn_calls.append((b, h, sq, sk, d))
        else:
            _COUNT[-1].masked_attn_calls.append((b, h, sq, sk, d, pairs))
    scale = 1.0 / np.sqrt(d)

    def rows(qb):
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k, precision=HIGHEST) * scale
        if mask is not None:
            s = s + mask
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                          precision=HIGHEST)

    if sq <= ATTN_ROW_BLOCK or sq % ATTN_ROW_BLOCK or mask is not None:
        return rows(q)
    blocks = q.reshape(b, h, sq // ATTN_ROW_BLOCK, ATTN_ROW_BLOCK, d)
    out = jax.lax.map(rows, jnp.moveaxis(blocks, 2, 0))
    return jnp.moveaxis(out, 0, 2).reshape(b, h, sq, d)


def heads(x, n: int):
    """[B,S,n*D] -> [B,n,S,D]."""
    b, s, c = x.shape
    return x.reshape(b, s, n, c // n).transpose(0, 2, 1, 3)


def unheads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def upsample2(x):
    """Nearest-neighbour 2x on NHWC."""
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def avgpool2(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def to_uint8(pixels):
    """[-1,1] decoder output -> uint8 RGB, round half to even."""
    x = jnp.clip(pixels * 0.5 + 0.5, 0.0, 1.0)
    return jnp.round(x * 255.0).astype(jnp.uint8)


def task_keys(seed: int):
    """The protocol's per-task key: low 32 bits key, high bits folded in."""
    lo, hi = seed & 0xFFFFFFFF, seed >> 32
    return jax.random.fold_in(jax.random.PRNGKey(np.uint32(lo)),
                              np.uint32(hi))


def byte_tokens(text: str, max_length: int, bos: int, eos: int) -> np.ndarray:
    """UTF-8 bytes between BOS and EOS, padded with EOS (CLIP pads so)."""
    raw = list(text.encode("utf-8"))[: max_length - 2]
    ids = [bos] + raw + [eos]
    return np.asarray(ids + [eos] * (max_length - len(ids)), np.int32)


def text_tower(p, ids, cfg):
    """CLIP-style causal pre-LN transformer; [B,L] ids -> [B,L,W] states.

    cfg: {"width","layers","heads","act"}. Attention kernels are stored
    per head: query/key/value [W,H,D], out [H,D,W]."""
    ids = jnp.asarray(ids)
    b, length = ids.shape
    w, nh = cfg["width"], cfg["heads"]
    x = f32(p["token_embed"]["embedding"])[ids] \
        + f32(p["pos_embed"])[None, :length]
    causal = jnp.where(jnp.tril(jnp.ones((length, length), bool)), 0.0,
                       -jnp.inf)[None, None]
    act = quick_gelu if cfg["act"] == "quick_gelu" else gelu
    for i in range(cfg["layers"]):
        lp = p[f"layer_{i}"]
        h = layer_norm(x, lp["LayerNorm_0"])
        a = lp["attn"]

        def proj(name):
            kern = {"kernel": f32(a[name]["kernel"]).reshape(w, w),
                    "bias": f32(a[name]["bias"]).reshape(w)}
            return heads(dense(h, kern), nh)

        o = unheads(attend(proj("query"), proj("key"), proj("value"),
                           mask=causal))
        o = dense(o, {"kernel": f32(a["out"]["kernel"]).reshape(w, w),
                      "bias": a["out"]["bias"]})
        x = x + o
        h = layer_norm(x, lp["LayerNorm_1"])
        x = x + dense(act(dense(h, lp["Dense_0"])), lp["Dense_1"])
    return layer_norm(x, p["final_norm"])


def resnet(x, p, temb=None, *, scale_shift=False, resample="none",
           eps=1e-5):
    """GN-SiLU-conv twice, timestep injection, learned 1x1 skip."""
    h = silu(group_norm(x, p["GroupNorm32_0"]["GroupNorm_0"], eps=eps))
    if resample == "down":
        h, x = avgpool2(h), avgpool2(x)
    elif resample == "up":
        h, x = upsample2(h), upsample2(x)
    h = conv(h, p["Conv_0"])
    t = None
    if temb is not None:
        t = dense(silu(temb), p["Dense_0"])[:, None, None, :]
        if not scale_shift:
            h = h + t
    h = group_norm(h, p["GroupNorm32_1"]["GroupNorm_0"], eps=eps)
    if t is not None and scale_shift:
        scale, shift = jnp.split(t, 2, axis=-1)
        h = h * (1 + scale) + shift
    h = conv(silu(h), p["Conv_1"])
    if "skip_proj" in p:
        x = conv(x, p["skip_proj"])
    return x + h


def mha(x, p, n_heads: int, context=None, mask=None):
    """to_q/to_k/to_v/to_out attention (bias where the tree has one)."""
    ctx = x if context is None else context
    q = heads(dense(x, p["to_q"]), n_heads)
    k = heads(dense(ctx, p["to_k"]), n_heads)
    v = heads(dense(ctx, p["to_v"]), n_heads)
    return dense(unheads(attend(q, k, v, mask=mask)), p["to_out"])
