"""Pallas causal / sliding-window flash attention for grouped KV heads.

Why: Trinity's prefill attends 8192 prompt positions with 48 query heads
over 8 KV heads, one full-attention layer to three of window 4096. The
XLA walk (ops/blockwise.py) writes a float32 score block `[8, 6, 512,
keys]` to HBM and reads it back for every 512-row query block: 6.8 GB a
sequence in the full layer, 5.4 GB in each window layer, for 13 and 10
TFLOP of arithmetic (PERF.md section 6, PR 31). The flash form never
makes that traffic: K and V of one KV head stay in VMEM, the scores of
one (query block, key block) pair live in registers and VMEM only, under
a running max / normaliser / accumulator.

The mathematics is `blockwise_attention`'s, and so is the precision
(ops/flash.py states the same policy for the unmasked kernel): query i
sees keys j <= i, and i - window < j where a window is given; q, k and v
go to the MXU in the type they arrive in, both products accumulate in
float32; scores, max, normaliser, accumulator and the exponent are
float32; the scale multiplies the float32 scores; the probabilities are
cast to v's type for the second product. Only the order of the softmax's
sums differs (online, over key blocks), so each side of `causal_attention`'s
rule is a determinism class of its own.

Layout: q `[B, S, KV, G, D]` and k, v `[B, S, KV, D]` are handed over as
they are (trailing axes merged, a free reshape: no transpose in HBM).
grid = (B, KV, S / block_q). A program owns `block_q` query positions of
the G query heads of one KV head — a `[block_q, G*D]` block, whose
lane-aligned column slices, one a head, it stacks as the ROWS of Q tiles
`[heads * block_q, D]`, so the heads of a group share one fetch of K and
V and one pass of each key block through the MXU — and sees the whole K
and V of its KV head as one VMEM block each (fetched once a head: the
block index does not move with the query block; 2 MiB each at S = 8192,
D = 128 in bf16). The group's heads are split over `_chains` such tiles,
each with a running max / normaliser / accumulator of its own: two
chains that depend on nothing of each other, side by side in one loop.

Skipping, from the static shape and the program's index alone: a query
block [q0, q1) walks key blocks `lo .. hi` where lo holds key
max(0, q0 - window + 1) and hi holds key q1 - 1, and reads no other.
Of these only the blocks the mask CUTS carry a mask — the far edge of the
window at the low end, the diagonal at the high end; padded keys lie past
every real query's diagonal, so the causal mask covers them — and the
blocks between run unmasked, `_UNROLL` to a trip. A row whose every key
in an edge block is masked reads exp(0) = 1 there (the fill is finite);
its running max is still the fill, so the first real score rescales that
to exactly 0 — and every row has a real score later, its own key.

Blocks walked by one KV head at S = 8192 and the tiles `_tiles` gives
(block_q 128, block_k 512), of the 64 x 16 = 1,024 blocks of the unmasked
grid (`walk_blocks`; tests/test_causal_flash.py pins these):

    full            544 of 1024   (53.1 %; the mask leaves 50.0 % of pairs)
    window 4096     432 of 1024   (42.2 %; the mask leaves 37.5 %)

`causal_flash_attention` is a drop-in for `blockwise_attention`;
`interpret=True` runs it on the CPU (tests/test_causal_flash.py).
`ops/flash.py` is the unmasked kernel of the image families and shares no
kernel body with this one: its program is pinned by their TPU goldens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from arbius_tpu.ops.blockwise import blockwise_attention
from arbius_tpu.ops.flash import (
    _LANES,
    _NT,
    _VMEM_DEFAULT,
    _VMEM_HEADROOM,
    NEG_INF,
    _pad_to,
    _round_up,
    _tile,
)

# What the constants below rest on: one call (1, 8192, 8, 6, 128) bf16 on a
# v5e, the layout copies XLA puts round the kernel included, full | window
# 4096, ms (PERF.md section 6, PR 31; bench_runs/pr31_causal_flash_tiles.jsonl):
# one chain of 1,536 rows, two key blocks a trip 8.29 | 7.39; two chains of
# 384 rows, three a trip 7.36 | 6.63; the walk 30.6 | 23.9.
#
# Unmasked key blocks a trip of the loop takes (ops/flash.py `_UNROLL`:
# one block's products overlap its neighbour's softmax): 7.56 | 6.75 at 2.
_UNROLL = 3
# Key rows a block: the mask's staircase wastes half a key block a query
# row at each cut edge (3.1 % of S = 8192 at 512), a trip's fixed cost is
# paid once a block. 256 rows read 8.85 | 7.69 where 512 read 8.29 | 7.39;
# 1,024 rows tie with 512 (7.61 | 6.91 at their best) and walk more.
_K_TILE = 512
# Rows of the stacked Q tiles of one program, all chains together
# (G * block_q): 128 positions of 6 heads. 1,536 and 3,072 rows read the
# same to 3 % and cut the mask's staircase coarser.
_Q_ROWS = 768
# Softmax chains a program runs side by side, each over its own heads of
# the group: they share nothing but K and V, so one's products overlap
# the other's exponent in the cut blocks too, which no neighbour block
# covers. One chain 8.02 | 7.12 where two read 7.56 | 6.75 (two blocks a
# trip); three 7.67 | 6.87; six (a head a chain) 10.6 | 8.9.
_CHAINS = 2


def _chains(group: int) -> int:
    """Independent softmax chains a program runs side by side: the
    group's heads split into this many row tiles (one where the group
    does not divide)."""
    return _CHAINS if group % _CHAINS == 0 else 1


def _tiles(s: int, group: int) -> tuple[int, int]:
    """(block_q, block_k) from the call's static shape: query positions a
    program owns — `_Q_ROWS` stacked rows shared among the group's heads,
    a multiple of 128 — and key rows a block."""
    block_k = _tile(s, _K_TILE, _K_TILE)
    block_q = _tile(s, max(_LANES, _Q_ROWS // group))
    return block_q, block_k


def _walk(q0, block_q: int, block_k: int, window: int | None, n_k: int,
          least=min, most=max):
    """(lo, plain_lo, plain_hi, hi): query block [q0, q0 + block_q) walks
    key blocks [lo, hi); those of [plain_lo, plain_hi) the mask does not
    cut. Integer arithmetic on non-negative values: the same lines serve
    the host's count and, with `least` / `most` for traced scalars, the
    kernel."""
    q1 = q0 + block_q
    hi = least((q1 - 1) // block_k + 1, n_k)
    if window is None:
        lo = plain_lo = 0
    else:
        # keys under q0 - window + 1 no row sees; from key q1 - window on
        # every row's window holds them
        lo = most(q0 - window + 1, 0) // block_k
        plain_lo = least(most(
            (most(q1 - window, 0) + block_k - 1) // block_k, lo), hi)
    # a block wholly at or under key q0 the causal mask does not cut
    plain_hi = least(most((q0 + 1) // block_k, plain_lo), hi)
    return lo, plain_lo, plain_hi, hi


@functools.lru_cache(maxsize=None)
def walk_blocks(s: int, window: int | None, group: int) -> tuple[int, int]:
    """(key blocks one KV head's programs walk, blocks of the unmasked
    S x S grid at the same tiles) for a call of `s` positions."""
    block_q, block_k = _tiles(s, group)
    n_q = _round_up(s, block_q) // block_q
    n_k = _round_up(s, block_k) // block_k
    walked = 0
    for i in range(n_q):
        lo, _, _, hi = _walk(i * block_q, block_q, block_k, window, n_k)
        walked += hi - lo
    return walked, n_q * n_k


def _vmem_bytes(block_q: int, block_k: int, s_p: int, group: int, d: int,
                itemsize: int) -> int:
    """What one program holds in VMEM: K and V of the head whole and the
    Q and O blocks, two buffers each; the stacked Q tile; per key block
    in flight the scores, the probabilities and the probabilities in v's
    type; the accumulator and the product added to it."""
    rows = group * block_q
    resident = 2 * 2 * s_p * d * itemsize
    blocks = 2 * 2 * rows * d * itemsize + rows * d * itemsize
    work = _UNROLL * 3 * rows * block_k * 4 + 2 * rows * d * 4
    return resident + blocks + work


def _kernel(q_ref, k_ref, v_ref, o_ref, *, window, scale: float,
            block_q: int, block_k: int, group: int, d: int, n_k: int):
    q0 = pl.program_id(2) * block_q
    chains = _chains(group)
    heads = group // chains            # query heads a chain
    rows = heads * block_q
    # a chain's heads, stacked as rows: row h * block_q + i is the chain's
    # head h at position q0 + i
    qs = [jnp.concatenate([q_ref[0, :, g * d:(g + 1) * d]
                           for g in range(c * heads, (c + 1) * heads)],
                          axis=0) for c in range(chains)]
    lo, plain_lo, plain_hi, hi = _walk(q0, block_q, block_k, window, n_k,
                                       jnp.minimum, jnp.maximum)

    def step(j, carry, masked: bool):
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(start, block_k), :]     # [block_k, D]
        v = v_ref[0, pl.ds(start, block_k), :]
        if masked:
            qpos = q0 + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            ok = kpos <= qpos
            if window is not None:
                ok &= kpos > qpos - window
        out = []
        for q, (m, l, acc) in zip(qs, carry):
            s = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(ok[None], s.reshape(heads, block_q, block_k),
                              NEG_INF).reshape(rows, block_k)
            mb = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - mb)
            alpha = jnp.exp(m - mb)
            l = l * alpha + p.sum(axis=-1, keepdims=True)
            acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                        preferred_element_type=jnp.float32)
            out.append((mb, l, acc))
        return tuple(out)

    cut = functools.partial(step, masked=True)
    plain = functools.partial(step, masked=False)

    def trip(t, carry):
        for u in range(_UNROLL):
            carry = plain(plain_lo + t * _UNROLL + u, carry)
        return carry

    carry = tuple((jnp.full((rows, 1), NEG_INF, jnp.float32),
                   jnp.zeros((rows, 1), jnp.float32),
                   jnp.zeros((rows, d), jnp.float32))
                  for _ in range(chains))
    if window is not None:
        carry = jax.lax.fori_loop(lo, plain_lo, cut, carry)
    trips = (plain_hi - plain_lo) // _UNROLL
    carry = jax.lax.fori_loop(0, trips, trip, carry)
    carry = jax.lax.fori_loop(plain_lo + trips * _UNROLL, plain_hi, plain,
                              carry)
    carry = jax.lax.fori_loop(plain_hi, hi, cut, carry)
    for c, (_, l, acc) in enumerate(carry):
        out = (acc / l).astype(o_ref.dtype)
        for h in range(heads):
            g = c * heads + h
            o_ref[0, :, g * d:(g + 1) * d] = \
                out[h * block_q:(h + 1) * block_q]


def _attention(q, k, v, window, block_q: int, block_k: int,
               interpret: bool):
    b, s, kv, group, d = q.shape
    scale = d ** -0.5
    d_p = _round_up(d, _LANES)       # head slices of a block: whole lanes
    qf = _pad_to(_pad_to(q, 4, _LANES), 1, block_q).reshape(
        b, -1, kv * group * d_p)
    kf = _pad_to(_pad_to(k, 3, _LANES), 1, block_k).reshape(b, -1, kv * d_p)
    vf = _pad_to(_pad_to(v, 3, _LANES), 1, block_k).reshape(b, -1, kv * d_p)
    sq_p, sk_p = qf.shape[1], kf.shape[1]

    q_spec = pl.BlockSpec((1, block_q, group * d_p),
                          lambda bi, h, i: (bi, i, h))
    kv_spec = pl.BlockSpec((1, sk_p, d_p), lambda bi, h, i: (bi, 0, h))
    out = pl.pallas_call(
        functools.partial(_kernel, window=window, scale=scale,
                          block_q=block_q, block_k=block_k, group=group,
                          d=d_p, n_k=sk_p // block_k),
        grid=(b, kv, sq_p // block_q),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            _VMEM_DEFAULT,
            _vmem_bytes(block_q, block_k, sk_p, group, d_p,
                        kf.dtype.itemsize) + _VMEM_HEADROOM)),
        interpret=interpret,
        name="causal_flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, sq_p, kv, group, d_p)[:, :s, ..., :d]


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def causal_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           window: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """q[B, S, KV, G, D], k/v[B, S, KV, D] → [B, S, KV, G, D], exactly
    `blockwise_attention`'s result but for the order of the softmax's
    sums. Tiles come from the static shape (`_tiles`)."""
    block_q, block_k = _tiles(q.shape[1], q.shape[3])
    return _attention(q, k, v, window, block_q, block_k, interpret)


# Query positions from which `causal_attention` takes the kernel on a TPU.
# One call (1, S, 8, 6, 128) bf16 on a v5e, walk | kernel, ms (PERF.md
# section 6, PR 31): 0.23 | 0.22 at S = 512 and 0.27 | 0.36 at 1,024, where
# the walk is one or two query blocks and its scores a few MB; 1.84 | 0.86
# at 2,048, 7.7 | 2.4 at 4,096, 30.6 | 7.4 at 8,192. No cell calls it
# between 2,048 and 8,191 positions: the constant rests on the first three.
_KERNEL_MIN_ROWS = 2048


def kernel_serves(s: int) -> bool:
    """Whether a prefill of `s` positions runs the kernel here."""
    return jax.default_backend() == "tpu" and s >= _KERNEL_MIN_ROWS


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     window: int | None = None) -> jax.Array:
    """Causal (and, with `window`, sliding-window) attention for grouped
    KV heads, the path read off the call: on a TPU from
    `_KERNEL_MIN_ROWS` query positions the Pallas kernel, else
    `blockwise_attention` (XLA's walk, the exact reference and the only
    compiled form off the TPU)."""
    if kernel_serves(q.shape[1]):
        return causal_flash_attention(q, k, v, window=window)
    return blockwise_attention(q, k, v, window=window)
