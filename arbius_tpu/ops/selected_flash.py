"""Pallas flash attention under a selection's mask: per-head keys and
values, one rotary key row for all heads, a data mask for all heads.

Why: DeepSeek-V3.2's prefill attends 16,384 prompt positions with 128
heads, each query over the keys its indexer selected (at most 2,048).
The XLA walk (`selected_walk`, below) makes the dense-masked products of
every causal (query block, key block) pair and, for each of the 528
pairs a (layer, sequence) has at 512-row blocks, writes a float32 score
block `[128, 512, 512]` — 134 MB — to HBM and reads it back for the
mask, the max, the exponent, the sums and the cast: 11.3 TFLOP made at
about 20 TFLOP/s (PERF.md section 6, PR 34 and PR 35). The flash form
makes the same products and never that traffic: the scores of one (query
block, key block, head) live in VMEM only, under a running max /
normaliser / accumulator.

The mathematics is the walk's, and so is the precision (ops/flash.py
and ops/causal_flash.py state the same policy): a query weighs the keys
at or before its own position that `keep` holds, and no other; q, k and
v go to the MXU in the type they arrive in; scores = q_nope·k_nopeᵀ +
q_pe·k_peᵀ, both products accumulating in float32; the scale multiplies
the float32 scores; max, normaliser, accumulator and the exponent are
float32; the probabilities are cast to v's type for the second product.
Only the order of the softmax's sums differs (the key blocks need not be
the walk's), so each side of `selected_attention`'s rule is a
determinism class of its own.

The trap the causal kernel (ops/causal_flash.py) does not have: a
selection need not keep a row's own key, so a row can meet key blocks in
which it keeps nothing, the first it visits included. A masked pair
contributes exactly 0, as in the walk — the exponent is taken under the
mask (`where(ok, exp(s - m), 0)`), not left as exp(0) = 1 for a later
rescale to clear. Every row keeps a key somewhere at or before its
diagonal, so its normaliser ends above 0.

Layout, no transpose in HBM: the query block's `q_nope [R, H·dn]` and
`q_pe [R, H·dr]`, the sequence's `kv [P, H·(dn + dv)]` (a head's columns
are K-nope | V: one block fetch gives both), `k_pe [P, dr]`, and `keep`
as int8 `[R, P]` with the causal mask already in it. grid = (H / group,
R / block_q, P / block_k), the key blocks innermost: a program owns
`block_q` query rows of `group` heads, meets one key block a step and
keeps the heads' running state in VMEM scratch across the steps; `keep`
and `k_pe` are fetched once for the group. The query rows' first
position arrives as a prefetched scalar: key blocks past the block of
the last row's own key are never fetched (the index maps clamp to it,
and an unchanged block index is no DMA) and never multiplied
(`pl.when`).

Blocks one group of heads walks at P = 16,384, 512 query rows a call, at
the tiles `_tiles` gives (block_q 512, block_k 512), of the 32 x 32 =
1,024 blocks of the unmasked grid (`walk_blocks`, which counts all 16
groups; tests/test_selected_flash.py pins these):

    528 of 1024   (51.6 %; the causal mask leaves 50.0 % of pairs)

`selected_flash_attention` is a drop-in for `selected_walk`;
`interpret=True` runs it on the CPU (tests/test_selected_flash.py).

The band (dots3_note's sliding layers, the second half of this file):
`window_flash_attention` and its walk `window_walk` attend each row to
the last `window` keys, the mask made from positions in the kernel and
no `[R, P]` array; a query block walks the key blocks the band reaches
and no other (`walk_blocks(..., window=)`: 31 of 256 a group of heads at
P = 8,192, window 513, 512-row tiles). tests/test_dots3.py runs it with
`interpret=True`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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

F32 = jnp.float32

# What the constants below rest on: one (layer, sequence) at P = 16,384,
# 128 heads of 128 + 64 | 128, bf16, on a v5e — 32 calls of 512 query rows
# in a loop as the model makes them, each output consumed by a product —
# ms (PERF.md section 6, PR 35; bench_runs/pr35_selected_flash_tiles2.jsonl
# and _tiles3.jsonl); the walk 584.6.
#
# Query rows a program and key rows a block, with `_GROUP` heads: (512,
# 512) 86.0; (512, 1024) 90.8; 16 heads at (256, 512) 89.3. 16 heads at
# (512, 512) want 50 MB of VMEM, which XLA refused beside its own buffers
# in one of the two programs tried.
_Q_TILE = 512
_K_TILE = 512
# Heads a program: `keep` and `k_pe` are fetched, and the mask widened,
# once for them all. 4 heads 91.2, 8 heads 86.3.
_GROUP = 8
# What moved the time most is the shape of the running max and normaliser:
# as columns `[block_q, 1]` 123.1, as `[block_q, 128]` with the row's value
# in every lane 86.0 (a column is a vreg a sublane group with one lane in
# use, widened again for every score tile). Measured and left: the
# normaliser summed lane tile by lane tile and across lanes once at the
# end, 84.9; no second `where` (the max held above -1e29, so that a masked
# score's exponent is exactly 0 by itself) 81.8, both 80.3 — the walk's
# own form of the mask is kept; one product of q_nope | q_pe against
# K-nope | k_pe joined in VMEM 127.3 where the two products read 123.1.


def _tiles(rows: int, p: int) -> tuple[int, int]:
    """(block_q, block_k) from the call's static shape."""
    return _tile(rows, _Q_TILE), _tile(p, _K_TILE, _K_TILE)


def _group(heads: int) -> int:
    """Heads a program owns: the largest divisor of `heads` up to
    `_GROUP`."""
    return max(g for g in range(1, min(heads, _GROUP) + 1)
               if heads % g == 0)


def _last(start, i, block_q: int, block_k: int, n_k: int, least=min):
    """The last key block that query block `i` of a call whose rows
    start at position `start` walks: the one that holds its last row's
    own key. The same line serves the host's count and, with `least` for
    traced scalars, the kernel and its index maps."""
    return least((start + (i + 1) * block_q - 1) // block_k, n_k - 1)


def _first(start, i, block_q: int, block_k: int, window: int | None,
           most=max):
    """The first key block query block `i` walks: 0, or under a window
    of `window` keys (a row's own included) the one that holds its first
    row's first key. Host and kernel alike, as `_last`."""
    if window is None:
        return 0
    return most(start + i * block_q - window + 1, 0) // block_k


@functools.lru_cache(maxsize=None)
def walk_blocks(p: int, rows: int, heads: int,
                window: int | None = None) -> tuple[int, int]:
    """(key blocks the programs walk over one sequence of `p` positions
    served `rows` query rows a call — a block counts once for each group
    of heads that meets it —, blocks of the unmasked grid at the same
    tiles); with `window`, the band's walk (`window_flash_attention`,
    one call of all `p` rows)."""
    block_q, block_k = _tiles(rows, p)
    n_q = _round_up(rows, block_q) // block_q
    n_k = _round_up(p, block_k) // block_k
    groups = heads // _group(heads)
    walked = sum(_last(start, i, block_q, block_k, n_k) + 1
                 - _first(start, i, block_q, block_k, window)
                 for start in range(0, p, rows) for i in range(n_q))
    return groups * walked, groups * -(-p // rows) * n_q * n_k


def _vmem_bytes(block_q: int, block_k: int, group: int, dn: int, dr: int,
                dv: int, itemsize: int) -> int:
    """What one program holds in VMEM, lanes padded to 128: the q_nope,
    q_pe, kv, k_pe, keep and output blocks, two buffers each; the
    heads' max, normaliser (a row's value in every lane) and accumulator;
    per head in flight the scores, the probabilities and the
    probabilities in v's type, two heads at a time, and the mask
    widened."""
    lanes = functools.partial(_round_up, mult=_LANES)
    blocks = 2 * (block_q * group * (lanes(dn) + lanes(dr) + lanes(dv))
                  + block_k * (group * lanes(dn + dv) + lanes(dr))
                  ) * itemsize + 2 * block_q * block_k
    state = group * block_q * (2 * _LANES + lanes(dv)) * 4
    work = (2 * 3 + 1) * block_q * block_k * 4
    return blocks + state + work


def _spread(x, width: int):
    """x[rows, 128], one value a row in every lane → [rows, width]."""
    if width <= _LANES:
        return x[:, :width]
    return pltpu.repeat(x, width // _LANES, axis=1)


def _kernel(start_ref, qn_ref, qp_ref, kv_ref, kpe_ref, keep_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, block_q: int,
            block_k: int, group: int, dn: int, dr: int, dv: int, n_k: int):
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last(start_ref[0], i, block_q, block_k, n_k, jnp.minimum)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    @pl.when(j <= last)
    def _():
        ok = keep_ref[...].astype(jnp.int32) != 0        # [block_q, block_k]
        kpe = kpe_ref[...]
        for g in range(group):
            qn = qn_ref[:, g * dn:(g + 1) * dn]
            qp = qp_ref[:, g * dr:(g + 1) * dr]
            c = g * (dn + dv)
            k, v = kv_ref[:, c:c + dn], kv_ref[:, c + dn:c + dn + dv]
            s = (jax.lax.dot_general(qn, k, _NT, preferred_element_type=F32)
                 + jax.lax.dot_general(qp, kpe, _NT,
                                       preferred_element_type=F32)) * scale
            s = jnp.where(ok, s, NEG_INF)
            m = m_ref[g]                  # a row's max, in every lane
            mb = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - _spread(mb, block_k)), 0.0)
            alpha = jnp.exp(m - mb)
            l_ref[g] = l_ref[g] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * _spread(alpha, dv) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=F32)
            m_ref[g] = mb

    @pl.when(j == n_k - 1)
    def _():
        for g in range(group):
            o_ref[:, g * dv:(g + 1) * dv] = \
                (acc_ref[g] / _spread(l_ref[g], dv)).astype(o_ref.dtype)


def _attention(qn, qp, kv, k_pe, keep, start, heads: int, dn: int,
               scale: float, block_q: int, block_k: int, group: int,
               interpret: bool):
    rows, p, dr = qn.shape[0], kv.shape[0], k_pe.shape[1]
    dv = kv.shape[1] // heads - dn
    qn, qp = _pad_to(qn, 0, block_q), _pad_to(qp, 0, block_q)
    kv, k_pe = _pad_to(kv, 0, block_k), _pad_to(k_pe, 0, block_k)
    keep = _pad_to(_pad_to(keep.astype(jnp.int8), 0, block_q), 1, block_k)
    n_k = kv.shape[0] // block_k

    def at(i, j, start_ref):
        return jnp.minimum(j, _last(start_ref[0], i, block_q, block_k, n_k,
                                    jnp.minimum))

    def q_spec(width):
        return pl.BlockSpec((block_q, group * width),
                            lambda h, i, j, s: (i, h))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, group=group, dn=dn, dr=dr,
                          dv=dv, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads // group, qn.shape[0] // block_q, n_k),
            in_specs=[
                q_spec(dn), q_spec(dr),
                pl.BlockSpec((block_k, group * (dn + dv)),
                             lambda h, i, j, s: (at(i, j, s), h)),
                pl.BlockSpec((block_k, dr),
                             lambda h, i, j, s: (at(i, j, s), 0)),
                pl.BlockSpec((block_q, block_k),
                             lambda h, i, j, s: (i, at(i, j, s))),
            ],
            out_specs=q_spec(dv),
            scratch_shapes=[pltpu.VMEM((group, block_q, _LANES), F32),
                            pltpu.VMEM((group, block_q, _LANES), F32),
                            pltpu.VMEM((group, block_q, dv), F32)]),
        out_shape=jax.ShapeDtypeStruct((qn.shape[0], heads * dv), qn.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(
                _VMEM_DEFAULT,
                _vmem_bytes(block_q, block_k, group, dn, dr, dv,
                            kv.dtype.itemsize) + _VMEM_HEADROOM)),
        interpret=interpret,
        name="selected_flash_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1), qn, qp, kv, k_pe, keep)
    return out[:rows]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def selected_flash_attention(q_nope, q_pe, kv, k_pe, keep, start, *,
                             scale: float, interpret: bool = False):
    """q_nope[R, H, dn], q_pe[R, H, dr]: the query rows at positions
    `start` .. `start` + R - 1 of a sequence whose keys and values are
    kv[P, H·(dn + dv)] (K-nope | V a head, as the expansion's product
    leaves them) and k_pe[P, dr]; keep[R, P] bool, the pairs the softmax
    weighs (no key past a row's own position) → [R, H·dv],
    `selected_walk`'s result but for the order of the softmax's sums.
    Tiles come from the static shape."""
    rows, heads, dn = q_nope.shape
    block_q, block_k = _tiles(rows, kv.shape[0])
    return _attention(q_nope.reshape(rows, -1), q_pe.reshape(rows, -1), kv,
                      k_pe, keep, start, heads, dn, scale, block_q, block_k,
                      _group(heads), interpret)


def selected_walk(q_nope, q_pe, kv, k_pe, keep, i, rows, qpos, *,
                  scale: float):
    """XLA's walk, the exact reference and the only compiled form off
    the TPU: query block `i` of a sequence cut into blocks of R rows —
    q_nope[R, H, dn], q_pe[R, H, dr] — over the key blocks (R rows each)
    of kv[P, H, dn + dv] and k_pe[P, dr] up to its diagonal, under the
    causal mask and keep[R, P], with a running max / normaliser /
    accumulator → [R, H·dv]. `rows` is arange(R) and `qpos` the query
    rows' positions as a column, i·R + rows[:, None]: the caller holds
    both, and this program, which `goldens/graph/deepseek_v32.*` pin,
    makes no second copy of them."""
    blk, nh, dn = q_nope.shape
    dv = kv.shape[-1] - dn

    def attend(j, carry):
        m, l, acc = carry
        kvb = jax.lax.dynamic_slice_in_dim(kv, j * blk, blk)
        kpb = jax.lax.dynamic_slice_in_dim(k_pe, j * blk, blk)
        s = jnp.einsum("qhd,khd->hqk", q_nope, kvb[..., :dn],
                       preferred_element_type=F32) \
            + jnp.einsum("qhd,kd->hqk", q_pe, kpb,
                         preferred_element_type=F32)
        ok = (j * blk + rows[None, :] <= qpos) \
            & jax.lax.dynamic_slice_in_dim(keep, j * blk, blk, 1)
        s = jnp.where(ok[None], s * scale, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        pr = jnp.where(ok[None], jnp.exp(s - m_new[..., None]), 0.0)
        fix = jnp.exp(m - m_new)
        l = l * fix + pr.sum(axis=-1)
        acc = acc * fix[..., None] + jnp.einsum(
            "hqk,khd->hqd", pr.astype(kvb.dtype), kvb[..., dn:],
            preferred_element_type=F32)
        return m_new, l, acc

    m0 = jnp.full((nh, blk), NEG_INF, F32)
    _, l, acc = jax.lax.fori_loop(
        0, i + 1, attend,
        (m0, jnp.zeros((nh, blk), F32), jnp.zeros((nh, blk, dv), F32)))
    o = (acc / l[..., None]).astype(q_nope.dtype)
    return jnp.moveaxis(o, 0, 1).reshape(blk, nh * dv)


# Prompt positions from which `selected_attention` takes the kernel on a
# TPU. One (layer, sequence) of (P, 128 heads, 128 + 64 | 128) bf16 on a
# v5e, 512 query rows a call, walk | kernel, ms (PERF.md section 6, PR 35;
# bench_runs/pr35_selected_flash_tiles3.jsonl): 4.46 | 1.32 at P = 1,024,
# 13.2 | 2.69 at 2,048, 42.7 | 7.32 at 4,096, 584.6 | 86.0 at 16,384. The
# selection bites from `index_topk` + 1 = 2,049 positions and no bucket is
# shorter than 16,384: the constant is where the family's TPU determinism
# class changes, not where the kernel starts to win.
_KERNEL_MIN_ROWS = 2048


def kernel_serves(p: int, dn: int, dv: int) -> bool:
    """Whether a prefill of `p` positions with per-head widths `dn` |
    `dv` runs the kernel here: a head's K-nope and V columns have to be
    whole lanes, as the published widths are."""
    return jax.default_backend() == "tpu" and p >= _KERNEL_MIN_ROWS \
        and dn % _LANES == 0 and dv % _LANES == 0


def selected_attention(kv, heads: int, dn: int, *, scale: float):
    """Prefill attention of one sequence under the selection's mask, the
    path read off the call: on a TPU from `_KERNEL_MIN_ROWS` prompt
    positions the Pallas kernel, else the walk. kv[P, H·(dn + dv)] is the
    expansion's product as it lies. → attend(q_nope, q_pe, k_pe, keep, i,
    rows, qpos) → [R, H·dv] for query block `i` (arguments as
    `selected_walk`'s).

    The path is chosen once a sequence and the query blocks are served
    by what it returns, because each path wants kv in a form of its own:
    the walk per head, `[P, H, dn + dv]`, which on a TPU is another
    tiling of the same numbers (a 1 GB copy at the cell's shapes, which
    XLA does not move out of a loop over the query blocks), the kernel
    as they lie."""
    p = kv.shape[0]
    if kernel_serves(p, dn, kv.shape[1] // heads - dn):
        def attend(q_nope, q_pe, k_pe, keep, i, rows, qpos):
            return selected_flash_attention(
                q_nope, q_pe, kv, k_pe,
                keep & (jnp.arange(p)[None, :] <= qpos),
                i * q_nope.shape[0], scale=scale)
        return attend
    kv = kv.reshape(p, heads, -1)
    return lambda q_nope, q_pe, k_pe, *at: selected_walk(
        q_nope, q_pe, kv, k_pe, *at, scale=scale)


# -- the band: sliding-window attention, the mask made in the kernel ---------
#
# dots3_note's sliding layers attend the last `window` keys of each row (513
# at the published width, the row's own included) with 64 heads of 192 +
# 64 | 128. There is no selection, so no `keep` array: the band is two
# comparisons of positions, made in VMEM, and a query block walks only the
# key blocks the band reaches — 31 of the 256 blocks of the unmasked grid a
# group of heads at 8,192 positions and 512-row tiles, where the causal walk
# visits 136. A head's query and key come JOINED, nope | rope (192 + 64 =
# 256 lanes: one product a key block, whole lanes, where 192 alone is not),
# the keys with the rotary key broadcast to every head; the values apart.
# One call a (layer, sequence), all rows: the first row is position 0, so
# every bound is static but the query block's, which the index maps read
# off the program id.


def _window_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale: float, window: int, block_q: int, block_k: int,
                   group: int, dk: int, dv: int, n_k: int, steps: int):
    i, j = pl.program_id(1), pl.program_id(2)
    kb = _first(0, i, block_q, block_k, window, jnp.maximum) + j

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, F32)
        l_ref[...] = jnp.zeros(l_ref.shape, F32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

    @pl.when(kb <= _last(0, i, block_q, block_k, n_k, jnp.minimum))
    def _():
        qpos = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        ok = (kpos <= qpos) & (kpos > qpos - window)
        for g in range(group):
            q = q_ref[:, g * dk:(g + 1) * dk]
            k = k_ref[:, g * dk:(g + 1) * dk]
            v = v_ref[:, g * dv:(g + 1) * dv]
            s = jax.lax.dot_general(q, k, _NT,
                                    preferred_element_type=F32) * scale
            s = jnp.where(ok, s, NEG_INF)
            m = m_ref[g]                  # a row's max, in every lane
            mb = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - _spread(mb, block_k)), 0.0)
            alpha = jnp.exp(m - mb)
            l_ref[g] = l_ref[g] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * _spread(alpha, dv) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=F32)
            m_ref[g] = mb

    @pl.when(j == steps - 1)
    def _():
        for g in range(group):
            o_ref[:, g * dv:(g + 1) * dv] = \
                (acc_ref[g] / _spread(l_ref[g], dv)).astype(o_ref.dtype)


def _window_vmem_bytes(block_q: int, block_k: int, group: int, dk: int,
                       dv: int, itemsize: int) -> int:
    """`_vmem_bytes`' count for the band's blocks: q, k, v and the
    output, two buffers each; the heads' running state; the scores, the
    probabilities and their cast a head in flight, two at a time, and
    the mask."""
    blocks = 2 * group * (block_q * (dk + dv) + block_k * (dk + dv)) \
        * itemsize
    state = group * block_q * (2 * _LANES + dv) * 4
    work = (2 * 3 + 1) * block_q * block_k * 4
    return blocks + state + work


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "interpret"))
def window_flash_attention(q, k, v, *, window: int, scale: float,
                           interpret: bool = False):
    """q[P, H, dk], k[P, H, dk], v[P, H, dv] of one sequence (positions
    0 .. P-1; dk and dv whole lanes) → [P, H·dv]: row t attends keys
    t - window < s <= t. `window_walk`'s result but for the order of the
    softmax's sums. Tiles come from the static shape."""
    p, heads, dk = q.shape
    dv = v.shape[-1]
    block_q, block_k = _tiles(p, p)
    group = _group(heads)
    qf = _pad_to(q.reshape(p, heads * dk), 0, block_q)
    kf = _pad_to(k.reshape(p, heads * dk), 0, block_k)
    vf = _pad_to(v.reshape(p, heads * dv), 0, block_k)
    n_q, n_k = qf.shape[0] // block_q, kf.shape[0] // block_k
    steps = max(_last(0, i, block_q, block_k, n_k) + 1
                - _first(0, i, block_q, block_k, window)
                for i in range(n_q))

    def at(i, j):
        return jnp.minimum(
            _first(0, i, block_q, block_k, window, jnp.maximum) + j,
            _last(0, i, block_q, block_k, n_k, jnp.minimum))

    def spec(rows, width, index):
        return pl.BlockSpec((rows, group * width), index)

    out = pl.pallas_call(
        functools.partial(_window_kernel, scale=scale, window=window,
                          block_q=block_q, block_k=block_k, group=group,
                          dk=dk, dv=dv, n_k=n_k, steps=steps),
        grid=(heads // group, n_q, steps),
        in_specs=[spec(block_q, dk, lambda h, i, j: (i, h)),
                  spec(block_k, dk, lambda h, i, j: (at(i, j), h)),
                  spec(block_k, dv, lambda h, i, j: (at(i, j), h))],
        out_specs=spec(block_q, dv, lambda h, i, j: (i, h)),
        scratch_shapes=[pltpu.VMEM((group, block_q, _LANES), F32),
                        pltpu.VMEM((group, block_q, _LANES), F32),
                        pltpu.VMEM((group, block_q, dv), F32)],
        out_shape=jax.ShapeDtypeStruct((qf.shape[0], heads * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(
                _VMEM_DEFAULT,
                _window_vmem_bytes(block_q, block_k, group, dk, dv,
                                   kf.dtype.itemsize) + _VMEM_HEADROOM)),
        interpret=interpret,
        name="window_flash_attention",
    )(qf, kf, vf)
    return out[:p]


def window_walk(q, k, v, *, window: int, scale: float, block: int):
    """XLA's walk of the band, the exact reference and the only compiled
    form off the TPU: q[P, H, dk], k[P, H, dk], v[P, H, dv] → [P, H·dv],
    a `block` of query rows at a time (`lax.map`) over the key blocks
    (`block` rows each) from the one that holds its first row's first
    key to its diagonal — `walk_blocks`' bounds at tiles (block, block) —
    under the band's mask, with a running max / normaliser /
    accumulator. `block` divides P."""
    p, nh, dk = q.shape
    dv = v.shape[-1]
    rows = jnp.arange(block)

    def query_block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        qpos = i * block + rows[:, None]

        def attend(j, carry):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, j * block, block)
            vb = jax.lax.dynamic_slice_in_dim(v, j * block, block)
            s = jnp.einsum("qhd,khd->hqk", qb, kb,
                           preferred_element_type=F32)
            kpos = j * block + rows[None, :]
            ok = (kpos <= qpos) & (kpos > qpos - window)
            s = jnp.where(ok[None], s * scale, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            pr = jnp.where(ok[None], jnp.exp(s - m_new[..., None]), 0.0)
            fix = jnp.exp(m - m_new)
            l = l * fix + pr.sum(axis=-1)
            acc = acc * fix[..., None] + jnp.einsum(
                "hqk,khd->hqd", pr.astype(vb.dtype), vb,
                preferred_element_type=F32)
            return m_new, l, acc

        m0 = jnp.full((nh, block), NEG_INF, F32)
        _, l, acc = jax.lax.fori_loop(
            _first(0, i, block, block, window, jnp.maximum), i + 1, attend,
            (m0, jnp.zeros((nh, block), F32),
             jnp.zeros((nh, block, dv), F32)))
        o = (acc / l[..., None]).astype(q.dtype)
        return jnp.moveaxis(o, 0, 1).reshape(block, nh * dv)

    return jax.lax.map(query_block, jnp.arange(p // block)).reshape(
        p, nh * dv)


def window_attention(q, k, v, *, window: int, scale: float, block: int):
    """Sliding-window prefill attention of one sequence, the path read
    off the call by the selection kernel's rule (`kernel_serves`, with
    the joined nope | rope width for dn): on a TPU from
    `_KERNEL_MIN_ROWS` positions `window_flash_attention`, else
    `window_walk` at `block` rows. q[P, H, dk], k[P, H, dk], v[P, H, dv]
    → [P, H·dv]."""
    if kernel_serves(q.shape[0], q.shape[-1], v.shape[-1]):
        return window_flash_attention(q, k, v, window=window, scale=scale)
    return window_walk(q, k, v, window=window, scale=scale, block=block)
