"""Pallas flash attention — blockwise softmax attention in VMEM.

Why: self-attention over S=9216 tokens (anythingv3's UNet at 768x768,
its VAE and Kandinsky's MOVQ mid-block at 96x96 latents, the video
UNet's spatial attention) materializes a 9216 x 9216 float32 score
matrix per head through the XLA einsum path (~340 MB a head) — HBM-bound.
So does the added-KV attention of Kandinsky's decoder UNet at its 48x48
level (2304 queries over 10 context + 2304 spatial keys, 12 heads of 64:
2 GB of scores a call at batch 8, seven calls a forward).
The flash form never materializes scores: K/V pass through VMEM in
blocks under a running max / normalizer / accumulator (the same
online-softmax mathematics as ops/ring.py, one level down the memory
hierarchy). It is the first device operation of the benchmark's
`mix-768-backlog` cell (PERF.md section 5); `flash_roofline_pct` there
is this kernel's share of its roofline.

Precision, the policy `sp_attention_reference` states for the same
model: q, k and v go to the MXU in the type they arrive in (bf16 in
both cells; float32 inputs stay float32), both products accumulate in
float32, scores / max / normalizer / accumulator and the exponent are
float32, the scale multiplies the float32 scores, and the probabilities
are cast to v's type for the second product.

Layout: grid = (batch*heads, Sq/block_q). A program owns one Q block
and sees the whole K and V of its head as one VMEM block (fetched once a
head: the block index does not move with the Q block), and walks it in
K blocks of block_k. D is padded to 128 lanes and the sequences to their
tiles; only a K block that can hold a padded key is masked — statically
none when block_k divides the keys, else the last one.

Tiles come from the call's static shape (`_tiles`), not from a name.
What a trip over one K block costs on a v5e is its chain — q @ k.T, two
lane reductions, the exponent, p @ v, each waiting on the last — far
more than its arithmetic: at 128 x 128 tiles a trip took 374 ns against
43 ns of MXU time, and neither bf16 operands nor dropping the mask moved
that (PERF.md section 6, PR 26). So the K tile is as long as the keys
allow up to 1024 rows, the Q tile is what a 1 MiB float32 score tile
and a 1 MiB float32 accumulator then leave (256 rows beside 1024 keys,
1024 rows beside the 77 keys of cross-attention, at most 512 at D=512),
and `_UNROLL` K blocks share a trip of the loop so that one block's
products overlap its neighbour's softmax. "Pads least" alone is not the
rule for the K tile: the 2314 keys of Kandinsky's added-KV attention
(2304 + 10 context tokens) are 18 x 128 and ten more, so the tile that
pads least is 128 — 19 K blocks a Q block, 3.94 ms a call at (8, 12,
2304, 2314, 64), pads and slice included, against 2.22-2.82 ms with any
K tile of 512-1024 and 2,560-3,072 padded keys; in the bucket program
the kernel alone took 3.49 ms at (768, 128) and takes 1.91 ms at (384,
640) (PERF.md section 6, PR 29). A K tile is therefore at least 512 rows
wherever the keys are; every shape whose tile was 512 or more keeps it,
and fewer keys than 512 take one block as before. VMEM per program is reckoned
in `_vmem_bytes` and stated as the call's limit: K/V whole and double-
buffered are 9 MiB at S=9216, D=40 (lane-padded) and 36 MiB at D=512
in bf16, 72 MiB in float32, beside at most 10 MiB of work arrays.

`flash_attention` is a drop-in for `sp_attention_reference` ([B, H, S, D]
→ [B, H, S, D]); `interpret=True` runs it on CPU for tests
(tests/test_flash_kernel.py in tier-1).
"""
from __future__ import annotations

import contextvars
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))     # q @ k.T without a transpose op
_LANES = 128
# Two float32 arrays of a program are shaped by the tiles, the score
# tile [block_q, block_k] and the accumulator [block_q, D]; each is held
# to 1 MiB (256 vregs an elementwise op, which Mosaic unrolls), and no
# tile is longer than 1024 rows.
_WORK_BYTES = 1024 * 1024
_MAX_TILE = 1024
# Padding is weighed against the K blocks a Q block walks: a score costs
# about the same from 512 keys a block up and 1.5 to 2 times that below
# (module docstring), more than any padding a tile of 512-1024 can add
# to keys that reach 512. Fewer keys than that take one block.
_MIN_K_TILE = 512
# K blocks a trip of the loop takes: consecutive blocks depend on each
# other only through (m, l, acc), so the next block's q @ k.T runs on
# the MXU while this block's softmax runs on the VPU. Written out by
# hand: Mosaic lowers `fori_loop(unroll=)` only for 1 and for all.
_UNROLL = 3
# Mosaic's default scoped-VMEM budget on a v5e; the limit is stated per
# call from the block sizes (_vmem_bytes), which moves no bits.
_VMEM_DEFAULT = 16 * 1024 * 1024
_VMEM_HEADROOM = 8 * 1024 * 1024   # Mosaic's own scratch and spills


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _tile(n: int, cap: int, floor: int = _LANES) -> int:
    """The tile for a sequence of n rows: of the multiples of 128 from
    the floor (or from n rounded up, if that is less) up to the cap, the
    one that pads n least; of equals, the largest."""
    cap = max(_LANES, min(_MAX_TILE, cap) // _LANES * _LANES)
    floor = min(floor, cap, _round_up(n, _LANES))
    return min(range(cap, floor - 1, -_LANES), key=lambda t: _round_up(n, t))


def _tiles(sq: int, kv_len: int, d: int) -> tuple[int, int]:
    """(block_q, block_k) from the call's static shape: the K tile as
    long as the keys allow and no shorter than `_MIN_K_TILE` where they
    reach it, the Q tile from what the two float32 work arrays then
    leave."""
    block_k = _tile(kv_len, _MAX_TILE, _MIN_K_TILE)
    rows = _WORK_BYTES // (4 * max(block_k, _round_up(d, _LANES)))
    return _tile(sq, rows), block_k


def _vmem_bytes(block_q: int, block_k: int, kv_p: int, d_p: int,
                itemsize: int) -> int:
    """What one program of the grid holds in VMEM, lanes padded to 128:
    the whole K and V sequence and the Q and O blocks, two buffers each
    (Pallas pipelines them); per K block in flight the scores, the
    probabilities and the probabilities in v's type; the accumulator and
    the product added to it."""
    lanes = _round_up(d_p, _LANES)
    resident = 2 * 2 * kv_p * lanes * itemsize
    blocks = 2 * 2 * block_q * lanes * itemsize
    work = _UNROLL * 3 * block_q * block_k * 4 + 2 * block_q * lanes * 4
    return resident + blocks + work


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_len: int, scale: float,
            block_k: int):
    q = q_ref[0]                                   # [block_q, D], as handed
    # whole K blocks hold no padded key; `ragged` real keys are left over
    n_whole, ragged = divmod(kv_len, block_k)

    def step(start, carry, valid=block_k):
        m, l, acc = carry
        k = k_ref[0, pl.ds(start, block_k), :]     # [block_k, D]
        v = v_ref[0, pl.ds(start, block_k), :]
        s = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32) * scale
        if valid < block_k:                        # static: padded keys here
            kpos = jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
            s = jnp.where(kpos < valid, s, NEG_INF)
        mb = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - mb)
        alpha = jnp.exp(m - mb)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return mb, l, acc

    def trip(j, carry):
        for u in range(_UNROLL):
            carry = step(
                pl.multiple_of((j * _UNROLL + u) * block_k, block_k), carry)
        return carry

    block_q, d = q.shape
    carry = (jnp.full((block_q, 1), NEG_INF, jnp.float32),
             jnp.zeros((block_q, 1), jnp.float32),
             jnp.zeros((block_q, d), jnp.float32))
    # a loop only where it has two trips or more; what it leaves, and the
    # block with padded keys, at static offsets after it
    trips = n_whole // _UNROLL if n_whole >= 2 * _UNROLL else 0
    if trips:
        carry = jax.lax.fori_loop(0, trips, trip, carry)
    for j in range(trips * _UNROLL, n_whole):
        carry = step(j * block_k, carry)
    if ragged:
        carry = step(n_whole * block_k, carry, ragged)
    _, l, acc = carry
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = _round_up(size, mult) - size
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Exact attention, flash-style. q/k/v: [B, H, S, D] → [B, H, Sq, D].

    D is padded to 128 lanes in HBM before the call. Handing the kernel
    the native D (Mosaic lane-pads blocks in VMEM itself) read the same
    on the chip, pad, kernel and slice together: 9.75 and 9.70 ms at
    (32, 9216, 9216, 40), 1.35 and 1.33 ms at (32, 2304, 2304, 80)
    (PERF.md section 6, PR 26) — the calls are MXU-bound, so there is
    one path. A shape at which the unpadded form wins is chosen here,
    from the static shape, not by a keyword."""
    b, h, sq, d = q.shape
    kv_len = k.shape[2]
    scale = 1.0 / np.sqrt(d)

    block_q, block_k = _tiles(sq, kv_len, d)
    qf = _pad_to(_pad_to(q.reshape(b * h, sq, d), 1, block_q), 2, _LANES)
    kf = _pad_to(_pad_to(k.reshape(b * h, kv_len, d), 1, block_k), 2, _LANES)
    vf = _pad_to(_pad_to(v.reshape(b * h, kv_len, d), 1, block_k), 2, _LANES)
    bh, sq_p, d_p = qf.shape
    kv_p = kf.shape[1]

    out = pl.pallas_call(
        functools.partial(_kernel, kv_len=kv_len, scale=scale,
                          block_k=block_k),
        grid=(bh, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d_p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, kv_p, d_p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, kv_p, d_p), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_p), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d_p), q.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            _VMEM_DEFAULT,
            _vmem_bytes(block_q, block_k, kv_p, d_p, kf.dtype.itemsize)
            + _VMEM_HEADROOM)),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :sq, :d].reshape(b, h, sq, d)


# The mesh of the GSPMD program being traced, if any (see on_mesh).
_KERNEL_MESH: contextvars.ContextVar = contextvars.ContextVar(
    "arbius_kernel_mesh", default=None)


def on_mesh(fn, mesh):
    """Wrap a solve fn that is about to be jitted with GSPMD shardings
    over `mesh`, so the kernels traced inside it know the mesh. XLA's
    partitioner cannot split a Mosaic custom call — jax refuses at
    lowering ("Mosaic kernels cannot be automatically partitioned") —
    so under a mesh `attention` runs the kernel per shard in a
    shard_map. `fn`'s name is kept: it is part of the program's name."""
    @functools.wraps(fn)
    def traced(*args):
        token = _KERNEL_MESH.set(mesh)
        try:
            return fn(*args)
        finally:
            _KERNEL_MESH.reset(token)

    return traced


def _flash(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """flash_attention, per shard when a GSPMD mesh is being traced.
    Every (batch row, head) is its own program of the kernel grid, so
    splitting rows over dp and heads over tp moves no bits; an axis
    that does not divide stays replicated (an under-filled bucket runs
    the whole batch on every dp lane, as meshsolve.batch_specs does)."""
    mesh = _KERNEL_MESH.get()
    if mesh is None:
        return flash_attention(q, k, v)

    def axis(name: str, size: int) -> str | None:
        n = mesh.shape.get(name, 1)
        return name if n > 1 and size % n == 0 else None

    spec = P(axis("dp", q.shape[0]), axis("tp", q.shape[1]), None, None)
    return jax.shard_map(flash_attention, mesh=mesh,
                         in_specs=(spec, spec, spec), out_specs=spec,
                         check_vma=False)(q, k, v)


# Query rows from which `attention` takes the kernel on a TPU.
_KERNEL_MIN_ROWS = 1024


def attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Exact attention for [B, H, S, D], the path read off the call: on
    a TPU from `_KERNEL_MIN_ROWS` query rows the pallas flash kernel,
    else `sp_attention_reference` (XLA's einsum, the only compiled form
    off the TPU).

    Both are exact attention and differ in reduction order, so each side
    of the rule is a determinism class: moving the constant re-records
    both image models' TPU goldens. What is measured: the kernel wins at
    2,304 rows (ledger, PR 29: `k2-768-backlog` 1,843.6 → 2,175.5 sol/h
    when those calls left XLA's two fusions) and at 9,216 the einsum's
    float32 scores do not fit beside the weights. What is not: no cell
    calls it between 1,024 and 2,303 rows, so the 1,024 itself rests on
    no measurement, and the 576-row level is measured (the kernel 0.33-
    0.53 ms against 0.70) and not acted on (PERF.md section 7)."""
    from arbius_tpu.ops.ring import sp_attention_reference

    if jax.default_backend() == "tpu" and q.shape[2] >= _KERNEL_MIN_ROWS:
        return _flash(q, k, v)
    return sp_attention_reference(q, k, v)
