"""Grouped matrix products over stacked expert kernels: the held experts'
products of `models.trinity.model.routed_experts` as one Pallas call a
kernel, on the TPU, for calls whose tiles are small and many.

Why: a decode step routes a handful of rows to each expert, so
`routed_experts`' tile loop runs one `fori_loop` iteration an 8-row
tile — about 1,050 a `joyai_llm_flash` step, each a few separate
operations that read one expert's three kernels (9.4 MB) and pay the
start of their fusions and the loop's own bookkeeping, with nothing
streaming the next expert's kernels while a tile multiplies
(ROADMAP S8). One grouped product a kernel walks the same used tiles as
one pipelined grid: megablox's `gmm` (installed with jax, not this
repo's code) takes the row buffer `xs[rows, K]`, the stacked kernels
`w[groups, K, N]` and each group's row count, and its grid's length is
the number of used tiles, a traced value, so an expert no row chose is
never read.

The layout is the caller's: every group a whole number of `tm`-row
tiles, so each grid step is one tile of one expert, and with the whole
contraction in one block (`tk = K`, `tiling`) consecutive tiles of one
expert fetch its kernel block once. Precision is `_dot`'s: the product
accumulates in float32 and is rounded to the operands' type. Only the
order of the float32 sums may differ from XLA's dot, so each side of
`kernel_serves` is a determinism class of its own
(docs/determinism.md). The rows past the last group are never written:
the caller reads held rows only.

Off the TPU the kernel runs only where a test sends it, in Pallas's
interpreter (tests/test_grouped_experts.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import megablox

# Tile rows at or below which a call on the TPU may take the grouped
# product. One `route` + `routed_experts` call on a v5e, loop | grouped,
# microseconds (tools/joyai_diag.py experts; PERF.md section 6):
# 8-row tiles 4,186 | 3,216 at joyai_llm_flash's 64 rows, 3,135 | 2,461
# at its 32, 866 | 859 at trinity's 16, 610 | 617 at deepseek_v32's 8,
# 1,029 | 996 at dots3_note's 16; 64-row tiles 7,033 | 5,784 at
# joyai_llm_flash's 1,024-row prefill block; 256-row tiles 9,800 |
# 13,050, 10,603 | 13,297 and 9,565 | 12,988 at the prefill calls of
# trinity (8,192 rows), deepseek_v32 and dots3_note (4,096). No cell
# calls with 128-row tiles: the constant rests on 64 and 256.
_MAX_TILE = 64
# Held experts a call must be able to reach to take it: the grouped
# call's own costs (three kernel launches, gmm's group metadata, no
# overlap with its neighbours) are paid a call and only many used tiles
# repay them. Whole cells with every 8-row call grouped, one pair each on
# a v5e (PERF.md section 6): joyai_llm_flash, 256 reachable a
# call, +25.3 %; dots3_note (16) -0.43 %, trinity (8) -1.24 %,
# deepseek_v32 (4) -0.50 %. No cell calls between 16 and 256.
_MIN_EXPERTS = 64
# VMEM for one grid step's blocks, double-buffered, and the float32
# accumulator: half of Mosaic's default scoped budget on a v5e (16 MiB),
# the rest left to its own scratch (gmm states no limit of its own).
_BLOCK_BYTES = 8 * 1024 * 1024
_LANES = 128


def kernel_serves(tile: int, experts: int) -> bool:
    """Whether a `routed_experts` call with `tile`-row tiles that can
    reach `experts` held experts takes the grouped product here: on the
    TPU, from those two static numbers alone."""
    return (jax.default_backend() == "tpu" and tile <= _MAX_TILE
            and experts >= _MIN_EXPERTS)


def _splits(dim: int) -> list[int]:
    """Block edges for an axis of `dim`: the whole axis, then its
    lane-aligned divisors, largest first."""
    return [dim] + [c for c in range(dim - dim % _LANES, 0, -_LANES)
                    if c < dim and dim % c == 0]


def tiling(tm: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """(tm, tk, tn) for `gmm`, static from the shapes: the whole
    contraction where it fits (an expert's kernel block is then fetched
    once for its consecutive tiles), then the widest output block whose
    double-buffered lhs, rhs and output blocks and float32 accumulator
    stay within `_BLOCK_BYTES` — e.g. (8, 2048, 768) for
    joyai_llm_flash's gate at a decode step."""
    for tk in _splits(k):
        for tn in _splits(n):
            if (2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
                    <= _BLOCK_BYTES):
                return tm, tk, tn
    raise ValueError(f"no gmm tiling of ({tm}, {k}, {n}) fits "
                     f"{_BLOCK_BYTES} bytes of VMEM")


def grouped_dot(x: jax.Array, w: jax.Array, sizes: jax.Array,
                tile: int) -> jax.Array:
    """x[rows, K] · w[g, K, N] by groups → [rows, N] in x's type: rows
    sizes[0] of group 0 first, then group 1's, ...; every size a whole
    number of `tile`-row tiles; rows past the last group unwritten."""
    return megablox.gmm(
        x, w, sizes, preferred_element_type=x.dtype,
        tiling=tiling(tile, x.shape[1], w.shape[2], x.dtype.itemsize),
        interpret=jax.default_backend() != "tpu")


def grouped_mlp(x: jax.Array, kernels: dict, sizes: jax.Array, tile: int,
                form: str) -> jax.Array:
    """One expert function by groups over x[rows, K], `grouped_dot`'s
    layout: `form` "swiglu" is silu(x·gate) · (x·up), "relu2" is
    relu(x·up)², each in float32 and rounded to x's type, then · down —
    `models.trinity.model.swiglu` and `relu2`'s roundings, a product a
    kernel."""
    f32 = jnp.float32
    if form == "swiglu":
        g, u = (grouped_dot(x, kernels[n], sizes, tile)
                for n in ("gate", "up"))
        h = (jax.nn.silu(g.astype(f32)) * u.astype(f32)).astype(x.dtype)
    elif form == "relu2":
        u = grouped_dot(x, kernels["up"], sizes, tile)
        h = jnp.square(jax.nn.relu(u.astype(f32))).astype(x.dtype)
    else:
        raise ValueError(f"no expert form {form!r}")
    return grouped_dot(h, kernels["down"], sizes, tile)
