"""Pallas flash attention — blockwise softmax attention in VMEM.

Why: the video UNet's spatial attention at zeroscope shape (1024×576 →
latent 128×72 = 9216 tokens) materializes a 9216² f32 score matrix per
head through the XLA einsum path (~340 MB/head-batch) — HBM-bound. The
flash form never materializes scores: K/V stream through VMEM in blocks
while running max/normalizer/accumulator stats (the same online-softmax
math as ops/ring.py, one level down the memory hierarchy).

Kernel layout (pallas_guide.md patterns):
  grid = (batch*heads, Sq/BLOCK_Q); each program owns one Q block in
  VMEM, loops over K/V blocks with fori_loop, f32 accumulators, MXU
  matmuls via jnp.dot(preferred_element_type=f32). Shapes are padded to
  the (8, 128) f32 tile grid; padded K positions are masked with -inf
  before the softmax stats, so padding never changes the math.

`flash_attention` is a drop-in for `sp_attention_reference` ([B, H, S, D]
→ [B, H, S, D]); `interpret=True` runs it on CPU for tests.
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

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30
# Mosaic's default scoped-VMEM budget on a v5e. Each program holds the
# WHOLE K and V sequence as one block, double-buffered by Pallas, so the
# budget is stated per call from the block sizes: the VAE mid-block
# (one head, D=512, S=4096) needs 16 MiB for K/V alone and libtpu 0.0.34
# refuses it at the default once the batch exceeds 1 (RESOURCE_EXHAUSTED
# in vmem); S=9216 needs 36 MiB. Stating the limit moves no bits.
_VMEM_DEFAULT = 16 * 1024 * 1024
_VMEM_HEADROOM = 8 * 1024 * 1024   # Q/O blocks + the kernel's f32 copies


def _kernel(q_ref, k_ref, v_ref, o_ref, *, kv_len: int, scale: float):
    q = q_ref[0].astype(jnp.float32)                  # [BLOCK_Q, D]
    n_kv = k_ref.shape[1] // BLOCK_K

    m0 = jnp.full((BLOCK_Q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((BLOCK_Q,), jnp.float32)
    acc0 = jnp.zeros((BLOCK_Q, q.shape[-1]), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * BLOCK_K, BLOCK_K), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * BLOCK_K, BLOCK_K), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        # mask K padding (positions >= kv_len)
        kpos = j * BLOCK_K + jax.lax.broadcasted_iota(jnp.int32,
                                                      (1, BLOCK_K), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)
        mb = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - mb[:, None])
        alpha = jnp.exp(m - mb)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return mb, l, acc

    m, l, acc = jax.lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = _round_up(size, mult) - size
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("interpret", "pad_d"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    interpret: bool = False, pad_d: bool = True) -> jax.Array:
    """Exact attention, flash-style. q/k/v: [B, H, S, D] → [B, H, Sq, D].

    `pad_d=False` skips the explicit head-dim pad to 128 lanes and hands
    the native D (40/80/160 at SD-1.5 levels) straight to the kernel —
    Mosaic lane-pads blocks in VMEM internally, so the math is identical,
    but the jnp.pad round-trips through HBM (a 3.2× inflation of Q/K/V
    traffic at D=40) disappear. MXU pass count is the same either way
    (contraction/lane dims ≤128 occupy one pass regardless), so this
    targets HBM bandwidth, not FLOPs — measured per-impl by
    tools/tpu_profile.py before it becomes the default."""
    b, h, sq, d = q.shape
    kv_len = k.shape[2]
    scale = 1.0 / np.sqrt(d)

    d_mult = 128 if pad_d else 1
    qf = _pad_to(_pad_to(q.reshape(b * h, sq, d), 1, BLOCK_Q), 2, d_mult)
    kf = _pad_to(_pad_to(k.reshape(b * h, kv_len, d), 1, BLOCK_K), 2, d_mult)
    vf = _pad_to(_pad_to(v.reshape(b * h, kv_len, d), 1, BLOCK_K), 2, d_mult)
    bh, sq_p, d_p = qf.shape
    kv_p = kf.shape[1]

    out = pl.pallas_call(
        functools.partial(_kernel, kv_len=kv_len, scale=scale),
        grid=(bh, sq_p // BLOCK_Q),
        in_specs=[
            pl.BlockSpec((1, BLOCK_Q, d_p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, kv_p, d_p), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, kv_p, d_p), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_Q, d_p), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, d_p), q.dtype),
        # K and V blocks, two buffers each, lane-padded to 128 in VMEM
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
            _VMEM_DEFAULT,
            2 * 2 * kv_p * _round_up(d_p, 128) * kf.dtype.itemsize
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


def _flash(q: jax.Array, k: jax.Array, v: jax.Array, *,
           pad_d: bool = True) -> jax.Array:
    """flash_attention, per shard when a GSPMD mesh is being traced.
    Every (batch row, head) is its own program of the kernel grid, so
    splitting rows over dp and heads over tp moves no bits; an axis
    that does not divide stays replicated (an under-filled bucket runs
    the whole batch on every dp lane, as meshsolve.batch_specs does)."""
    kernel = functools.partial(flash_attention, pad_d=pad_d)
    mesh = _KERNEL_MESH.get()
    if mesh is None:
        return kernel(q, k, v)

    def axis(name: str, size: int) -> str | None:
        n = mesh.shape.get(name, 1)
        return name if n > 1 and size % n == 0 else None

    spec = P(axis("dp", q.shape[0]), axis("tp", q.shape[1]), None, None)
    return jax.shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


VALID_ATTN_IMPLS = ("auto", "flash", "flash_nopad", "einsum")


def _read_attn_impl() -> str:
    import os

    impl = os.environ.get("ARBIUS_ATTN_IMPL", "auto")
    if impl not in VALID_ATTN_IMPLS:
        # a typo must not silently measure/run a different impl than the
        # label claims — the A/B exists to decide the production dispatch
        raise ValueError(f"ARBIUS_ATTN_IMPL={impl!r} not in "
                         + "|".join(VALID_ATTN_IMPLS))
    return impl


# Pinned ONCE at import. Reading the env var at trace time looked like a
# runtime toggle but wasn't one: jitted callers only re-read it on a
# retrace, so flipping it after a shape bucket compiled silently kept
# the old impl — and a flip that DID land would change reduction order,
# i.e. the golden CIDs' determinism class. The node boots against this
# pinned value (MinerNode._check_attention_impl) and the profiler
# threads its A/B through set_attention_impl(), re-jitting per impl.
_ATTN_IMPL = _read_attn_impl()


def attention_impl() -> str:
    """The attention dispatch pinned for this process."""
    return _ATTN_IMPL


def set_attention_impl(impl: str | None) -> str:
    """Explicitly re-pin the dispatch (A/B measurement only — callers
    own the retrace; tools/tpu_profile.py builds a fresh jit per impl).
    `None` restores the env-pinned import-time value. Returns the
    previous value so callers can restore it."""
    global _ATTN_IMPL

    if impl is None:
        impl = _read_attn_impl()
    if impl not in VALID_ATTN_IMPLS:
        raise ValueError(f"attention impl {impl!r} not in "
                         + "|".join(VALID_ATTN_IMPLS))
    prior, _ATTN_IMPL = _ATTN_IMPL, impl
    return prior


def attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Backend-dispatching exact attention for [B, H, S, D].

    TPU + long sequences → the pallas flash kernel; otherwise the XLA
    einsum path (which XLA already fuses well at short S, and which is
    the only compiled option off-TPU).

    The module-level pinned impl (ARBIUS_ATTN_IMPL at import, or an
    explicit set_attention_impl) overrides the dispatch for on-chip A/B
    measurement (tools/tpu_profile.py drives the FULL UNet step under
    each value): "flash" | "flash_nopad" | "einsum" | "auto" (default).
    All three are exact attention; they differ in reduction order
    (ULP-class output drift), so a fleet pins ONE impl per determinism
    class — changing the production dispatch re-records the platform
    goldens, and a node booting with a non-default impl must prove its
    goldens still hold (node.py boot check).
    """
    from arbius_tpu.ops.ring import sp_attention_reference

    impl = _ATTN_IMPL
    if impl == "einsum":
        return sp_attention_reference(q, k, v)
    on_tpu = jax.default_backend() == "tpu"
    if impl == "flash" and on_tpu:
        return _flash(q, k, v)
    if impl == "flash_nopad" and on_tpu:
        return _flash(q, k, v, pad_d=False)
    # flash impls requested off-TPU fall through here: einsum is the only
    # compiled option off-TPU, so a fleet pinning "flash" still boots on
    # CPU dev hosts (the profiler only labels non-auto impls on TPU)
    if on_tpu and q.shape[2] >= 1024:
        return _flash(q, k, v)
    return sp_attention_reference(q, k, v)
