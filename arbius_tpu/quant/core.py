"""quantserve core — weight quantization + dequant under the determinism gate.

The zoo runs bf16-compute/f32-stats everywhere (models/common.py); this
module adds the int8/fp8 execution modes the ROADMAP's quantized-serving
item calls for. The scheme is symmetric per-output-channel weight
quantization (the last axis of every kernel is the output-feature axis
throughout the zoo — flax Dense/Conv convention):

    scale  = absmax(w, all axes but -1) / bound        (float32)
    int8   q = clip(round(w / scale), -127, 127)       (int8 storage)
    fp8    q = (w / scale) -> float8_e4m3fn            (fp8 storage)
    dequant  = q -> float32 * scale                    (inside the jit)

Quantization happens ONCE at checkpoint-load (node/factory.py); the
runner then holds the quantized tree — int8/fp8 kernels plus explicit
f32 scales — and every bucket program begins by dequantizing it, so HBM
weight residency and any cross-chip weight collective move 1-byte
elements while the compute path stays the bf16/f32 program the family
always ran. Dequant ALWAYS passes through float32 (never int8→bf16
directly) and scales are always float32 — GRAPH407 audits exactly this
contract in every traced program.

Determinism: `quantize_tree` is a pure jittable function of the weight
tree, so a checkpoint quantizes to the same bits on every host, and the
dequantizing bucket program is one fixed XLA program per (family,
bucket, layout, mode) — its own graphlint golden, its own AOT cache
key. A mode is never a runtime branch inside a program.
"""
# detlint: enforce[DET101,DET102,DET103,DET104,DET105]
from __future__ import annotations

from arbius_tpu.quant.modes import (
    DEFAULT_MODE,
    FP8_BOUND,
    INT8_BOUND,
    PRECISION_MODES,
    mode_tag,
    validate_mode,
    wire_width,
)

# guard against all-zero kernels: a zero absmax would divide out to
# NaN scales; the floor keeps the scale finite and the dequant exact 0
_SCALE_FLOOR = 1e-12

# the sentinel keys a quantized leaf carries; dict leaves of exactly
# this shape are what `dequantize_tree` unpacks (pytree-stable: dict
# keys flatten sorted, so "qs" then "qv")
QUANT_KEYS = frozenset({"qs", "qv"})


def storage_dtype(mode: str):
    """The on-device array dtype quantized tensors of `mode` use."""
    import jax.numpy as jnp

    validate_mode(mode)
    if mode == "int8":
        return jnp.int8
    if mode == "fp8":
        return jnp.float8_e4m3fn
    return None


def is_quantized_leaf(x) -> bool:
    """True for the {"qs": scale, "qv": values} dict a quantized leaf
    becomes (the `is_leaf` predicate tree walks use)."""
    return isinstance(x, dict) and set(x) == set(QUANT_KEYS)


def _eligible(leaf) -> bool:
    """Which leaves quantize: floating kernels/embeddings (ndim >= 2).
    Biases, norm scales, and every other 0/1-D leaf stay full-width —
    they are a rounding error of the byte budget and the f32-statistics
    convention (models/common.py) wants them exact."""
    import jax.numpy as jnp

    dtype = getattr(leaf, "dtype", None)
    return (dtype is not None and jnp.issubdtype(dtype, jnp.inexact)
            and getattr(leaf, "ndim", 0) >= 2)


def quantize_leaf(w, mode: str) -> dict:
    """One kernel → {"qs": f32 per-out-channel scale, "qv": quantized
    values}. Pure and jittable; f32 math throughout."""
    import jax.numpy as jnp

    validate_mode(mode)
    w32 = w.astype(jnp.float32)
    axes = tuple(range(w32.ndim - 1))
    bound = INT8_BOUND if mode == "int8" else FP8_BOUND
    absmax = jnp.max(jnp.abs(w32), axis=axes)
    scale = (jnp.maximum(absmax, _SCALE_FLOOR) / bound).astype(jnp.float32)
    scaled = w32 / scale
    if mode == "int8":
        q = jnp.clip(jnp.round(scaled), -INT8_BOUND, INT8_BOUND) \
            .astype(jnp.int8)
    else:
        q = scaled.astype(jnp.float8_e4m3fn)
    return {"qs": scale, "qv": q}


def dequantize_leaf(leaf):
    """{"qs", "qv"} → float32 kernel: the quantized values convert to
    float32 FIRST, then multiply by the f32 scale (the GRAPH407
    contract — never int8/fp8 → bf16 directly). Full-width leaves pass
    through untouched."""
    import jax.numpy as jnp

    if not is_quantized_leaf(leaf):
        return leaf
    return leaf["qv"].astype(jnp.float32) * leaf["qs"]


def quantize_tree(params, mode: str):
    """Quantize every eligible leaf of a param tree; `bf16` returns the
    tree UNTOUCHED (the pre-quant path, byte-identical). Pure and
    jittable — factory wraps it in one jitted program at boot so the
    full-width tree is freed leaf-by-leaf as it quantizes."""
    import jax

    validate_mode(mode)
    if mode == DEFAULT_MODE:
        return params
    return jax.tree_util.tree_map(
        lambda w: quantize_leaf(w, mode) if _eligible(w) else w, params)


def dequantize_tree(params):
    """Inverse of `quantize_tree` up to quantization error: rebuilds a
    float tree with quantized kernels dequantized to f32 (flax modules
    cast to their compute dtype at use, exactly as with f32 checkpoint
    params). The no-op on an unquantized tree, so bucket programs can
    call it unconditionally."""
    import jax

    return jax.tree_util.tree_map(dequantize_leaf, params,
                                  is_leaf=is_quantized_leaf)


def quantize_params(params, mode: str):
    """Boot-time entry point (node/factory.py): ONE jitted program
    quantizing the loaded checkpoint tree on-device — eager per-leaf
    quantizes would dispatch hundreds of ops one-by-one (the boot-cast
    rationale). No donation: an
    int8 output can never alias its f32 source, and XLA frees each
    full-width leaf when its last read (the absmax/divide) retires."""
    import jax

    validate_mode(mode)
    if mode == DEFAULT_MODE:
        return params
    return jax.jit(lambda p: quantize_tree(p, mode))(params)


def abstract_quantized(shapes, mode: str):
    """The quantized tree's abstract (ShapeDtypeStruct) form for a given
    full-width abstract tree — what trace specs feed `jax.make_jaxpr`
    so quantized-mode goldens trace without allocating weights."""
    import jax

    return jax.eval_shape(lambda p: quantize_tree(p, mode), shapes)


def quantized_dot(qx, qw, sx, sw, mode: str = "int8"):
    """Fully-quantized matmul for activation-quantized paths: int8
    operands accumulate in int32 (`preferred_element_type`), fp8
    operands in f32, and the result dequantizes by the f32 product of
    both scales — the accumulation-dtype contract GRAPH407 pins.

    The weight-only serving path dequantizes before the matmul instead
    (the checkpoint programs above); this primitive is the building
    block for activation quantization — the quantized collective's
    wire math (parallel/collectives.py) and the GRAPH407 fixtures use
    it, and a future W8A8 bucket program would too."""
    import jax.numpy as jnp
    from jax import lax

    validate_mode(mode)
    if mode == DEFAULT_MODE:
        raise ValueError("quantized_dot needs a quantized mode "
                         "(int8|fp8) — bf16 is the unquantized path")
    acc = jnp.int32 if mode == "int8" else jnp.float32
    out = lax.dot_general(qx, qw, (((qx.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=acc)
    return out.astype(jnp.float32) * (sx[..., None] * sw)
