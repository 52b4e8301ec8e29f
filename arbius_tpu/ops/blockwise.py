"""Blockwise causal / sliding-window attention in plain XLA.

Exact softmax attention over the (query, key) pairs a causal mask
leaves — optionally only the last `window` keys of each query, its own
included — for grouped KV heads, without ever holding a whole
[Sq, Sk] score matrix: query rows are walked in blocks of `q_block`
and each block sees only the key rows its mask can reach (STATIC
slices, so every block is its own pair of matmuls and a fully masked
key block is never read). Scores and the softmax are float32; the
probabilities go back to the operands' dtype for the value product
(the zoo's f32-softmax convention, models/common.py).

This is the side of `ops.causal_flash.causal_attention`'s rule that
serves every call off the TPU and, on it, prompts under 2,048 positions
— every tier-1 shape and the `goldens/graph/trinity.*` programs — and
the exact reference the Pallas kernel of `ops/causal_flash.py` (the
other side: long prompts on a TPU) is tested and measured against. The
two share the mathematics and the precision policy and differ in the
order of the softmax's sums. The unmasked flash kernel (ops/flash.py)
knows no mask and no grouped heads; its program stays what the image
families' goldens pin.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# additive-free mask fill: large-negative f32, finite so a row can never
# turn into NaN (every causal row holds at least its own key)
_NEG = -1e30


def block_ranges(sq: int, q_block: int, window: int | None):
    """[(q0, q1, k0)]: the query rows [q0, q1) of a block and the first
    key row k0 its mask reaches; the last is q1 - 1 (causal)."""
    out = []
    for q0 in range(0, sq, q_block):
        q1 = min(q0 + q_block, sq)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        out.append((q0, q1, k0))
    return out


def blockwise_attention(q, k, v, *, window: int | None = None,
                        q_block: int = 512):
    """q[B, S, KV, G, D], k/v[B, S, KV, D] → [B, S, KV, G, D].

    Query i attends keys j with j <= i, and i - window < j where
    `window` is given. Each KV head serves its G query heads."""
    s, d = q.shape[1], q.shape[-1]
    scale = d ** -0.5
    outs = []
    for q0, q1, k0 in block_ranges(s, q_block, window):
        qb, kb, vb = q[:, q0:q1], k[:, k0:q1], v[:, k0:q1]
        logits = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                            preferred_element_type=jnp.float32) * scale
        qpos = q0 + jnp.arange(q1 - q0)[:, None]
        kpos = k0 + jnp.arange(q1 - k0)[None, :]
        ok = kpos <= qpos
        if window is not None:
            ok &= kpos > qpos - window
        logits = jnp.where(ok, logits, _NEG)
        att = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        outs.append(jnp.einsum("bkgqs,bskd->bqkgd", att, vb))
    return jnp.concatenate(outs, axis=1)
