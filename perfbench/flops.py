"""FLOPs and bytes of the algorithm, from shapes alone.

One solution's FLOPs are counted by walking the plain reference's
forward passes under `jax.eval_shape` with `ops.count_flops()` open
(nothing is computed): each part's matmul FLOPs at the task's shapes,
times the calls a solution makes (anythingv3: 2 text, steps x the CFG
pair through the UNet, 1 VAE; kandinsky2: 1 text, 25 x the pair through
the prior, 50 x the pair through the decoder, 1 MOVQ). Never read from
the program, `perfscope` or a compiled bucket's `cost_analysis()`.
"""
from __future__ import annotations

import functools
import json

import jax

from perfbench.reference import ops


def _abstract(params_shapes):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params_shapes)


def count_parts(reference, arch: dict, task: dict, params_shapes,
                batch: int = 1) -> dict:
    """{part: {"flops": per call, "calls": per solution,
               "attn_calls": [(b,h,sq,sk,d)],
               "masked_attn_calls": [(b,h,sq,sk,d,pairs)],
               "other": {kind: flops}}} for `batch` tasks at once."""
    fns = reference.parts(arch)
    p = _abstract(params_shapes)
    out = {}
    for part, args, calls in reference.forward_shapes(arch, task, batch):
        with ops.count_flops() as c:
            jax.eval_shape(fns[part], p, *args)
        out[part] = {"flops": c.total, "calls": calls, "dense": c.dense,
                     "conv": c.conv, "attn": c.attn,
                     "attn_calls": list(c.attn_calls),
                     "masked_attn_calls": list(c.masked_attn_calls),
                     "other": dict(c.other)}
    return out


def total(parts: dict) -> float:
    return sum(v["flops"] * v["calls"] for v in parts.values())


def solution_flops(reference, arch: dict, task: dict, params_shapes) -> float:
    return total(count_parts(reference, arch, task, params_shapes, batch=1))


def attention_floor_seconds(b, h, sq, sk, d, peaks: dict,
                            itemsize: int = 2,
                            pairs: int | None = None) -> tuple[float, str]:
    """The least time the chip could take for exact attention at these
    shapes (over the `pairs` a mask leaves, where stated), and which
    bound binds."""
    t_flops = ops.attention_flops(b, h, sq, sk, d, pairs) \
        / peaks["bf16_flops"]
    t_bytes = ops.attention_bytes(b, h, sq, sk, d, itemsize) \
        / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
