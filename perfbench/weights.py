"""Weights from `--seed`, made on the device in one jitted call, in the
type they are served in.

The program's own init pins nothing the benchmark may use (factory seed
0) and the reference may take nothing the program made, so the benchmark
makes the tree itself: only the *layout* (leaf names, shapes, dtypes —
the checkpoint format a node loads) is read from the program, with
`jax.eval_shape`, which computes nothing. Values follow the rules in the
configuration file's `weights.init` (first matching rule wins):

    {"match": "<regex on the leaf path>", "dist": "fan_in"|"normal"|"rows",
     "std": s, "mean": m}

`fan_in` is N(0, gain²/fan_in) with fan_in = size / last-axis (per-head
q/k/v kernels [W,H,D] use W; `gain` defaults to 1); `normal` is N(mean, std²); `rows` adds
N(0, std²) to a per-row constant (`mean` is the list of row values).
Leaves that share a shape and a rule are drawn as one array and split,
so the program that makes three billion weights stays a few hundred ops.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in keys)


def _rule_for(path: str, rules: list[dict]) -> dict:
    for r in rules:
        if re.search(r["match"], path):
            return r
    raise ValueError(f"no weights.init rule matches leaf {path!r}")


def _fan_in(path: str, shape: tuple) -> int:
    if len(shape) == 3 and re.search(r"/(query|key|value)/kernel$", path):
        return shape[0]
    return max(1, math.prod(shape) // shape[-1])


def plan(shapes, rules: list[dict]):
    """Group the tree's leaves: [(shape, dtype, rule-id, scale, [index])]."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    groups: dict[tuple, list[int]] = {}
    for i, (keys, leaf) in enumerate(flat):
        path = _path(keys)
        rule = _rule_for(path, rules)
        if rule["dist"] == "fan_in":
            spec = ("normal", 0.0, float(rule.get("gain", 1.0))
                    / math.sqrt(_fan_in(path, leaf.shape)))
        elif rule["dist"] == "rows":
            spec = ("rows", tuple(rule["mean"]), float(rule["std"]))
        else:
            spec = ("normal", float(rule.get("mean", 0.0)),
                    float(rule["std"]))
        groups.setdefault((tuple(leaf.shape), str(leaf.dtype)) + spec,
                          []).append(i)
    return sorted(groups.items(), key=lambda kv: kv[1][0]), treedef, len(flat)


def make(shapes, seed: int, rules: list[dict]):
    """The weight tree for `shapes` from `seed`: one jitted program."""
    groups, treedef, n = plan(shapes, rules)

    def build(key):
        leaves = [None] * n
        for gi, ((shape, dtype, dist, mean, std), idx) in enumerate(groups):
            k = jax.random.fold_in(key, gi)
            x = jax.random.normal(k, (len(idx),) + shape, jnp.float32) * std
            if dist == "rows":
                rows = jnp.asarray(mean, jnp.float32).reshape(
                    (shape[0],) + (1,) * (len(shape) - 1))
                x = x + rows
            else:
                x = x + mean
            x = x.astype(dtype)
            for j, i in enumerate(idx):
                leaves[i] = x[j]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    # seeds run a little past 2**31: fold both halves so none collides
    # the "rbg" generator is the chip's own bit generator: three billion
    # draws compile and run in seconds where threefry took over a minute
    # and a half (my chip run, PR 24). Weights need no cross-platform
    # bits: the reference takes these very arrays.
    key = jax.random.fold_in(
        jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl="rbg"),
        np.uint32(seed >> 32))
    return jax.jit(build)(key)


def count(shapes) -> int:
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))
