"""Weights from `--seed`, made on the device in one jitted call, in the
type they are served in.

The program's own init pins nothing the benchmark may use (factory seed
0) and the reference may take nothing the program made, so the benchmark
makes the tree itself: only the *layout* (leaf names, shapes, dtypes —
the checkpoint format a node loads) is read from the program, with
`jax.eval_shape`, which computes nothing. Values follow the rules in the
configuration file's `weights.init` (first matching rule wins):

    {"match": "<regex on the leaf path>", "dist": "fan_in"|"normal"|"rows",
     "std": s, "mean": m, "fan_in_axes": [axis, ..]}

`fan_in` is N(0, gain²/fan_in) with fan_in = size / last-axis (per-head
q/k/v kernels [W,H,D] use W; `gain` defaults to 1), or the product of the
axes a rule states under `fan_in_axes` (kernels stacked [E,K,N]: [1]);
`normal` is N(mean, std²); `rows` adds N(0, std²) to a per-row constant
(`mean` is the list of row values).
Leaves that share a shape and a rule are drawn as one array and split,
so the program that makes three billion weights stays a few hundred ops.
One draw is float32 and holds at most CEILING elements: a group over it
is drawn leaf by leaf, and a single leaf over it in slices of its first
axis, each from `fold_in` of the group's key.
"""
from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np

CEILING = 2 ** 29   # float32 elements in one draw: 2 GiB of a 16 GB chip


def _path(keys) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in keys)


def _rule_for(path: str, rules: list[dict]) -> dict:
    for r in rules:
        if re.search(r["match"], path):
            return r
    raise ValueError(f"no weights.init rule matches leaf {path!r}")


def _fan_in(path: str, shape: tuple, axes=None) -> int:
    if axes is not None:
        return math.prod(shape[a] for a in axes)
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
                    / math.sqrt(_fan_in(path, leaf.shape,
                                        rule.get("fan_in_axes"))))
        elif rule["dist"] == "rows":
            spec = ("rows", tuple(rule["mean"]), float(rule["std"]))
        else:
            spec = ("normal", float(rule.get("mean", 0.0)),
                    float(rule["std"]))
        groups.setdefault((tuple(leaf.shape), str(leaf.dtype)) + spec,
                          []).append(i)
    return sorted(groups.items(), key=lambda kv: kv[1][0]), treedef, len(flat)


def _draw(key, shape: tuple, spec: tuple, dtype, lead: tuple = (),
          rows: slice = slice(None)):
    """`lead + shape` values by `spec`, drawn in float32, in `dtype`;
    `rows` says which of a leaf's rows `shape` holds."""
    dist, mean, std = spec
    x = jax.random.normal(key, lead + shape, jnp.float32) * std
    if dist == "rows":
        mean = jnp.asarray(mean, jnp.float32)[rows].reshape(
            (shape[0],) + (1,) * (len(shape) - 1))
    return (x + mean).astype(dtype)


def builder(shapes, rules: list[dict], ceiling: int = CEILING):
    """key -> the weight tree for `shapes`, to be jitted."""
    groups, treedef, n = plan(shapes, rules)

    def build(key):
        leaves = [None] * n
        for gi, ((shape, dtype, *spec), idx) in enumerate(groups):
            k = jax.random.fold_in(key, gi)
            size = math.prod(shape)
            if len(idx) * size <= ceiling:
                x = _draw(k, shape, spec, dtype, lead=(len(idx),))
                for j, i in enumerate(idx):
                    leaves[i] = x[j]
                continue
            step = max(1, ceiling // (size // shape[0]))   # rows a draw
            for j, i in enumerate(idx):
                kj = jax.random.fold_in(k, j)
                leaves[i] = jnp.concatenate([
                    _draw(jax.random.fold_in(kj, a),
                          (min(step, shape[0] - a),) + shape[1:], spec,
                          dtype, rows=slice(a, a + step))
                    for a in range(0, shape[0], step)])
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return build


def make(shapes, seed: int, rules: list[dict]):
    """The weight tree for `shapes` from `seed`: one jitted program."""
    # seeds run a little past 2**31: fold both halves so none collides
    # the "rbg" generator is the chip's own bit generator: three billion
    # draws compile and run in seconds where threefry took over a minute
    # and a half (my chip run, PR 24). Weights need no cross-platform
    # bits: the reference takes these very arrays.
    key = jax.random.fold_in(
        jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl="rbg"),
        np.uint32(seed >> 32))
    return jax.jit(builder(shapes, rules))(key)


def count(shapes) -> int:
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))
