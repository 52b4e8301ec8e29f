"""Checkpoint/resume support (SURVEY.md §5).

The reference's checkpoint is its sqlite DB; model weights live inside
cog containers and reload with them. Here weights are first-class:

  - `save_params` / `load_params`: param-tree persistence via orbax
    (the converted checkpoint is written once at deployment; the node
    restores it at boot — no re-conversion, no container pulls)
  - `enable_compile_cache`: persistent XLA compilation cache, so a node
    restart (or the bench) skips the multi-minute jit of each shape
    bucket — the "compiled-graph cache keyed by (model, shape bucket)"
    the survey calls for, with the key handled by XLA's own fingerprint
"""
from __future__ import annotations

import os

import jax


# <checkout>/.jax_cache, from this file's own location: the cache path is
# part of XLA's cache key, so a directory that moves with the working
# directory (or a pid, a temp name, the time) never hits
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent XLA cache; returns the directory in effect.

    The ONE placement rule (docs/compile-cache.md): where
    `JAX_COMPILATION_CACHE_DIR` is exported, jax has already read it and
    this code sets no directory; otherwise the cache is
    DEFAULT_COMPILE_CACHE_DIR. Idempotent."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = jax.config.jax_compilation_cache_dir
    else:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        # detlint: allow[DET106] boot-time compile-cache config — node.boot()
        # runs this before any solve program compiles
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # detlint: allow[DET106] boot-time compile-cache config (see above)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # detlint: allow[DET106] boot-time compile-cache config (see above)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir


def save_params(path: str, params: dict) -> None:
    """Write a param tree with orbax (atomic directory checkpoint)."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    with ocp.PyTreeCheckpointer() as ckptr:
        ckptr.save(path, params, force=True)


def load_params(path: str) -> dict:
    """Restore a param tree saved by save_params."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    with ocp.PyTreeCheckpointer() as ckptr:
        return ckptr.restore(path)

def cast_floating(params: dict, dtype) -> dict:
    """Cast every inexact-dtype leaf of a param tree to `dtype`.

    The production weights-in-bf16 option: halves HBM weight traffic per
    denoise step (batch-1 diffusion is weight-bandwidth-bound on TPU) at
    the cost of bf16 weight precision — the same trade the reference's
    fp16 cog containers make. Integer leaves (embedding ids, stats
    counters) pass through. Determinism note: the fleet pins ONE weights
    dtype per model; goldens recorded in f32 do not transfer to bf16."""
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype)

    def cast(x):
        return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.inexact) else x

    return jax.tree_util.tree_map(cast, params)


def with_cast(init_fn, dtype):
    """Wrap a param-init closure so an optional weights cast runs INSIDE
    the same XLA program. Init always computes in f32 (identical bits to
    init-then-cast), but fused, XLA frees each f32 leaf at its convert —
    a SEPARATE cast program holds both full trees live at once, which
    OOMed the ~3B kandinsky tree on a 16 GB chip (12 GB f32 + 6 GB bf16).
    `dtype=None` returns init_fn unchanged."""
    if dtype is None:
        return init_fn
    return lambda key: cast_floating(init_fn(key), dtype)
