"""The causal / sliding-window flash kernel in tier-1: `interpret=True` on
the CPU against the program's other prefill attention path,
`blockwise_attention` (the exact XLA walk, whose precision policy the
kernel states too), and against the benchmark's plain reference
`perfbench.reference.ops.attend` under the same additive mask; the rule
that chooses between kernel and walk; the block counts the `text.bucket`
span reports."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

from arbius_tpu.models.trinity import TrinityConfig, TrinityPipeline
from arbius_tpu.models.trinity import pipeline as trinity_pipeline
from arbius_tpu.ops import causal_flash
from arbius_tpu.ops.blockwise import blockwise_attention
from arbius_tpu.ops.causal_flash import causal_flash_attention
from perfbench.reference import ops as ref_ops

# per dtype: |kernel - walk|, and |kernel - exact| where exact is the
# plain float32 reference on the same (already rounded) inputs
TOL = {"float32": (2e-5, 2e-5), "bfloat16": (2e-2, 1.5e-2)}


def qkv(b, s, kv, g, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(s * 7 + g + d), 3)
    return (jax.random.normal(ks[0], (b, s, kv, g, d), dtype),
            jax.random.normal(ks[1], (b, s, kv, d), dtype),
            jax.random.normal(ks[2], (b, s, kv, d), dtype))


def f32(x):
    return np.asarray(x, dtype=np.float32)


def mask_of(s, window):
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    ok = j <= i
    if window is not None:
        ok &= j > i - window
    return ok


def exact(q, k, v, window):
    """`ops.attend` in float32 under the same mask, on [B, H, S, D] with
    each KV head repeated for its group."""
    b, s, kv, g, d = q.shape
    qh = jnp.moveaxis(q.astype(jnp.float32).reshape(b, s, kv * g, d), 1, 2)
    kh, vh = (jnp.moveaxis(jnp.repeat(x.astype(jnp.float32), g, axis=2),
                           1, 2) for x in (k, v))
    bias = jnp.where(mask_of(s, window), 0.0, -1e30)[None, None]
    out = ref_ops.attend(qh, kh, vh, mask=bias)
    return jnp.moveaxis(out, 1, 2).reshape(b, s, kv, g, d)


def check(got, q, k, v, window, dtype):
    assert got.shape == q.shape and got.dtype == q.dtype
    want = blockwise_attention(q, k, v, window=window)
    ref = exact(q, k, v, window)
    vs_walk, vs_exact = TOL[dtype]
    np.testing.assert_allclose(f32(got), f32(want), rtol=vs_walk,
                               atol=vs_walk)
    # no further from the exact answer than the walk itself is
    assert np.abs(f32(got) - f32(ref)).max() <= max(
        vs_exact, 1.5 * np.abs(f32(want) - f32(ref)).max())


# S = 1300 is a multiple of neither tile the rule gives it (128 query
# positions, 512 keys: three key blocks, the last with padded keys);
# 520 is no multiple of the key tile, 5000 is longer than the sequence
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("group", [1, 6])
@pytest.mark.parametrize("s,window", [
    (1300, None), (1300, 520), (1300, 5000), (19, 8), (200, None)])
def test_kernel_matches_the_walk_and_the_reference(s, window, group, dtype):
    q, k, v = qkv(1, s, 2, group, 16, jnp.dtype(dtype))
    got = causal_flash_attention(q, k, v, window=window, interpret=True)
    check(got, q, k, v, window, dtype)


# small tiles, so that every way through the key blocks is taken: cut
# blocks at both ends, whole trips of unmasked blocks and what they leave,
# a window shorter than a tile (no unmasked block), one longer than S,
# query tiles longer and shorter than the key tile, a batch of two
@pytest.mark.parametrize("b,s,group,d,window,tiles", [
    (1, 1100, 3, 8, None, (128, 128)),
    (1, 1100, 3, 8, 300, (128, 128)),
    (2, 1100, 6, 16, 333, (128, 256)),
    (1, 1100, 2, 16, 5000, (256, 128)),
    (1, 900, 2, 8, 40, (128, 128)),
    (1, 1300, 1, 128, 520, (128, 128)),
])
def test_every_section_of_the_walk(b, s, group, d, window, tiles):
    q, k, v = qkv(b, s, 2, group, d, jnp.float32)
    got = causal_flash._attention(q, k, v, window, *tiles, True)
    check(got, q, k, v, window, "float32")


def reached(s, window, block_q, block_k):
    """Blocks of the padded grid that hold a pair the mask leaves."""
    ok = mask_of(s, window)
    n_q, n_k = -(-s // block_q), -(-s // block_k)
    pad = np.zeros((n_q * block_q, n_k * block_k), bool)
    pad[:s, :s] = ok
    return int(pad.reshape(n_q, block_q, n_k, block_k).any(axis=(1, 3)).sum())


def test_block_counts_are_the_docstrings_and_the_masks():
    """`attn_blocks` / `attn_blocks_dense` for one KV head at the cell's
    shapes are what the module's docstring states, and what is walked is
    what the mask reaches, no block more and none less."""
    assert causal_flash._tiles(8192, 6) == (128, 512)
    assert causal_flash.walk_blocks(8192, None, 6) == (544, 1024)
    assert causal_flash.walk_blocks(8192, 4096, 6) == (432, 1024)
    doc = causal_flash.__doc__
    assert "544 of 1024" in doc and "432 of 1024" in doc
    for s, window, group in [(8192, None, 6), (8192, 4096, 6),
                             (1300, 520, 6), (1300, 520, 1),
                             (2000, 77, 3), (700, 5000, 6)]:
        walked, dense = causal_flash.walk_blocks(s, window, group)
        block_q, block_k = causal_flash._tiles(s, group)
        assert walked == reached(s, window, block_q, block_k)
        assert dense == -(-s // block_q) * -(-s // block_k)


def has_kernel(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_the_rule_keeps_tier1_and_the_graph_goldens_on_the_walk(
        monkeypatch):
    """Off the TPU no shape takes the kernel; on it (the backend's name
    patched: nothing is compiled) the kernel starts at `_KERNEL_MIN_ROWS`
    positions, over every prompt edge the tier-1 tests of the family and
    its four `goldens/graph/trinity.*` programs run."""
    sds = jax.ShapeDtypeStruct

    def args(s):
        return (sds((1, s, 2, 2, 8), jnp.bfloat16),
                sds((1, s, 2, 8), jnp.bfloat16),
                sds((1, s, 2, 8), jnp.bfloat16))

    attn = causal_flash.causal_attention
    assert not causal_flash.kernel_serves(8192)
    assert not has_kernel(attn, *args(4096))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    edges = {32, 12, 19}           # tests/test_trinity.py, test_textgen.py
    for spec in trinity_pipeline.trace_specs():
        edges.add(int(spec.bucket.split(".")[1][1:]))
    assert max(edges) < causal_flash._KERNEL_MIN_ROWS
    for s in sorted(edges) + [causal_flash._KERNEL_MIN_ROWS - 1]:
        assert not causal_flash.kernel_serves(s)
        assert not has_kernel(attn, *args(s))
    assert causal_flash.kernel_serves(causal_flash._KERNEL_MIN_ROWS)
    assert has_kernel(attn, *args(causal_flash._KERNEL_MIN_ROWS))
    assert has_kernel(lambda q, k, v: attn(q, k, v, window=4096),
                      *args(8192))


def test_pipeline_reports_what_the_kernel_serves(monkeypatch):
    """`text.bucket`'s static attributes: nothing off the TPU; on it, at
    the cell's shapes, 5 layers x 16 sequences and their blocks."""
    cfg = TrinityConfig(layers=TrinityConfig.pattern(1, 4),
                        vocab_rows=(0, 25024), experts_held=(0, 32))
    pipe = TrinityPipeline(cfg)
    assert pipe.attn_kernel(16, 8192) == (0, 0, 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pipe.attn_kernel(16, 8192) == (
        80, 16 * 8 * (4 * 432 + 544), 16 * 8 * 5 * 1024)
    tiny = TrinityPipeline(TrinityConfig.tiny(), prompt_buckets=(12,),
                           decode_buckets=(6,))
    assert tiny.attn_kernel(2, 12) == (0, 0, 0)
