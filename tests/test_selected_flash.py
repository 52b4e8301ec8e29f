"""The flash kernel under a selection's mask in tier-1: `interpret=True` on
the CPU against the program's other prefill attention path, `selected_walk`
(the exact XLA walk, whose precision policy the kernel states too), and
against a plain dense softmax in float32 under the same mask; the rule that
chooses between kernel and walk; the block counts the `text.bucket` span
reports."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

from arbius_tpu.models.deepseek_v32 import (
    DeepSeekV32Config,
    DeepSeekV32Pipeline,
)
from arbius_tpu.models.deepseek_v32 import model as dsv32
from arbius_tpu.models.deepseek_v32 import pipeline as dsv32_pipeline
from arbius_tpu.ops import selected_flash

# per dtype: |kernel - walk|, and |kernel - exact| where exact is the
# plain float32 softmax on the same (already rounded) inputs — the
# tolerances tests/test_causal_flash.py holds its pair to
TOL = {"float32": (2e-5, 2e-5), "bfloat16": (2e-2, 1.5e-2)}
# several heads, the query/key split into a per-head part and a shared
# rotary part, the value narrower than the key
H, DN, DR, DV = 4, 16, 8, 8
SCALE = 0.3


def f32(x):
    return np.asarray(x, dtype=np.float32)


def qkv(p, rows, dtype):
    ks = jax.random.split(jax.random.PRNGKey(p + rows), 4)
    return (jax.random.normal(ks[0], (rows, H, DN), dtype),
            jax.random.normal(ks[1], (rows, H, DR), dtype),
            jax.random.normal(ks[2], (p, H * (DN + DV)), dtype),
            jax.random.normal(ks[3], (p, DR), dtype))


def selection(p, rows, i, k):
    """What the program's own selection keeps of random index scores:
    `k` keys a row, among them keys past the row where it has fewer
    than `k` behind it (the walk and the rule mask those)."""
    qpos = i * rows + np.arange(rows)[:, None]
    sc = jax.random.normal(jax.random.PRNGKey(k), (rows, p))
    return dsv32.select_topk(
        jnp.where(np.arange(p)[None] <= qpos, sc, -jnp.inf), k)


def exact(q_nope, q_pe, kv, k_pe, ok):
    kv = f32(kv).reshape(kv.shape[0], H, DN + DV)
    s = np.einsum("qhd,khd->hqk", f32(q_nope), kv[..., :DN]) \
        + np.einsum("qhd,kd->hqk", f32(q_pe), f32(k_pe))
    s = np.where(ok[None], s * SCALE, -np.inf)
    w = np.exp(s - s.max(axis=-1, keepdims=True))
    w /= w.sum(axis=-1, keepdims=True)
    out = np.einsum("hqk,khd->qhd", w, kv[..., DN:])
    return out.reshape(out.shape[0], H * DV)


def check(p, rows, i, keep, dtype, tiles=None, spoil_past_diagonal=False):
    """The kernel, on query block `i`, against the walk and the exact
    answer; with `tiles` at those (block_q, block_k, group), else at the
    rule's own."""
    dtype = jnp.dtype(dtype)
    q_nope, q_pe, kv, k_pe = qkv(p, rows, dtype)
    r = jnp.arange(rows)
    qpos = i * rows + r[:, None]
    want = selected_flash.selected_walk(
        q_nope, q_pe, kv.reshape(p, H, DN + DV), k_pe, keep, i, r, qpos,
        scale=SCALE)
    ok = keep & (jnp.arange(p)[None, :] <= qpos)
    assert bool(ok.any(axis=1).all())      # every row keeps a key
    ref = exact(q_nope, q_pe, kv, k_pe, np.asarray(ok))
    if spoil_past_diagonal:
        # rows of keys and values that no row of this block may see: a
        # kernel that multiplied them would return NaN
        past = (jnp.arange(p) >= (i + 1) * rows)[:, None]
        kv = jnp.where(past, jnp.nan, kv)
        k_pe = jnp.where(past, jnp.nan, k_pe)
    if tiles is None:
        got = selected_flash.selected_flash_attention(
            q_nope, q_pe, kv, k_pe, ok, i * rows, scale=SCALE,
            interpret=True)
    else:
        got = selected_flash._attention(
            q_nope.reshape(rows, -1), q_pe.reshape(rows, -1), kv, k_pe, ok,
            i * rows, H, DN, SCALE, *tiles, True)
    assert got.shape == (rows, H * DV) and got.dtype == dtype
    vs_walk, vs_exact = TOL[dtype.name]
    np.testing.assert_allclose(f32(got), f32(want), rtol=vs_walk,
                               atol=vs_walk)
    # no further from the exact answer than the walk itself is
    assert np.abs(f32(got) - ref).max() <= max(
        vs_exact, 1.5 * np.abs(f32(want) - ref).max())


# P = 512 is four key blocks of 128 and the call 128 query rows, two
# query blocks of 64: (a) a selection that bites, (b) one that keeps every
# key (P <= index_topk), (d) a query block in the middle of the sequence,
# whose walk stops at its diagonal, and the first and the last
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("i,k", [(3, 100), (3, 512), (1, 100), (0, 30),
                                 (2, 512)])
def test_kernel_matches_the_walk_and_the_exact_softmax(i, k, dtype):
    keep = selection(512, 128, i, k)
    check(512, 128, i, keep, dtype, tiles=(64, 128, 2),
          spoil_past_diagonal=True)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_rows_that_keep_nothing_in_the_blocks_they_meet_first(dtype):
    """(c) The trap the causal kernel does not have: no row keeps its own
    key, none keeps a key of the first key block, and some keep nothing
    in the second either — a masked pair has to weigh exactly 0 while the
    running max is still the fill."""
    p, rows, i = 512, 128, 3
    keys = np.arange(p)[None, :]
    row = np.arange(rows)[:, None]
    keep = (keys >= 200 + row) & (keys < 230 + row)
    assert not keep[:, :128].any() and not keep[60:, 128:256].any()
    qpos = i * rows + row
    assert not keep[row[:, 0], qpos[:, 0]].any()
    check(p, rows, i, jnp.asarray(keep), dtype, tiles=(64, 128, 2))


# the rule's own tiles on shapes they do not divide: 1,300 keys are three
# key blocks of 512 with 236 padded keys, 200 query rows one block of 256
# with 56 padded rows; one head a program, and all four
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("p,rows,i,k,tiles", [
    (1300, 100, 12, 300, None), (1300, 100, 4, 2048, None),
    (1000, 200, 3, 64, (256, 512, 1)), (1000, 200, 4, 64, (128, 256, 4))])
def test_shapes_the_tiles_do_not_divide(p, rows, i, k, tiles, dtype):
    check(p, rows, i, selection(p, rows, i, k), dtype, tiles=tiles)


def test_block_counts_are_the_docstrings_and_the_diagonals():
    """`attn_blocks` / `attn_blocks_dense` at the cell's shapes are what
    the module's docstring states, and what is walked is what the causal
    mask reaches, no block more and none less."""
    assert selected_flash._tiles(512, 16384) == (512, 512)
    assert selected_flash._group(128) == 8
    assert selected_flash.walk_blocks(16384, 512, 128) \
        == (16 * 528, 16 * 1024)
    doc = selected_flash.__doc__
    assert "528 of 1024" in doc and "all 16\ngroups" in doc
    for p, rows, heads in [(16384, 512, 128), (4096, 512, 128),
                           (2048, 256, 6), (12000, 500, 128)]:
        block_q, block_k = selected_flash._tiles(rows, p)
        n_q, n_k = -(-rows // block_q), -(-p // block_k)
        reached = 0
        for start in range(0, p, rows):
            ok = np.zeros((n_q * block_q, n_k * block_k), bool)
            ok[:rows, :p] = np.arange(p)[None] \
                <= start + np.arange(rows)[:, None]
            # a padded query row walks as far as the call's last real one
            ok[rows:] = ok[rows - 1]
            reached += int(ok.reshape(n_q, block_q, n_k, block_k)
                           .any(axis=(1, 3)).sum())
        groups = heads // selected_flash._group(heads)
        assert selected_flash.walk_blocks(p, rows, heads) \
            == (groups * reached, groups * (p // rows) * n_q * n_k)


def has_kernel(fn, *args):
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def test_the_rule_keeps_tier1_and_the_graph_goldens_on_the_walk(
        monkeypatch):
    """Off the TPU no shape takes the kernel; on it (the backend's name
    patched: nothing is compiled) the kernel starts at `_KERNEL_MIN_ROWS`
    prompt positions, over every prompt edge the tier-1 tests of the
    family and its four `goldens/graph/deepseek_v32.*` programs run, and
    serves no head whose columns are not whole lanes."""
    sds = jax.ShapeDtypeStruct

    def attn(p, dn=128, dv=128, heads=2, rows=8):
        def fn(kv, q_nope, q_pe, k_pe, keep):
            r = jnp.arange(rows)
            return selected_flash.selected_attention(
                kv, heads, dn, scale=SCALE)(
                    q_nope, q_pe, k_pe, keep, 1, r, rows + r[:, None])
        return fn, (sds((p, heads * (dn + dv)), jnp.bfloat16),
                    sds((rows, heads, dn), jnp.bfloat16),
                    sds((rows, heads, 64), jnp.bfloat16),
                    sds((p, 64), jnp.bfloat16), sds((rows, p), jnp.bool_))

    def takes_kernel(p, **kw):
        fn, args = attn(p, **kw)
        return has_kernel(fn, *args)

    floor = selected_flash._KERNEL_MIN_ROWS
    assert not selected_flash.kernel_serves(16384, 128, 128)
    assert not takes_kernel(16384)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    edges = {12, 32}               # tests/test_deepseek_v32.py
    for spec in dsv32_pipeline.trace_specs():
        edges.add(int(spec.bucket.split(".")[1][1:]))
    assert max(edges) < floor
    for p in sorted(edges) + [floor - 8]:
        assert not selected_flash.kernel_serves(p, 128, 128)
        assert not takes_kernel(p)
    assert selected_flash.kernel_serves(floor, 128, 128)
    assert takes_kernel(floor) and takes_kernel(16384)
    assert not selected_flash.kernel_serves(16384, 8, 8)
    assert not takes_kernel(16384, dn=8, dv=8)
    assert not takes_kernel(16384, dn=128, dv=64)


def test_pipeline_reports_what_the_kernel_serves(monkeypatch):
    """`text.bucket`'s static attributes: nothing off the TPU; on it, at
    the cell's shapes, 32 calls a layer a sequence and their blocks."""
    cfg = DeepSeekV32Config(layers=("dense",) + ("moe",) * 4,
                            experts_held=(0, 16), vocab_rows=(0, 16160))
    pipe = DeepSeekV32Pipeline(cfg)
    assert pipe.attn_kernel(8, 16384) == (0, 0, 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pipe.attn_kernel(8, 16384) == (
        8 * 5 * 32, 8 * 5 * 16 * 528, 8 * 5 * 16 * 1024)
    attrs = pipe.bucket_attrs(8, 16384, 256)
    assert (attrs["attn_kernel_calls"], attrs["attn_blocks"],
            attrs["attn_blocks_dense"]) == pipe.attn_kernel(8, 16384)
    tiny = DeepSeekV32Pipeline(DeepSeekV32Config.tiny(),
                               prompt_buckets=(12,), decode_buckets=(6,))
    assert tiny.attn_kernel(2, 12) == (0, 0, 0)
