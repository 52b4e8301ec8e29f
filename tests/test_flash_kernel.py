"""The flash kernel's mathematics in tier-1: `interpret=True` on CPU at
the cells' head shapes with S scaled down (tests/test_flash.py is slow,
so nothing else in tier-1 runs the kernel body). Oracle: the program's
other attention path, `sp_attention_reference`, whose precision policy
the kernel states too — operands as handed, float32 scores and
softmax, probabilities in v's type."""
from __future__ import annotations

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arbius_tpu.ops import flash
from arbius_tpu.ops.flash import flash_attention
from arbius_tpu.ops.ring import sp_attention_reference

# per dtype: |kernel - reference|, and |kernel - exact| where exact is
# the reference in float32 on the same (already rounded) inputs
TOL = {"float32": (2e-5, 2e-5), "bfloat16": (2e-2, 1.5e-2)}


def qkv(b, h, sq, sk, d, dtype):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(sq * 7 + sk + d), 3)
    return (jax.random.normal(kq, (b, h, sq, d), dtype),
            jax.random.normal(kk, (b, h, sk, d), dtype),
            jax.random.normal(kv, (b, h, sk, d), dtype))


def f32(x):
    return np.asarray(x, dtype=np.float32)


# the cells' calls (PERF.md section 4) with S cut, then shapes chosen so
# that between them every tile the shape rule can return is reached, on
# the Q side and on the K side, and every way through the K blocks
CASES = [
    # b, h, sq, sk, d, (block_q, block_k)
    (1, 2, 256, 1024, 40, (256, 1024)),   # anythingv3 UNet level 0, self
    (1, 2, 1024, 77, 40, (1024, 128)),    # ... cross: 77 keys, one masked block
    (1, 2, 256, 768, 80, (256, 768)),     # level 1, self (2304 = 3 x 768)
    (1, 2, 768, 77, 80, (768, 128)),      # level 1, cross
    (1, 1, 256, 1024, 512, (256, 1024)),  # one head of D=512: VAE, MOVQ
    (1, 1, 512, 256, 512, (512, 256)),    # ... where the accumulator binds
    (1, 1, 384, 640, 64, (384, 640)),
    (1, 1, 640, 384, 40, (640, 384)),
    (1, 1, 512, 512, 40, (512, 512)),
    (1, 1, 896, 128, 40, (896, 128)),
    (1, 1, 1024, 256, 40, (1024, 256)),
    (1, 1, 256, 896, 40, (256, 896)),
    # keys past 512 take a K tile of 512 or more (PR 29), whatever pads least
    (1, 2, 128, 1400, 40, (128, 768)),    # no loop: 1 whole block, 632 keys
    (1, 1, 200, 1100, 40, (256, 640)),    # ... 1 whole block, 460 keys
    (1, 2, 128, 5384, 40, (128, 512)),    # 3 trips of 3, 1 more, 264 keys
    (2, 1, 128, 5632, 128, (128, 512)),   # 3 trips, 2 more, nothing padded
    # Kandinsky's added-KV attention: 10 context keys before the spatial ones
    (1, 2, 256, 266, 64, (256, 384)),     # ... cut down: one masked block
    (1, 1, 384, 2314, 64, (384, 640)),    # level 1's keys: 3 whole, 394 more
]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("b,h,sq,sk,d,tiles", CASES)
def test_kernel_matches_the_reference(b, h, sq, sk, d, tiles, dtype):
    assert flash._tiles(sq, sk, d) == tiles
    q, k, v = qkv(b, h, sq, sk, d, jnp.dtype(dtype))
    got = flash_attention(q, k, v, interpret=True)
    assert got.shape == q.shape and got.dtype == q.dtype
    want = sp_attention_reference(q, k, v)
    exact = sp_attention_reference(*(x.astype(jnp.float32) for x in (q, k, v)))
    vs_ref, vs_exact = TOL[dtype]
    np.testing.assert_allclose(f32(got), f32(want), rtol=vs_ref, atol=vs_ref)
    # no further from the exact answer than the reference path itself is
    assert np.abs(f32(got) - f32(exact)).max() <= max(
        vs_exact, 1.5 * np.abs(f32(want) - f32(exact)).max())


def test_every_tile_of_the_rule_is_exercised():
    every = set(range(128, flash._MAX_TILE + 1, 128))
    assert {flash._tile(n, flash._MAX_TILE) for n in range(1, 4096)} == every
    assert {c[5][0] for c in CASES} == every    # Q tiles
    assert {c[5][1] for c in CASES} == every    # K tiles
    # the K side's floor: 512 where the keys reach it, else one block
    for n in range(1, 4096):
        t = flash._tile(n, flash._MAX_TILE, flash._MIN_K_TILE)
        assert t >= flash._MIN_K_TILE if n > flash._MIN_K_TILE \
            else t == -(-n // 128) * 128


@pytest.mark.parametrize("sq,sk,d,tiles", [
    (9216, 9216, 40, (256, 1024)),    # anythingv3 UNet level 0 at 768x768
    (9216, 77, 40, (1024, 128)),
    (2304, 2304, 80, (256, 768)),     # level 1: 2304 = 3 x 768, no padding
    (2304, 77, 80, (768, 128)),
    (9216, 9216, 512, (256, 1024)),   # VAE and MOVQ mid-block
    (4096, 4096, 40, (256, 1024)),    # the 512x512 golden's level 0
])
def test_tiles_of_the_cells_shapes_pad_nothing_but_the_77_keys(sq, sk, d,
                                                                 tiles):
    assert flash._tiles(sq, sk, d) == tiles
    assert sq % tiles[0] == 0 and (sk % tiles[1] == 0 or sk == 77)
    # each float32 work array of a program stays within its budget
    assert 4 * tiles[0] * max(tiles[1], d) <= flash._WORK_BYTES


@pytest.mark.parametrize("sq,sk,d,tiles", [
    (2304, 2314, 64, (384, 640)),     # kandinsky2 level 1 at 768x768
    (4096, 4106, 64, (256, 896)),     # ... at 1024x1024
    (1024, 1034, 64, (256, 640)),     # level 2 at 1024x1024
])
def test_tiles_of_the_added_kv_shapes_walk_few_k_blocks(sq, sk, d, tiles):
    """Ten context keys push the keys just past a multiple of 128: the
    tile that pads least is then the shortest, 19 K blocks a Q block at
    2314 keys and 3.94 ms a call on the chip against 2.48 at (384, 640),
    pads and slice included
    (PERF.md section 6, PR 29)."""
    assert flash._tiles(sq, sk, d) == tiles
    padded = -(-sk // tiles[1]) * tiles[1]
    assert sq % tiles[0] == 0 and padded // tiles[1] <= 5
    assert padded < 1.25 * sk
    assert 4 * tiles[0] * max(tiles[1], d) <= flash._WORK_BYTES


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested ones (the kernel, its loop)
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def _kernel_eqns(sq, sk, d, dtype):
    q, k, v = qkv(1, 1, sq, sk, d, jnp.dtype(dtype))
    return list(_eqns(jax.make_jaxpr(
        lambda *a: flash_attention(*a, interpret=True))(q, k, v).jaxpr))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_operands_reach_both_products_in_the_type_they_arrive_in(dtype):
    dots = [e for e in _kernel_eqns(256, 5384, 40, dtype)
            if e.primitive.name == "dot_general"]
    assert len(dots) == 10
    for e in dots:
        assert [str(x.aval.dtype) for x in e.invars] == [dtype, dtype]
        assert str(e.outvars[0].aval.dtype) == "float32"


@pytest.mark.parametrize("sk,blocks,masks,loops", [
    (1024, 1, 0, 0),   # an exact multiple: statically no mask anywhere
    (5632, 5, 0, 1),   # 11 blocks of 512: 3 in the loop's body, 2 after it
    (5384, 5, 1, 1),   # 10 whole blocks unmasked, the last one masked
    (2314, 4, 1, 0),   # too few blocks for a loop
    (77, 1, 1, 0),     # one block, masked
])
def test_only_a_block_that_can_hold_a_padded_key_is_masked(sk, blocks, masks,
                                                           loops):
    names = [e.primitive.name for e in _kernel_eqns(256, sk, 40, "bfloat16")]
    assert names.count("dot_general") == 2 * blocks
    assert names.count("iota") == masks
    assert names.count("select_n") == masks
    assert names.count("scan") + names.count("while") == loops


def test_ragged_and_exact_key_counts_agree_with_each_other():
    """600 real keys must read the same whether the kernel pads them to
    640 and masks the last block itself, or is handed 640 (an exact
    multiple, so no mask) of which every query scores the last 40
    hopelessly low."""
    q, k, v = qkv(1, 2, 256, 600, 40, jnp.float32)
    q = jnp.abs(q) + 0.1
    ragged = flash_attention(q, k, v, interpret=True)
    k2 = jnp.concatenate([k, jnp.full((1, 2, 40, 40), -100.0)], axis=2)
    v2 = jnp.concatenate([v, jnp.ones((1, 2, 40, 40), jnp.float32)], axis=2)
    assert k2.shape[2] % flash._tiles(256, 640, 40)[1] == 0
    exact = flash_attention(q, k2, v2, interpret=True)
    want = sp_attention_reference(q, k, v)
    np.testing.assert_allclose(f32(ragged), f32(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(f32(exact), f32(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("sk", [128, 77])
def test_extreme_logits_stay_finite(sk, dtype):
    dt = jnp.dtype(dtype)
    q = jnp.full((1, 1, 128, 64), 20.0, dt)
    k = jnp.full((1, 1, sk, 64), 20.0, dt)
    v = qkv(1, 1, 128, sk, 64, dt)[2]
    out = f32(flash_attention(q, k, v, interpret=True))
    assert np.isfinite(out).all()
    tol = TOL[dtype][0]
    np.testing.assert_allclose(out, f32(sp_attention_reference(q, k, v)),
                               rtol=tol, atol=tol)


def test_vmem_limit_is_stated_from_the_blocks():
    """K and V whole and double-buffered are what the limit is for: 36
    MiB at S=9216, D=512 in bf16, which the 16 MiB default refuses."""
    kv = 2 * 2 * 9216 * 512 * 2
    got = flash._vmem_bytes(256, 1024, 9216, 512, 2)
    assert kv < got < kv + 16 * 1024 * 1024
    # D=40 is held lane-padded to 128 whether or not the caller padded it
    assert flash._vmem_bytes(256, 1024, 9216, 40, 2) \
        == flash._vmem_bytes(256, 1024, 9216, 128, 2)


# -- the rule at the kernel's door (`flash.attention`) ----------------------

@pytest.mark.parametrize("sq,sk,d,kernel", [
    (9216, 9216, 40, True),     # anythingv3 level 0 at 768 x 768
    (2304, 2314, 64, True),     # Kandinsky's added-KV level 1
    (1024, 1034, 64, True),     # the first row count the kernel takes
    (1023, 1033, 64, False),
    (576, 586, 64, False),      # added-KV level 2: measured, not acted on
    (144, 154, 64, False),
])
def test_on_a_tpu_the_kernel_serves_from_1024_query_rows(sq, sk, d, kernel,
                                                         monkeypatch):
    """Traced only: nothing is lowered, so the CPU host never meets the
    Mosaic call it could not compile."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jax.ShapeDtypeStruct((2, 4, sq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 4, sk, d), jnp.bfloat16)
    names = [e.primitive.name
             for e in _eqns(jax.make_jaxpr(flash.attention)(q, kv, kv).jaxpr)]
    assert names.count("pallas_call") == int(kernel)


@pytest.mark.parametrize("value", ["einsum", "bogus"])
def test_the_environment_selects_no_path(value, monkeypatch):
    """`ARBIUS_ATTN_IMPL` chose among four paths once and an unknown
    value failed the import; the module imported afresh under it reads
    nothing, and off the TPU every call is the reference, bit for bit."""
    monkeypatch.setenv("ARBIUS_ATTN_IMPL", value)
    spec = importlib.util.spec_from_file_location("flash_afresh",
                                                  flash.__file__)
    afresh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(afresh)
    q, k, v = qkv(1, 2, 1024, 1034, 64, jnp.bfloat16)
    assert np.array_equal(f32(afresh.attention(q, k, v)),
                          f32(sp_attention_reference(q, k, v)))
