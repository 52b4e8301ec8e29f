"""Flash-attention kernel tests (interpret mode on CPU; the same kernel
compiles for TPU). Oracle: the einsum reference with f32 softmax."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arbius_tpu.ops.flash import flash_attention
from arbius_tpu.ops.ring import sp_attention_reference

pytestmark = [pytest.mark.slow, pytest.mark.model]


def rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


@pytest.mark.parametrize("b,h,s,d", [
    (1, 1, 128, 128),     # exactly one tile
    (2, 3, 256, 64),      # padded head_dim
    (1, 2, 200, 40),      # ragged seq + ragged dim (SD-1.5 head shape)
    (1, 1, 384, 128),     # multi K-block loop
])
def test_flash_matches_reference(b, h, s, d):
    q, k, v = (rand((b, h, s, d), i) for i in range(3))
    got = np.asarray(flash_attention(q, k, v, interpret=True))
    want = np.asarray(sp_attention_reference(q, k, v))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_cross_attention_shape():
    """kv_len ≠ q_len (text cross-attention: 77 context tokens)."""
    q = rand((1, 2, 256, 64), 0)
    k = rand((1, 2, 77, 64), 1)
    v = rand((1, 2, 77, 64), 2)
    got = np.asarray(flash_attention(q, k, v, interpret=True))
    want = np.asarray(sp_attention_reference(q, k, v))
    assert got.shape == (1, 2, 256, 64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_flash_bf16_inputs():
    q, k, v = (rand((1, 2, 128, 64), i, jnp.bfloat16) for i in range(3))
    got = np.asarray(flash_attention(q, k, v, interpret=True),
                     dtype=np.float32)
    want = np.asarray(sp_attention_reference(q, k, v), dtype=np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_flash_extreme_logits():
    q = jnp.full((1, 1, 128, 64), 20.0)
    k = jnp.full((1, 1, 128, 64), 20.0)
    v = rand((1, 1, 128, 64), 3)
    out = np.asarray(flash_attention(q, k, v, interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(
        out, np.asarray(sp_attention_reference(q, k, v)), rtol=1e-5,
        atol=1e-5)
