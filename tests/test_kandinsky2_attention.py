"""Kandinsky 2's added-KV attention behind `ops.flash.attention` (PR 29).

Off the TPU the door sends the call to `sp_attention_reference`: the same
five operations in the same order as the einsum/softmax lines the module
held before, so the CPU determinism class (and the 3,067-second
`kandinsky2.full.cpu.bfloat16` golden) did not move — pinned here bit for
bit against those lines, put back behind the door for the comparison. On
the TPU the 48x48 level at 768x768 (2304 queries over 10 context tokens +
2304 spatial keys) takes the flash kernel: the call compiles for a
described v5e chip (no chip attached; topology inside a fixture, one
file, `on-chip-measurement` section 2) and holds no score buffer.

The rest of the family's tests (tests/test_kandinsky2.py) are marked
slow as a module; these are tier-1.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arbius_tpu.models.kandinsky2.decoder import AttnAddedKV
from arbius_tpu.ops import flash


def einsum_attention(q, k, v):
    """The lines `AttnAddedKV` held until PR 29, on its split q, k, v."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_cpu_bits_are_those_of_the_einsum_lines_it_replaces(dtype, jitted,
                                                            monkeypatch):
    dt = jnp.dtype(dtype)
    kx, kc, kp = jax.random.split(jax.random.PRNGKey(29), 3)
    x = jax.random.normal(kx, (2, 6, 6, 64), dt)
    context = jax.random.normal(kc, (2, 3, 24), dt)
    attn = AttnAddedKV(2, 32, 24, dt)
    # weights that make attention matter: the random init's are tiny
    params = jax.tree.map(lambda p: p * 4, attn.init(kp, x, context))

    def run():      # a fresh function a call, so nothing traced is reused
        fn = lambda *a: attn.apply(*a)   # noqa: E731
        return (jax.jit(fn) if jitted else fn)(params, x, context)

    got = run()
    monkeypatch.setattr(flash, "attention", einsum_attention)
    want = run()
    assert got.dtype == want.dtype == dt and got.shape == x.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    assert float(jnp.abs(got - x).max()) > 1e-2   # the branch is not idle


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("b", [8, 4], ids=["k2-cell", "mix-cell"])
def test_level_1_call_compiles_for_the_v5e_with_no_score_buffer(
        one_chip, no_persistent_cache, b):
    """4 (2) tasks x the CFG pair, 12 heads of 64, 2304 queries over 2314
    keys: as XLA's einsum this call wrote and read back f32[b,12,2304,2314]
    (2.05 GB at b=8) seven times a UNet forward."""
    q = jax.ShapeDtypeStruct((b, 12, 2304, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, 12, 2314, 64), jnp.bfloat16,
                              sharding=one_chip)
    lowered = jax.jit(flash.flash_attention).lower(q, kv, kv)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert "2304,2314]" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == b * 12 * 2304 * 64 * 2
    # the padded q, k, v and the padded output, nothing of the scores' size
    assert mem.temp_size_in_bytes < 2304 * 2314 * 4 * 12
