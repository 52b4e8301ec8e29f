"""The causal / sliding-window flash kernel at the text cell's two shapes,
and the routed experts' grouped product (`ops/grouped.py`, megablox's
gmm at the tiling `ops.grouped.tiling` picks) at the text cells' decode
and prefill tiles, compiled for a described v5e chip (no chip attached):
what Mosaic refuses here would cost chip time there. The topology is
built inside a fixture, as tests/perfbench/test_flash_compiles_tpu.py
does for the unmasked kernel."""
from __future__ import annotations

import os

import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


# trinity-large-ep8 prefill, one sequence at the 8192 edge: 48 query heads
# over 8 KV heads of 128; the full layer and a window-4096 layer
@pytest.mark.parametrize("window", [None, 4096])
def test_causal_flash_kernel_compiles_at_the_cells_shapes(
        one_chip, no_persistent_cache, window):
    import jax
    import jax.numpy as jnp

    from arbius_tpu.ops.causal_flash import causal_flash_attention

    s, kv, g, d = 8192, 8, 6, 128
    q = jax.ShapeDtypeStruct((1, s, kv, g, d), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, s, kv, d), jnp.bfloat16, sharding=one_chip)
    lowered = jax.jit(
        lambda q, k, v: causal_flash_attention(q, k, v, window=window)
    ).lower(q, k, k)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert compiled.memory_analysis().output_size_in_bytes \
        == s * kv * g * d * 2


# (d, f, held experts, tokens routed at once; 8 of 256 experts a token in
# each): joyai_llm_flash's verify step, dots3_note's decode step (the
# widest contraction), a deepseek_v32 prefill chunk (256-row tiles)
@pytest.mark.parametrize("d,f,held,t", [(2048, 768, 256, 64),
                                        (5120, 1536, 32, 16),
                                        (7168, 2048, 16, 4096)])
def test_grouped_product_compiles_at_the_cells_shapes(
        one_chip, no_persistent_cache, d, f, held, t):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import megablox

    from arbius_tpu.models.deepseek_v32 import DeepSeekV32Config
    from arbius_tpu.models.trinity.model import expert_tile
    from arbius_tpu.ops import grouped

    cfg = DeepSeekV32Config.published()
    tile = expert_tile(t, cfg)
    rows = (-(-t * cfg.experts_per_token // tile) + held) * tile
    for kk, nn in ((d, f), (f, d)):
        tiling = grouped.tiling(tile, kk, nn, 2)
        lowered = jax.jit(lambda x, w, g: megablox.gmm(
            x, w, g, preferred_element_type=jnp.bfloat16, tiling=tiling)
        ).lower(jax.ShapeDtypeStruct((rows, kk), jnp.bfloat16,
                                     sharding=one_chip),
                jax.ShapeDtypeStruct((held, kk, nn), jnp.bfloat16,
                                     sharding=one_chip),
                jax.ShapeDtypeStruct((held,), jnp.int32,
                                     sharding=one_chip))
        assert "tpu_custom_call" in lowered.as_text()
        compiled = lowered.compile()
        assert compiled.memory_analysis().output_size_in_bytes \
            == rows * nn * 2


# nemotron3-super-ep4: the attention layer's prefill at the 2048 edge, 32
# query heads over 2 KV heads (16 a group: the widest stacked Q tile of
# any cell), and a verify step's latent experts (64 rows, 22 of 512 a
# token, 128 held; squared ReLU's up 1024 -> 2688 and down 2688 -> 1024)
@pytest.mark.parametrize("part", ["causal", "up", "down"])
def test_nemotron_kernels_compile_at_the_cells_shapes(
        one_chip, no_persistent_cache, part):
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import megablox

    from arbius_tpu.models.nemotron_h import NemotronHConfig
    from arbius_tpu.models.trinity.model import expert_tile
    from arbius_tpu.ops import grouped
    from arbius_tpu.ops.causal_flash import causal_flash_attention

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if part == "causal":
        s, kv, g, d = 2048, 2, 16, 128
        lowered = jax.jit(causal_flash_attention).lower(
            sds((1, s, kv, g, d)), sds((1, s, kv, d)), sds((1, s, kv, d)))
        out = s * kv * g * d * 2
    else:
        cfg = NemotronHConfig.published()
        held, t = 128, 64
        tile = expert_tile(t, cfg)
        assert tile == 8
        rows = (-(-t * cfg.experts_per_token // tile) + held) * tile
        kk, nn = ((cfg.latent, cfg.expert_ff) if part == "up"
                  else (cfg.expert_ff, cfg.latent))
        tiling = grouped.tiling(tile, kk, nn, 2)
        lowered = jax.jit(lambda x, w, gs: megablox.gmm(
            x, w, gs, preferred_element_type=jnp.bfloat16, tiling=tiling)
        ).lower(sds((rows, kk)), sds((held, kk, nn)),
                sds((held,), jnp.int32))
        out = rows * nn * 2
    assert "tpu_custom_call" in lowered.as_text()
    assert lowered.compile().memory_analysis().output_size_in_bytes == out
