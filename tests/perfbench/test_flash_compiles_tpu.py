"""The flash kernel at the cells' shapes, and the weight draw of a stacked
expert layer, compiled for a described v5e
chip (no chip attached): what the TPU's compiler refuses here would cost
chip time there. One file, topology inside a fixture
(`on-chip-measurement` section 2)."""
from __future__ import annotations

import os

import pytest

import pb_paths  # noqa: F401


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


@pytest.mark.parametrize("b,h,sq,sk,d", [
    (8, 8, 9216, 9216, 40),    # anythingv3 UNet level 0 self, 4 tasks x CFG
    (8, 8, 9216, 77, 40),      # ... its cross-attention over 77 tokens
    (8, 8, 2304, 2304, 80),    # level 1 self
    (4, 1, 9216, 9216, 512),   # VAE / MOVQ mid-block at 96x96 latents
])
def test_flash_kernel_compiles_at_the_cells_shapes(one_chip,
                                                   no_persistent_cache,
                                                   b, h, sq, sk, d):
    import jax
    import jax.numpy as jnp

    from arbius_tpu.ops.flash import flash_attention

    q = jax.ShapeDtypeStruct((b, h, sq, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, h, sk, d), jnp.bfloat16, sharding=one_chip)
    lowered = jax.jit(flash_attention).lower(q, kv, kv)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert compiled.memory_analysis().output_size_in_bytes \
        == b * h * sq * d * 2


def test_a_stacked_expert_layers_draw_fits_one_chip(one_chip,
                                                    no_persistent_cache):
    """Twelve kernels of [32 experts held, 3072, 3072] share a shape and a
    rule: as one float32 draw they are 14.5 GB and the chip's compiler
    refuses the program; under the ceiling they are drawn leaf by leaf
    beside their 7.2 GB in bfloat16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import weights

    sds = jax.ShapeDtypeStruct
    shapes = {f"layer_{i}": {n: {"kernel": sds((32, 3072, 3072),
                                               jnp.bfloat16)}
                             for n in ("gate", "up", "down")}
              for i in range(4)}
    rules = [{"match": "/kernel$", "dist": "fan_in", "fan_in_axes": [1]}]
    (group, idx), = weights.plan(shapes, rules)[0]
    assert len(idx) * 32 * 3072 * 3072 > 6 * weights.CEILING
    assert abs(group[4] - 3072 ** -0.5) < 1e-9       # fan-in d, not E*d
    key = jax.random.fold_in(jax.random.key(np.uint32(5), impl="rbg"),
                             np.uint32(0))
    key = sds(key.shape, key.dtype, sharding=one_chip)
    compiled = jax.jit(weights.builder(shapes, rules),
                       out_shardings=one_chip).lower(key).compile()
    mem = compiled.memory_analysis()
    assert 0 <= mem.output_size_in_bytes - 12 * 32 * 3072 * 3072 * 2 < 4096
    assert mem.temp_size_in_bytes < 4 * 2 ** 30
