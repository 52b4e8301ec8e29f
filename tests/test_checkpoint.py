"""Param checkpoint + compile-cache tests (SURVEY.md §5 checkpoint/resume)."""
from __future__ import annotations

import os

import jax
import numpy as np
import pytest

from arbius_tpu.utils import (
    DEFAULT_COMPILE_CACHE_DIR,
    enable_compile_cache,
    load_params,
    save_params,
)


def test_save_load_roundtrip(tmp_path):
    params = {"unet": {"conv": {"kernel": np.arange(12.0).reshape(3, 4),
                                "bias": np.zeros(4)}},
              "text": {"embed": np.ones((5, 2), np.float32)}}
    path = str(tmp_path / "ckpt")
    save_params(path, params)
    restored = load_params(path)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        params, restored)


def test_save_overwrites(tmp_path):
    path = str(tmp_path / "ckpt")
    save_params(path, {"a": np.zeros(2)})
    save_params(path, {"a": np.ones(2)})
    np.testing.assert_array_equal(np.asarray(load_params(path)["a"]),
                                  np.ones(2))


@pytest.fixture
def restore_cache_dir():
    """enable_compile_cache edits process-wide jax config; put it back."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_placed_from_outside_is_left_alone(
        monkeypatch, tmp_path, restore_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set ⇒ the code sets no directory (jax
    read the variable itself; here the config stands in for that)."""
    outside = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
    jax.config.update("jax_compilation_cache_dir", outside)
    assert enable_compile_cache() == outside
    assert jax.config.jax_compilation_cache_dir == outside
    assert not os.path.exists(outside)      # nothing created on the side
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0


def test_compile_cache_defaults_to_the_checkout_from_any_cwd(
        monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert DEFAULT_COMPILE_CACHE_DIR == want
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.path.isdir(want) and os.listdir(tmp_path) == []
    enable_compile_cache()                  # idempotent


def test_force_cpu_devices_checks_what_jax_actually_runs_on(monkeypatch):
    """jax ignores a late platform/XLA_FLAGS change without a word; the
    helper must not (conftest already forced 8 CPU devices here)."""
    from arbius_tpu.utils import force_cpu_devices

    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    force_cpu_devices(1)        # fewer than we have: fine, count untouched
    assert jax.device_count() == 8
    assert "device_count=8" in os.environ["XLA_FLAGS"]
    with pytest.raises(RuntimeError, match="before first jax use"):
        force_cpu_devices(9)


def test_fused_init_cast_matches_separate_cast():
    """init_params(dtype=) must be bit-identical to init-then-cast.

    The fused form exists for HBM peak (a separate cast program holds the
    f32 AND bf16 trees live at once — it OOMed the ~3B kandinsky tree on
    a 16 GB chip), but goldens were recorded via the two-program path, so
    the bits must not move. Covers every pipeline family's init path.
    """
    import jax.numpy as jnp

    from arbius_tpu.models.kandinsky2 import Kandinsky2Config, Kandinsky2Pipeline
    from arbius_tpu.models.rvm import RVMPipeline, RVMPipelineConfig
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.models.video import Text2VideoConfig, Text2VideoPipeline
    from arbius_tpu.utils import cast_floating

    pipes = [
        SD15Pipeline(SD15Config.tiny()),
        Kandinsky2Pipeline(Kandinsky2Config.tiny()),
        Text2VideoPipeline(Text2VideoConfig.tiny()),
        RVMPipeline(RVMPipelineConfig.tiny()),
    ]
    for pipe in pipes:
        ref = jax.jit(lambda p: cast_floating(p, "bfloat16"))(
            pipe.init_params(seed=0))
        fused = pipe.init_params(seed=0, dtype="bfloat16")
        leaves_ref = jax.tree_util.tree_leaves_with_path(ref)
        leaves_fused = jax.tree_util.tree_leaves_with_path(fused)
        assert len(leaves_ref) == len(leaves_fused)
        for (path_r, a), (path_f, b) in zip(leaves_ref, leaves_fused):
            assert path_r == path_f
            assert a.dtype == b.dtype, (type(pipe).__name__, path_r)
            if jnp.issubdtype(a.dtype, jnp.inexact):
                assert a.dtype == jnp.bfloat16, (type(pipe).__name__, path_r)
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=f"{type(pipe).__name__} {path_r}")
