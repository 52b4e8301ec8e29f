"""Shared by the benchmark's own tests: the repo root on the path, and
jax's compile-cache settings put back after a test that ran the harness
in this process (`enable_compile_cache` changes them for good)."""
import pytest

import pb_paths  # noqa: F401 — puts the repo root on the path

@pytest.fixture
def compile_cache_restored():
    import jax

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    before = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in before.items():
        jax.config.update(k, v)
