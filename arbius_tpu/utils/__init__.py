"""Shared utilities: parameter checkpointing, compile-cache setup, platform forcing."""
from arbius_tpu.utils.checkpoint import (
    DEFAULT_COMPILE_CACHE_DIR,
    cast_floating,
    enable_compile_cache,
    load_params,
    save_params,
    with_cast,
)
from arbius_tpu.utils.platform import force_cpu_devices

__all__ = ["DEFAULT_COMPILE_CACHE_DIR", "cast_floating",
           "enable_compile_cache", "force_cpu_devices", "load_params",
           "save_params", "with_cast"]
