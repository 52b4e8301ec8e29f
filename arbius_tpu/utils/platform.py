"""Host-platform forcing for CPU-mesh simulation.

Tests and multi-chip dry-runs (SURVEY.md §4: "multi-chip behavior tested
with jax CPU mesh simulation") run on N virtual CPU devices. Shared by
tests/conftest.py and __graft_entry__.py so the XLA_FLAGS edit lives
in exactly one place.
"""
from __future__ import annotations

import os
import re


def force_cpu_devices(n_devices: int) -> None:
    """Force the CPU platform with at least `n_devices` virtual devices.

    Must run before first jax use: XLA_FLAGS and the platform choice are
    read once per process, and a late call is silently ignored by jax —
    so the result is checked, and a process that already holds another
    backend (or fewer CPU devices) raises RuntimeError instead of leaving
    the caller with a silently wrong device set. A process that already
    runs on CPU with at least `n_devices` devices passes (demo-mine
    under pytest).
    """
    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    opt = f"--xla_force_host_platform_device_count={n_devices}"
    have = re.search(r"--?xla_force_host_platform_device_count=(\d+)", flags)
    if have is None:
        flags = (flags + " " + opt).strip()
    elif int(have.group(1)) < n_devices:
        flags = flags.replace(have.group(0), opt)
    # detlint: allow[DET106] process-boot platform forcing — checked
    # below: a late call raises instead of being silently ignored
    os.environ["XLA_FLAGS"] = flags
    # detlint: allow[DET106] process-boot platform forcing (see above)
    os.environ["JAX_PLATFORMS"] = "cpu"
    # detlint: allow[DET106] process-boot platform forcing (see above)
    jax.config.update("jax_platforms", "cpu")
    backend, have_n = jax.default_backend(), jax.device_count()
    if backend != "cpu" or have_n < n_devices:
        raise RuntimeError(
            f"jax already runs on {backend} with {have_n} device(s); "
            f"force_cpu_devices({n_devices}) must run before first jax "
            "use in a fresh interpreter")
