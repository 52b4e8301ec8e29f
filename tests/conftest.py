"""Test harness config.

Multi-chip behavior is tested on a virtual 8-device CPU mesh (the driver
separately dry-run-compiles the multichip path): `force_cpu_devices`
sets the platform and the device count before first jax use.
"""
import pathlib

from arbius_tpu.utils import force_cpu_devices

force_cpu_devices(8)

import time

import pytest

FIXTURES = pathlib.Path(__file__).parent / "fixtures"

# tier-1 wall budget (ROADMAP.md): the suite must finish inside the
# 870 s driver timeout; warn loudly once the 'not slow' selection
# crosses this, so headroom erosion is visible in EVERY run instead of
# surfacing as a CI timeout three PRs later
TIER1_WARN_WALL_S = 700.0


@pytest.fixture(scope="session")
def fixtures_dir() -> pathlib.Path:
    return FIXTURES


def pytest_sessionstart(session):
    session.config._arbius_wall_t0 = time.time()


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    t0 = getattr(config, "_arbius_wall_t0", None)
    if t0 is None:
        return
    wall = time.time() - t0
    markexpr = getattr(config.option, "markexpr", "") or ""
    tier1 = "not slow" in markexpr
    terminalreporter.write_line(
        f"suite wall: {wall:.1f} s"
        + (f" (tier-1 budget: warn {TIER1_WARN_WALL_S:.0f} s, "
           "driver timeout 870 s)" if tier1 else ""))
    if tier1 and wall > TIER1_WARN_WALL_S:
        terminalreporter.write_line(
            f"WARNING: tier-1 suite wall {wall:.1f} s exceeds the "
            f"{TIER1_WARN_WALL_S:.0f} s headroom line — the driver "
            "kills the run at 870 s; move tests to @pytest.mark.slow "
            "or shrink fixtures (ROADMAP.md tier-1 budget)",
            red=True, bold=True)
