"""The routed experts' two walks (docs/text-serving.md "the tile loop"):
`routed_experts` on the grouped product (`ops.grouped`, megablox's gmm in
Pallas's interpreter on the CPU) against the same call on the tile loop,
at tiny shapes — experts no row chose, a group longer than one tile,
assignments on experts not held, one token, a draft's and a verify
step's rows, a prefill's; and the rule that picks the walk from the
static tile."""
from __future__ import annotations

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arbius_tpu.models.deepseek_v32 import DeepSeekV32Config
from arbius_tpu.models.deepseek_v32 import model as dsv32
from arbius_tpu.models.dots3 import Dots3NoteConfig
from arbius_tpu.models.joyai_flash import JoyAIFlashConfig
from arbius_tpu.models.trinity import TrinityConfig
from arbius_tpu.models.trinity import model as trinity
from arbius_tpu.ops import grouped


def _experts(cfg, seed):
    """One expert layer's router and held experts, bfloat16, as every
    text family lays them out."""
    d, f = cfg.hidden, cfg.expert_ff
    shapes = {"router": {"kernel": (d, cfg.num_experts)},
              "expert_bias": (cfg.num_experts,),
              "experts": {"gate": {"kernel": (cfg.n_held, d, f)},
                          "up": {"kernel": (cfg.n_held, d, f)},
                          "down": {"kernel": (cfg.n_held, f, d)}}}
    tree = trinity.init_tree(shapes, jax.random.PRNGKey(seed))
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), tree)


def _both(monkeypatch, x, chosen, w, experts, cfg):
    """(grouped, loop) results of one `routed_experts` call."""
    loop = trinity.routed_experts(x, chosen, w, experts, cfg)
    monkeypatch.setattr(grouped, "kernel_serves", lambda *_: True)
    kernel = trinity.routed_experts(x, chosen, w, experts, cfg)
    monkeypatch.undo()
    return kernel, loop


def _close(kernel, loop):
    (y, n), (y0, n0) = kernel, loop
    assert int(n) == int(n0)
    assert y.dtype == y0.dtype == jnp.bfloat16
    # the products' float32 sums in another order, each rounded to
    # bfloat16 (8 bits of mantissa) before the float32 combine
    scale = float(jnp.abs(y0.astype(jnp.float32)).max())
    err = float(jnp.abs(y.astype(jnp.float32)
                        - y0.astype(jnp.float32)).max())
    assert err <= 2 ** -7 * max(scale, 1e-6)


# tiny configs: trinity's whole model (8 experts, 2 a token), a share of
# 16 experts holding 4..11, and the two latent families' routers
SHARE = TrinityConfig.tiny(num_experts=16, experts_held=(4, 12))


@pytest.mark.parametrize("cfg,t", [
    (TrinityConfig.tiny(), 1),        # one token: 6 of 8 experts unchosen
    (TrinityConfig.tiny(), 2),        # a draft's rows at batch 2
    (TrinityConfig.tiny(), 4),        # a verify step's: 2 positions x 2
    (TrinityConfig.tiny(), 24),       # a prefill's: 16-row tiles
    (SHARE, 1),
    (SHARE, 6),
    (SHARE, 40),                      # assignments on experts not held
])
def test_grouped_walk_matches_the_loop_on_the_router(monkeypatch, cfg, t):
    tree = _experts(cfg, seed=t)
    x = jax.random.normal(jax.random.PRNGKey(100 + t),
                          (t, cfg.hidden)).astype(jnp.bfloat16)
    chosen, w = trinity.route(x, tree, cfg)
    kernel, loop = _both(monkeypatch, x, chosen, w, tree["experts"], cfg)
    _close(kernel, loop)
    lo, hi = cfg.experts_held
    c = np.asarray(chosen)
    assert int(loop[1]) == int(((c >= lo) & (c < hi)).sum())


def test_grouped_walk_groups_longer_than_a_tile(monkeypatch):
    """A skewed load: 64 tokens all sent to experts 0 and 1 — tiles of
    32 rows, so each of the two groups is two tiles and six experts have
    none."""
    cfg = TrinityConfig.tiny()
    tree = _experts(cfg, seed=7)
    x = jax.random.normal(jax.random.PRNGKey(7),
                          (64, cfg.hidden)).astype(jnp.bfloat16)
    chosen = jnp.tile(jnp.array([[0, 1]]), (64, 1))
    w = jax.random.uniform(jax.random.PRNGKey(8), (64, 2))
    assert trinity.expert_tile(64, cfg) == 32
    kernel, loop = _both(monkeypatch, x, chosen, w, tree["experts"], cfg)
    _close(kernel, loop)
    assert int(kernel[1]) == 128


def test_grouped_walk_with_no_assignment_held(monkeypatch):
    """Every choice falls on experts another chip holds: the combine
    reads the appended zero row alone, never a row of the products."""
    cfg = SHARE
    tree = _experts(cfg, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (5, cfg.hidden)).astype(jnp.bfloat16)
    chosen = jnp.tile(jnp.array([[0, 13]]), (5, 1))
    w = jnp.ones((5, 2), jnp.float32)
    kernel, loop = _both(monkeypatch, x, chosen, w, tree["experts"], cfg)
    assert int(kernel[1]) == int(loop[1]) == 0
    assert not bool(jnp.any(kernel[0])) and not bool(jnp.any(loop[0]))


@pytest.mark.parametrize("cfg", [DeepSeekV32Config.tiny(),
                                 JoyAIFlashConfig.tiny()])
def test_grouped_walk_under_the_group_limited_router(monkeypatch, cfg):
    """The latent families' `moe`: their own router over trinity's
    `routed_experts`, a decode step's rows and a prefill block's."""
    tree = _experts(cfg, seed=11)
    for t in (3, 32):
        x = jax.random.normal(jax.random.PRNGKey(t),
                              (t, cfg.hidden)).astype(jnp.bfloat16)
        chosen, w = dsv32.route(x, tree, cfg)
        kernel, loop = _both(monkeypatch, x, chosen, w, tree["experts"],
                             cfg)
        _close(kernel, loop)


def test_the_walk_follows_the_backend_and_the_static_shape(monkeypatch):
    """On a TPU backend joyai_llm_flash's decode steps, drafts and
    prefill blocks (8- and 64-row tiles, all 256 experts reachable) take
    the grouped product; the share families' decode steps (8-row tiles,
    at most 4 to 16 experts reachable) and prefill chunks (256-row
    tiles) keep the loop; on the CPU every call keeps it."""
    joyai = JoyAIFlashConfig.published()
    # the cells' shares: (config, decode rows, prefill rows a call)
    shares = [
        (replace(TrinityConfig.published(), experts_held=(0, 32)), 16, 8192),
        (replace(DeepSeekV32Config.published(), experts_held=(0, 16)), 8,
         4096),
        (replace(Dots3NoteConfig.published(), experts_held=(0, 32)), 16,
         4096)]
    assert [trinity.expert_tile(t, joyai) for t in (64, 32, 1024)] \
        == [8, 8, 64]
    assert jax.default_backend() == "cpu"
    assert not any(trinity.grouped_walk(t, joyai) for t in (64, 32, 1024))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert all(trinity.grouped_walk(t, joyai) for t in (64, 32, 1024))
    for cfg, decode, prefill in shares:
        assert trinity.expert_tile(decode, cfg) == 8
        assert not trinity.grouped_walk(decode, cfg)
        assert not trinity.grouped_walk(prefill, cfg)
    assert grouped.kernel_serves(grouped._MAX_TILE, grouped._MIN_EXPERTS)
    assert not grouped.kernel_serves(2 * grouped._MAX_TILE, 256)
    assert not grouped.kernel_serves(8, grouped._MIN_EXPERTS - 1)


@pytest.mark.parametrize("tm,k,n,want", [
    (8, 2048, 768, (8, 2048, 768)),       # joyai_llm_flash gate / up
    (8, 768, 2048, (8, 768, 2048)),       # and down
    (8, 3072, 3072, (8, 3072, 512)),      # trinity
    (8, 7168, 2048, (8, 7168, 256)),      # deepseek_v32
    (8, 5120, 1536, (8, 5120, 384)),      # dots3_note
    (256, 7168, 2048, (256, 3584, 256)),  # a prefill chunk's tiles
    (8, 32, 16, (8, 32, 16)),             # the tiny configs: whole blocks
])
def test_tiling_fits_the_vmem_budget(tm, k, n, want):
    got = grouped.tiling(tm, k, n, 2)
    assert got == want
    _, tk, tn = got
    assert k % tk == 0 and n % tn == 0
    assert 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn \
        <= grouped._BLOCK_BYTES
