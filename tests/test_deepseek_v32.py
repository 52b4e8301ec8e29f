"""deepseek_v32 tier-1 suite (docs/text-serving.md): the DeepSeek-V3.2-Exp
family at a tiny size on the CPU against the benchmark's plain float32
reference (perfbench/reference/deepseek_v32.py, which imports nothing of
the program) on seeded weights, with `index_topk` (4) smaller than the
context so that the selection bites in prefill and in every decode step:
prefill then decode through the latent cache against one full forward
pass, the latent form against the per-head form, the selected set
against `lax.top_k`'s, group-limited routing against a plain rendering,
the YaRN table against closed-form values, the shares' routed parts
adding up to the uncut layer, and greedy CIDs through a real MinerNode."""
from __future__ import annotations

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

from arbius_tpu.models.deepseek_v32 import (
    DeepSeekV32Config,
    DeepSeekV32Pipeline,
)
from arbius_tpu.models.deepseek_v32 import model as dsv32
from arbius_tpu.models.trinity.model import expert_tile, swiglu
from arbius_tpu.node.config import ConfigError, load_config
from arbius_tpu.node.solver import TextGenRunner
from perfbench.reference import deepseek_v32 as reference

P, T = 12, 6      # 17 positions against an index_topk of 4


def _params(cfg, seed=0, dtype=None):
    p = dsv32.init_params(cfg, jax.random.PRNGKey(seed))
    # gains, biases and the router's bias away from their neutral init,
    # so that a gain or a bias left out cannot hide
    flat, treedef = jax.tree_util.tree_flatten_with_path(p)
    out = []
    for i, (path, x) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        if name in ("scale", "bias", "expert_bias"):
            x = x + 0.1 * jax.random.normal(k, x.shape)
        if name == "embedding":
            x = x * 50.0      # N(0, 1): the token leads the stream
        out.append(x.astype(dtype) if dtype else x)
    return jax.tree_util.tree_unflatten(treedef, out)


def _arch(cfg):
    return {"model": {k: list(v) if isinstance(v, tuple) else v
                      for k, v in dataclasses.asdict(cfg).items()},
            "prompt_buckets": [P], "decode_buckets": [T], "top_k": 4,
            "tokenizer": {"kind": "byte", "bos_id": 257, "eos_id": 258}}


def _program_logits(cfg, params, ids):
    """Teacher-forced through the program's own split: prefill on the
    first P ids, then one decode step an id, each through the caches."""
    p = ids.shape[1] - T + 1
    logits0, carry = dsv32.prefill(params, ids[:, :p], p + T, cfg)
    rows = [logits0]
    for i in range(1, T):
        lg, carry = dsv32.decode(params, ids[:, p + i - 1], carry,
                                 jnp.int32(p + i - 1), cfg)
        rows.append(lg)
    return jnp.stack(rows, axis=1), carry


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 4 rows in the program's prefill attention (3 to the
    prompt; its FFN one chunk of the 12) and in the reference (row blocks
    of 4, coarse blocks of 8, head groups of 2), so that every loop over
    attention blocks runs more than once."""
    monkeypatch.setattr(dsv32, "_SCORE_BYTES", 4 * 4 * 4 * 4)
    monkeypatch.setattr(reference, "ROW_BLOCK", 4)
    monkeypatch.setattr(reference, "COARSE", 2)
    monkeypatch.setattr(reference, "HEAD_GROUP", 2)


@pytest.mark.parametrize("dtype,tol,held,layers", [
    # float32 program against the float32 reference: only the order of
    # sums differs (blocks, the running softmax, the latent form in
    # decode, grouped tiles) — 1e-4 of logits whose spread is ~1; the
    # whole model, and a share of its experts
    ("float32", 1e-4, (0, 16), None),
    ("float32", 1e-4, (4, 8), None),
    # bfloat16 as served, two layers deep: a selection of 4 keys of 17 a
    # rounding apart, or a router's choice, moves a logit by tenths here
    # (at the published sizes one key in 2,048 does not), so this case
    # keeps every key and bounds the rounding of the stream alone
    ("bfloat16", 0.25, (0, 16), ("dense", "moe")),
])
def test_prefill_then_decode_through_the_latent_cache_matches_full_forward(
        dtype, tol, held, layers, small_blocks):
    over = {} if layers is None else {"layers": layers, "index_topk": 64}
    cfg = DeepSeekV32Config.tiny(dtype=dtype, experts_held=held, **over)
    params = _params(cfg, dtype=dtype)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + T - 1), 0, 256)
    assert dsv32._block(P, cfg.heads) == 4
    got, (caches, stats) = jax.jit(
        lambda p, i: _program_logits(cfg, p, i))(params, ids)
    want = reference.forward(params, ids, jnp.zeros((T,), jnp.int32),
                             _arch(cfg)["model"])
    assert got.shape == want.shape == (2, T, cfg.n_vocab)
    assert float(jnp.abs(got - want).max()) < tol
    # the carry: a latent row and an indexer key a position a layer, and
    # no per-head row anywhere
    assert len(caches) == len(cfg.layers)
    for lat, k_i in caches:
        assert lat.shape == (2, P + T, cfg.cache_width)
        assert k_i.shape == (2, P + T, cfg.index_head_dim)
    assert cfg.cache_width == cfg.kv_lora_rank + cfg.qk_rope_head_dim
    n_moe = dsv32.n_moe(cfg)
    made = 2 * (P + T - 1) * cfg.experts_per_token * n_moe
    assert int(stats[0]) == made
    assert int(stats[1]) == made if held == (0, 16) \
        else 0 < int(stats[1]) < made


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 0.25)])
def test_prefill_through_the_kernel_gives_the_walks_logits_and_caches(
        dtype, tol, small_blocks, monkeypatch):
    """The other side of `ops.selected_flash.selected_attention`'s rule,
    forced here (`interpret=True`: the TPU's path on the CPU) at the tiny
    config with the selection biting and three query blocks to the
    prompt: the same logits and the same caches as the walk's, to the
    order of the softmax's sums."""
    from arbius_tpu.ops import selected_flash

    over = {} if dtype == "float32" else {"layers": ("dense", "moe"),
                                          "index_topk": 64}
    cfg = DeepSeekV32Config.tiny(dtype=dtype, **over)
    params = _params(cfg, dtype=dtype)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, P), 0, 256)
    run = lambda: jax.jit(
        lambda p, i: dsv32.prefill(p, i, P + T, cfg))(params, ids)
    want, (caches, stats) = run()
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda p, i: dsv32.prefill(p, i, P + T, cfg))(params, ids))
    kernel = selected_flash.selected_flash_attention
    calls = []

    def forced(*a, **kw):
        calls.append(a[0].shape)
        return kernel(*a, **kw, interpret=True)

    monkeypatch.setattr(selected_flash, "kernel_serves", lambda *a: True)
    monkeypatch.setattr(selected_flash, "selected_flash_attention", forced)
    got, (caches_k, stats_k) = run()
    # one call a query block of 4 rows, traced once a layer
    assert calls == [(4, cfg.heads, cfg.qk_nope_head_dim)] * len(cfg.layers)
    assert float(jnp.abs(got - want).max()) < tol
    for (lat, k_i), (lat_k, k_i_k) in zip(caches, caches_k):
        assert float(jnp.abs(lat.astype(jnp.float32)
                             - lat_k.astype(jnp.float32)).max()) < tol
        assert float(jnp.abs(k_i.astype(jnp.float32)
                             - k_i_k.astype(jnp.float32)).max()) < tol
    assert int(stats_k[0]) == int(stats[0])


@pytest.mark.parametrize("dtype,tol,held", [("float32", 1e-5, (4, 12)),
                                            ("bfloat16", 0.25, (0, 16))])
def test_ffn_chunks_give_the_block_wise_logits_caches_and_held_count(
        dtype, tol, held, small_blocks, monkeypatch):
    """Prefill's FFN pass over chunks of 8 rows (`_ffn_rows` through its
    own rule, its byte bound cut to 8 rows' temporaries) against the
    block-wise form, the FFN on each 4-row attention block (the bound at
    0: no chunk fits, `_block`'s rows): 24 prompt rows, 6 attention
    blocks, 3 FFN chunks; the dense layer and every expert layer go
    through the chunks."""
    cfg = DeepSeekV32Config.tiny(dtype=dtype, experts_held=held)
    params = _params(cfg, dtype=dtype)
    p = 24
    ids = jax.random.randint(jax.random.PRNGKey(6), (2, p), 0, 256)
    ffn = dsv32._ffn
    seen = []

    def traced(x, lp, kind, c):
        seen.append((kind, x.shape))
        return ffn(x, lp, kind, c)

    monkeypatch.setattr(dsv32, "_ffn", traced)

    def run(ffn_bytes):
        monkeypatch.setattr(dsv32, "_FFN_BYTES", ffn_bytes)
        seen.clear()
        out = jax.jit(lambda q, i: dsv32.prefill(q, i, p + T, cfg))(
            params, ids)
        return out, list(seen)

    assert dsv32._block(p, cfg.heads) == 4
    (want, (caches, stats)), blocks = run(0)
    assert dsv32._ffn_rows(p, cfg) == 4
    (got, (caches_c, stats_c)), chunks = run(dsv32._routed_bytes(8, cfg))
    assert dsv32._ffn_rows(p, cfg) == 8
    assert blocks == [(k, (4, cfg.hidden)) for k in cfg.layers]
    assert chunks == [(k, (8, cfg.hidden)) for k in cfg.layers]
    assert cfg.layers[0] == "dense" and "moe" in cfg.layers
    assert float(jnp.abs(got - want).max()) < tol
    for (lat, k_i), (lat_c, k_i_c) in zip(caches, caches_c):
        assert float(jnp.abs(lat.astype(jnp.float32)
                             - lat_c.astype(jnp.float32)).max()) < tol
        assert float(jnp.abs(k_i.astype(jnp.float32)
                             - k_i_c.astype(jnp.float32)).max()) < tol
    assert int(stats_c[0]) == int(stats[0]) == 2 * p * 2 * dsv32.n_moe(cfg)
    assert int(stats_c[1]) == int(stats[1])
    assert held == (0, 16) or 0 < int(stats[1]) < int(stats[0])


def test_the_selection_bites_and_keeps_everything_up_to_index_topk():
    """With index_topk 4 the logits differ from the dense model's from
    the fifth position on, and equal them while t + 1 <= index_topk."""
    cfg = DeepSeekV32Config.tiny(dtype="float32")
    dense = dataclasses.replace(cfg, index_topk=64)
    params = _params(cfg)
    def pre(c, n):
        return jax.jit(lambda p, i: dsv32.prefill(p, i, n + T, c))

    ids = jax.random.randint(jax.random.PRNGKey(9), (1, 4), 0, 256)
    a, _ = pre(cfg, 4)(params, ids)
    b, _ = pre(dense, 4)(params, ids)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ids = jax.random.randint(jax.random.PRNGKey(9), (1, P), 0, 256)
    a, ca = pre(cfg, P)(params, ids)
    b, cb = pre(dense, P)(params, ids)
    assert float(jnp.abs(a - b).max()) > 1e-3
    # layer 0's caches do not depend on the selection, deeper ones do
    np.testing.assert_array_equal(np.asarray(ca[0][0][0]),
                                  np.asarray(cb[0][0][0]))
    # and in decode: the same carry, one step, with and without
    tok = jnp.array([7])
    a, _ = dsv32.decode(params, tok, ca, jnp.int32(P), cfg)
    b, _ = dsv32.decode(params, tok, ca, jnp.int32(P), dense)
    assert float(jnp.abs(a - b).max()) > 1e-3


@pytest.mark.parametrize("k", [1, 4, 9, 39, 40, 50])
def test_select_topk_returns_the_set_lax_top_k_returns(k):
    """Ties to the lower position, -inf rows, signed zeros; everything
    kept when the row is no longer than k."""
    x = jax.random.normal(jax.random.PRNGKey(3), (6, 40))
    x = x.at[:, 3].set(x[:, 7])                  # a tie in every row
    x = x.at[0, :10].set(1.5)                    # ten equal leaders
    x = x.at[1, 5:].set(-jnp.inf)                # fewer finite than k
    x = x.at[2, ::2].set(0.0).at[2, 1::4].set(-0.0)
    x = x.at[3].set(-jnp.abs(x[3]))              # all negative
    x = x.at[4].set(jnp.round(x[4] * 2) / 2)     # many ties
    got = np.asarray(jax.jit(lambda s: dsv32.select_topk(s, k))(x))
    _, idx = jax.lax.top_k(x, min(k, 40))
    want = np.zeros((6, 40), bool)
    np.put_along_axis(want, np.asarray(idx), True, axis=1)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(axis=1) == min(k, 40)).all()


def test_latent_form_equals_per_head_form():
    """Decode's attention on the cache (W_UK folded into the query, W_UV
    after the weighted sum) against keys and values expanded per head
    from the same latents, under the same selection (float32: 1e-5)."""
    cfg = DeepSeekV32Config.tiny(dtype="float32")
    ap = _params(cfg)["layer_1"]["attn"]
    b, t = 3, 11
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q_nope = jax.random.normal(ks[0], (b, cfg.heads, cfg.qk_nope_head_dim))
    q_pe = jax.random.normal(ks[1], (b, cfg.heads, cfg.qk_rope_head_dim))
    lat = jax.random.normal(ks[2], (b, t, cfg.cache_width))
    keep = jax.random.bernoulli(ks[3], 0.5, (b, t)).at[:, 0].set(True)
    got = dsv32._decode_attention(q_nope, q_pe, lat, keep, ap, cfg)
    c, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = (lat[..., :c] @ ap["wkv_b"]["kernel"]).reshape(
        b, t, cfg.heads, dn + cfg.v_head_dim)
    s = (jnp.einsum("bhd,bthd->bht", q_nope, kv[..., :dn])
         + jnp.einsum("bhd,btd->bht", q_pe, lat[..., c:])) \
        * cfg.softmax_scale
    att = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bht,bthd->bhd", att, kv[..., dn:])
    want = o.reshape(b, -1) @ ap["wo"]["kernel"]
    assert float(jnp.abs(got - want).max()) < 1e-5


def _plain_route(x, p, cfg):
    """Group-limited routing, a token at a time, in numpy."""
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64)
                          @ np.asarray(p["router"]["kernel"], np.float64))))
    b = s + np.asarray(p["expert_bias"], np.float64)
    per = cfg.num_experts // cfg.n_group
    out = []
    for row_s, row_b in zip(s, b):
        score = [np.sort(row_b[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(cfg.n_group)]
        groups = sorted(range(cfg.n_group),
                        key=lambda g: (-score[g], g))[:cfg.topk_group]
        allowed = [e for g in sorted(groups)
                   for e in range(g * per, (g + 1) * per)]
        chosen = sorted(allowed, key=lambda e: (-row_b[e], e))[
            :cfg.experts_per_token]
        w = row_s[chosen] / row_s[chosen].sum() * cfg.route_scale
        out.append((groups, chosen, w))
    return out


def test_group_limited_routing_against_a_plain_rendering():
    cfg = DeepSeekV32Config.tiny(dtype="float32")
    p = _params(cfg)["layer_2"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.hidden))
    chosen, w = dsv32.route(x, p, cfg)
    per = cfg.num_experts // cfg.n_group
    for i, (groups, want, want_w) in enumerate(_plain_route(x, p, cfg)):
        assert chosen[i].tolist() == want
        np.testing.assert_allclose(w[i], want_w, rtol=1e-5)
        # only kept groups, and no more of them than topk_group
        assert {e // per for e in chosen[i].tolist()} <= set(groups)
    np.testing.assert_allclose(w.sum(axis=-1), cfg.route_scale, rtol=1e-5)
    # the reference's dense weights say the same
    dense_w = reference.route(x, p, _arch(cfg)["model"])
    want = np.zeros((40, cfg.num_experts), np.float32)
    np.put_along_axis(want, np.asarray(chosen), np.asarray(w), axis=1)
    np.testing.assert_allclose(dense_w, want, atol=1e-6)


def test_router_ties_go_to_the_lower_group_and_the_lower_expert():
    cfg = DeepSeekV32Config.tiny(dtype="float32")
    p = {"router": {"kernel": jnp.zeros((cfg.hidden, cfg.num_experts))},
         "expert_bias": jnp.zeros((cfg.num_experts,))}
    chosen, w = dsv32.route(jnp.ones((3, cfg.hidden)), p, cfg)
    assert chosen.tolist() == [[0, 1]] * 3       # all scores 0.5
    np.testing.assert_allclose(w, cfg.route_scale / 2, rtol=1e-6)
    # a bias lifts group 3 and then group 2 over the tie: experts of the
    # kept groups only, though expert 0's own score ties with theirs
    bias = jnp.zeros((cfg.num_experts,)).at[12:16].set(0.2).at[8:12].set(0.1)
    chosen, w = dsv32.route(jnp.ones((1, cfg.hidden)),
                            {**p, "expert_bias": bias}, cfg)
    assert chosen.tolist() == [[12, 13]]
    # the weights are the scores WITHOUT the bias
    np.testing.assert_allclose(w, cfg.route_scale / 2, rtol=1e-6)


def test_yarn_table_and_softmax_scale_against_closed_form_values():
    cfg = DeepSeekV32Config.published()
    f = dsv32.yarn_freqs(cfg)
    base = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert f.shape == (32,)
    # pairs 0-10 turn more than 32 times in 4,096 positions: kept; pairs
    # 23-31 less than once: slowed 40 times; a linear ramp between
    np.testing.assert_allclose(f[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    for i in (11, 16, 22):
        ramp = (i - 10) / 13
        np.testing.assert_allclose(
            f[i], base[i] / 40 * ramp + base[i] * (1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(f[0], 1.0)
    np.testing.assert_allclose(f[31], 10000.0 ** (-62 / 64) / 40, rtol=1e-6)
    m = 0.1 * math.log(40.0) + 1.0
    assert m == pytest.approx(1.36889, abs=1e-5)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    # the reference's table is its own code and the same numbers
    np.testing.assert_array_equal(
        f, reference.yarn_freqs(_arch(cfg)["model"]))
    assert reference.softmax_scale(_arch(cfg)["model"]) == cfg.softmax_scale
    # adjacent pairs against halves: the same angles, another pairing
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 64))
    pos = jnp.arange(5) * 1000
    a = dsv32.rope_pairs(x, pos, cfg)
    b = dsv32.rope_halves(
        jnp.concatenate([x[:, 0::2], x[:, 1::2]], axis=-1), pos, cfg)
    np.testing.assert_allclose(a[:, 0::2], b[:, :32], atol=1e-5)
    np.testing.assert_allclose(a[:, 1::2], b[:, 32:], atol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(a, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_four_shares_add_up_to_the_uncut_layer():
    """16 tiny experts in 4 groups over 4 chips, a group a chip: the
    shares' routed parts, with the shared expert counted once, add up to
    what the uncut layer gives — in the program and in the reference
    (float32: 1e-5, the order of a four-term sum)."""
    whole = DeepSeekV32Config.tiny(dtype="float32")
    params = _params(whole)
    lp = params["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, whole.hidden))
    full, n_full = dsv32.moe(x, lp, whole)
    shared = swiglu(x, lp["shared"])
    total, held_sum = shared, 0
    ref_total = jnp.zeros_like(x)
    for e in range(0, 16, 4):
        cfg = DeepSeekV32Config.tiny(dtype="float32",
                                     experts_held=(e, e + 4))
        share = {**lp, "experts": jax.tree_util.tree_map(
            lambda k: k[e:e + 4], lp["experts"])}
        y, n = dsv32.moe(x, share, cfg)
        total = total + (y - shared)
        held_sum += int(n)
        ref_total = ref_total + reference.moe(
            x, share, _arch(cfg)["model"]) - reference.swiglu(
                x, lp["shared"])
    assert held_sum == int(n_full) == 24 * whole.experts_per_token
    assert float(jnp.abs(total - full).max()) < 1e-5
    ref_full = reference.moe(x, lp, _arch(whole)["model"])
    assert float(jnp.abs(ref_total + shared - ref_full).max()) < 1e-5
    assert float(jnp.abs(full - ref_full).max()) < 1e-5


@pytest.mark.parametrize("over,match", [
    ({"layers": ("dense", "sparse")}, "layer kind"),
    ({"experts_held": (8, 4)}, "experts_held"),
    ({"experts_held": (0, 17)}, "experts_held"),
    ({"vocab_rows": (0, 513)}, "vocab_rows"),
    ({"n_group": 3}, "n_group"),
    ({"topk_group": 5}, "topk_group"),
    ({"experts_per_token": 9}, "experts_per_token"),
    ({"qk_rope_head_dim": 3}, "qk_rope_head_dim"),
])
def test_config_refuses_what_is_no_share_of_the_model(over, match):
    with pytest.raises(ValueError, match=match):
        DeepSeekV32Config.tiny(**over)


def test_published_widths_and_the_static_counts_at_the_cells_shapes():
    cfg = DeepSeekV32Config.published()
    assert len(cfg.layers) == 61 and cfg.layers.count("dense") == 3
    assert (cfg.hidden, cfg.heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_head_dim, cfg.v_head_dim, cfg.cache_width) \
        == (7168, 128, 1536, 512, 192, 128, 576)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) \
        == (64, 128, 2048)
    assert not [f.name for f in dataclasses.fields(cfg)
                if "tile" in f.name or "block" in f.name
                or "chunk" in f.name]           # no program-shape field
    share = dataclasses.replace(cfg, layers=("dense",) + ("moe",) * 4,
                                experts_held=(0, 16),
                                vocab_rows=(0, 16160))
    held, per_head = share.cache_bytes(16384 + 256)
    # 1,408 B a position a layer against 81,920 B of per-head K and V
    assert held == 5 * 16640 * 1408 and per_head == 5 * 16640 * 81920
    assert 100.0 * held / per_head == 1.71875
    pairs, causal = share.attn_pairs(16384, 256)
    n = 16384 + 255
    assert causal == 5 * n * (n + 1) // 2
    assert pairs == 5 * (2048 * 2049 // 2 + (n - 2048) * 2048)
    assert 100.0 * pairs / causal == pytest.approx(23.1012, abs=1e-4)
    assert reference.kept_pairs(n, 2048) == (pairs // 5, causal // 5)
    # the program's shapes, from the static shapes alone
    assert dsv32._block(16384, 128) == 512
    assert dsv32._block(12000, 128) == 500
    assert dsv32._block(12, 4) == 12
    assert expert_tile(512, share) == 32 and expert_tile(8, share) == 8
    # the FFN chunk: 4,096 rows (256-row tiles, 1.53 GB of routed
    # temporaries; 8,192 would take 3.05 GB), 160 chunks a bucket of 8
    # where 512-row blocks made 1,280; the goldened tiny programs run one
    # chunk of their 12 rows
    assert dsv32._ffn_rows(16384, share) == 4096
    assert expert_tile(4096, share) == 256
    assert dsv32._routed_bytes(4096, share) <= dsv32._FFN_BYTES \
        < dsv32._routed_bytes(8192, share)
    attrs = DeepSeekV32Pipeline(share).bucket_attrs(8, 16384, 256)
    assert (attrs["ffn_rows"], attrs["ffn_calls"]) == (4096, 160)
    assert dsv32._ffn_rows(12, DeepSeekV32Config.tiny()) == 12
    assert dsv32._ffn_rows(12000, share) == 4000
    shapes = jax.eval_shape(
        lambda: dsv32.init_params(share, jax.random.PRNGKey(0)))
    n_params = sum(math.prod(x.shape)
                   for x in jax.tree_util.tree_leaves(shapes))
    assert n_params == 4_635_518_208
    assert shapes["layer_1"]["moe"]["experts"]["gate"]["kernel"].shape \
        == (16, 7168, 2048)


def test_bucket_program_is_deterministic_and_prefix_stable():
    cfg = DeepSeekV32Config.tiny()
    pipe = DeepSeekV32Pipeline(cfg, prompt_buckets=(P,),
                               decode_buckets=(3, T), top_k=4)
    params = pipe.init_params(seed=0, dtype="bfloat16")
    kw = dict(prompts=["a prompt", "another"], seeds=[11, 2**40 + 5],
              prompt_bucket=P)
    for sampler in ("greedy", "top_k"):
        a, ra = pipe.generate(params, decode_bucket=T, sampler=sampler, **kw)
        b, _ = pipe.generate(params, decode_bucket=T, sampler=sampler, **kw)
        c, rc = pipe.generate(params, decode_bucket=3, sampler=sampler, **kw)
        assert (a == b).all() and (a[:, :3] == c).all()
        assert ra[0] == 2 * (P + T - 1) * 2 * 4 and ra[0] == ra[1]
        assert rc[0] == 2 * (P + 2) * 2 * 4
        assert int(a.max()) < pipe.BYTE_IDS
    assert pipe.bucket_tag(2, P, T, "greedy") \
        == f"deepseek_v32.2.{P}.{T}.greedy"
    attrs = pipe.bucket_attrs(2, P, T)
    assert set(attrs) == {"cache_bytes", "cache_bytes_per_head",
                          "attn_pairs", "attn_pairs_causal",
                          "attn_kernel_calls", "attn_blocks",
                          "attn_blocks_dense", "ffn_rows", "ffn_calls",
                          "expert_calls_grouped", "expert_calls_loop"}
    # off the TPU the tile loop walks every routed call: a chunk an
    # expert layer a sequence, then one a layer a step
    assert (attrs["expert_calls_grouped"], attrs["expert_calls_loop"]) \
        == (0, 2 * 4 + (T - 1) * 4)
    # the prefill kernel's counts: the walk serves every call off the TPU
    assert (attrs["attn_kernel_calls"], attrs["attn_blocks"],
            attrs["attn_blocks_dense"]) == (0, 0, 0)
    # the FFN's chunks run on every backend: one of the 12 rows a layer
    assert (attrs["ffn_rows"], attrs["ffn_calls"]) == (P, 2 * 5)
    assert (attrs["cache_bytes"], attrs["cache_bytes_per_head"]) \
        == cfg.cache_bytes(P + T)
    n = P + T - 1
    assert attrs["attn_pairs"] == 5 * (4 * 5 // 2 + (n - 4) * 4)
    assert attrs["attn_pairs_causal"] == 5 * n * (n + 1) // 2
    with pytest.raises(ValueError, match="bf16 only"):
        DeepSeekV32Pipeline(cfg, precision="int8")
    with pytest.raises(ValueError, match="deepseek_v32 ships no mesh"):
        DeepSeekV32Pipeline(cfg, mesh=object())
    with pytest.raises(ValueError, match="byte"):
        DeepSeekV32Pipeline(DeepSeekV32Config.tiny(vocab_rows=(256, 512)))


def test_dsv32_graph_goldens_and_rules_are_clean():
    """The four goldened programs (prefill, decode greedy and top-k, the
    composed bucket) trace to their checked-in goldens with no GRAPH4xx
    finding; this family's specs alone, through the library (the
    graphlint CLI is red on jax 0.9.0: ROADMAP D0)."""
    from arbius_tpu.analysis import graph
    from arbius_tpu.models.deepseek_v32.pipeline import trace_specs

    specs = trace_specs()
    assert sorted(s.key for s in specs) == [
        "deepseek_v32.decode.b2.p12.t4.greedy.single.bfloat16",
        "deepseek_v32.decode.b2.p12.t4.top_k.single.bfloat16",
        "deepseek_v32.generate.b2.p12.t4.greedy.single.bfloat16",
        "deepseek_v32.prefill.b2.p12.t4.single.bfloat16"]
    assert graph.audit(specs) == []


def test_bucket_edges_and_share_per_text_template():
    cfg = load_config({"textgen": {
        "templates": {"deepseek_v32": {"prompt_buckets": [16384],
                                       "decode_buckets": [256],
                                       "max_new_tokens": 256}},
        "share": {"experts_held": [0, 16], "vocab_rows": [0, 16160],
                  "layers": ["dense", "moe", "moe", "moe", "moe"]}}})
    tg = cfg.textgen.for_template("deepseek_v32")
    assert (tg.prompt_buckets, tg.decode_buckets, tg.max_new_tokens) \
        == ((16384,), (256,), 256)
    assert set(tg.share) == {"experts_held", "vocab_rows", "layers"}
    # a share states which experts, rows and layers: no program shape
    for bad in ("index_topk", "block", "chunk"):
        with pytest.raises(ConfigError, match=bad):
            load_config({"textgen": {"share": {bad: 8}}})


def test_factory_builds_the_runner_from_the_template_block():
    from arbius_tpu.node.factory import build_registry

    mid = "0x" + "7b" * 32
    cfg = load_config({
        "models": [{"id": mid, "template": "deepseek_v32", "tiny": True,
                    "weights_dtype": "bfloat16"}],
        "textgen": {"templates": {"deepseek_v32": {
            "prompt_buckets": [P], "decode_buckets": [T],
            "max_new_tokens": T}},
            "share": {"experts_held": [4, 12],
                      "layers": ["dense", "moe"]}}})
    runner = build_registry(cfg).get(mid).runner
    assert isinstance(runner, TextGenRunner)
    assert runner.pipeline.FAMILY == "deepseek_v32"
    assert runner.pipeline.prompt_buckets == (P,)
    assert runner.pipeline.config.experts_held == (4, 12)
    assert runner.pipeline.config.layers == ("dense", "moe")
    gate = runner.params["layer_1"]["moe"]["experts"]["gate"]["kernel"]
    assert gate.shape == (8, 32, 16) and gate.dtype == jnp.bfloat16
    assert set(runner.params["layer_0"]["indexer"]["k_norm"]) \
        == {"scale", "bias"}
    assert "bias" not in runner.params["head"]      # untied, no bias
    bad = load_config({
        "models": [{"id": mid, "template": "deepseek_v32", "tiny": True}],
        "textgen": {"share": {"experts_held": [6, 2]}}})
    with pytest.raises(ConfigError, match="experts_held"):
        build_registry(bad)


def _world(pipe, params, pipeline_on):
    from test_textgen import _text_world   # the text families' node world

    eng, node, mid, user = _text_world(pipe, params, pipeline_on=pipeline_on,
                                       template="deepseek_v32")
    while node.tick():
        pass
    for i in range(3):       # a full bucket and a padded one
        obj = {"prompt": f"dsv32 task {i}", "max_new_tokens": (T, 2)[i % 2]}
        eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]),
                        (1 + i) * 10**18,
                        json.dumps(obj, sort_keys=True).encode())
    for _ in range(128):
        if node.tick() == 0:
            break
    cids = {"0x" + t.hex(): "0x" + s.cid.hex()
            for t, s in eng.solutions.items()}
    text = node.obs.registry.render()
    spans = [e for e in node.obs.journal.events() if e.get("kind") == "span"]
    node.close()
    return cids, text, spans


def test_greedy_cids_equal_with_the_staged_executor_on_and_off():
    """The node path end to end: MinerNode.tick() -> TextGenRunner ->
    the family's bucket programs; the same tasks, the same CIDs twice."""
    cfg = DeepSeekV32Config.tiny()

    def fresh():
        return DeepSeekV32Pipeline(cfg, prompt_buckets=(P, 32),
                                   decode_buckets=(T,), top_k=4)

    params = fresh().init_params(seed=0, dtype="bfloat16")
    off, text, spans = _world(fresh(), params, False)
    on, text_on, spans_on = _world(fresh(), params, True)
    assert len(off) == 3 and on == off
    for text, spans in ((text, spans), (text_on, spans_on)):
        # the runner's spans and counters (docs/observability.md)
        bucket = [s for s in spans if s["name"] == "text.bucket"]
        routed = [s for s in spans if s["name"] == "text.routed"]
        assert len(bucket) == len(routed) == 2
        a = bucket[0]["attrs"]
        assert (a["model"], a["prompt_bucket"], a["decode_bucket"],
                a["batch"]) == ("deepseek_v32", 32, T, 2)
        assert (a["cache_bytes"], a["cache_bytes_per_head"]) \
            == cfg.cache_bytes(32 + T)
        assert (a["attn_pairs"], a["attn_pairs_causal"]) \
            == cfg.attn_pairs(32, T)
        # this family's quantities and not trinity's rows; the prefill
        # kernel's counts under trinity's names, 0 on the CPU
        assert "kv_rows" not in a
        assert (a["attn_kernel_calls"], a["attn_blocks"],
                a["attn_blocks_dense"]) == (0, 0, 0)
        assert (a["ffn_rows"], a["ffn_calls"]) == (32, 2 * 5)
        made = 2 * (32 + T - 1) * 2 * 4
        assert all(s["attrs"]["assignments"] == s["attrs"]["held"] == made
                   for s in routed)
        assert f'arbius_moe_assignments_total{{held="yes"}} {2 * made}' \
            in text
        assert f'arbius_text_tokens_total{{phase="prefill"}} {2 * 2 * 32}' \
            in text
