"""dots3_note tier-1 suite (docs/text-serving.md): the dots3-note-prev
family at a tiny size on the CPU against the benchmark's plain float32
reference (perfbench/reference/dots3_note.py, which imports nothing of
the program) on seeded weights, with a window of 5 and an `index_topk`
of 4, both smaller than the context so that the band and the selection
bite in prefill and in every decode step: prefill then decode through
both forms of cache — the sliding layers' ring wrapping twice in the
decode steps — against one full forward pass; each layer kind's rotary
theta and softmax scale, the headwise gate, the LoRA rescale and the
window each held by a case that fails without it; the shares' routed
parts adding up to the uncut layer; the banded kernel (`interpret=True`)
against its walk and a plain softmax; the static counts at the cell's
shapes; and greedy CIDs through a real MinerNode."""
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

from arbius_tpu.models.deepseek_v32 import model as dsv32
from arbius_tpu.models.dots3 import Dots3NoteConfig, Dots3NotePipeline
from arbius_tpu.models.dots3 import model as dots3
from arbius_tpu.models.dots3.model import MLA
from arbius_tpu.models.trinity.model import swiglu
from arbius_tpu.node.config import load_config
from arbius_tpu.node.solver import TextGenRunner
from arbius_tpu.ops import selected_flash
from perfbench.reference import deepseek_v32 as dsv32_reference
from perfbench.reference import dots3_note as reference

# 22 positions: the ring of 5 is written at slots 2, 3, 4, 0, 1, 2, 3, 4,
# 0, 1 by the ten decode steps (two wraps), after the prompt's fill kept
# positions 7-11; the selection of 4 keys bites from the fifth position
P, T = 12, 11


def _params(cfg, seed=0, dtype=None):
    p = dots3.init_params(cfg, jax.random.PRNGKey(seed))
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
    model = {k: list(v) if isinstance(v, tuple) else v
             for k, v in dataclasses.asdict(cfg).items()}
    model["layers"] = [list(k) for k in cfg.layers]
    return {"model": model, "prompt_buckets": [P], "decode_buckets": [T],
            "top_k": 4,
            "tokenizer": {"kind": "byte", "bos_id": 257, "eos_id": 258}}


def _program_logits(cfg, params, ids):
    """Teacher-forced through the program's own split: prefill on the
    first P ids, then one decode step an id, each through the caches."""
    p = ids.shape[1] - T + 1
    logits0, carry = dots3.prefill(params, ids[:, :p], p + T, cfg)
    rows = [logits0]
    for i in range(1, T):
        lg, carry = dots3.decode(params, ids[:, p + i - 1], carry,
                                 jnp.int32(p + i - 1), cfg)
        rows.append(lg)
    return jnp.stack(rows, axis=1), carry


def _reference_logits(cfg, params, ids):
    return reference.forward(params, ids, jnp.zeros((T,), jnp.int32),
                             _arch(cfg)["model"])


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 4 rows in the program's prefill attention of both kinds
    (3 to the prompt: the band's walk starts past block 0 from the third)
    and in the reference (row blocks of 4, coarse blocks of 8, head
    groups of 2), so that every loop over blocks runs more than once."""
    monkeypatch.setattr(dsv32, "_SCORE_BYTES", 4 * 4 * 4 * 4)
    monkeypatch.setattr(dsv32_reference, "ROW_BLOCK", 4)
    monkeypatch.setattr(dsv32_reference, "COARSE", 2)
    monkeypatch.setattr(reference, "HEAD_GROUP", 2)


@pytest.mark.parametrize("dtype,tol,held", [
    # float32 program against the float32 reference: only the order of
    # sums differs (blocks, the running softmax, the latent form in
    # decode, grouped tiles) — 1e-4 of logits whose spread is ~1; the
    # whole model, and a share of its experts
    ("float32", 1e-4, (0, 16)),
    ("float32", 1e-4, (4, 12)),
])
def test_prefill_then_decode_through_both_caches_matches_full_forward(
        dtype, tol, held, small_blocks):
    cfg = Dots3NoteConfig.tiny(dtype=dtype, experts_held=held)
    params = _params(cfg, dtype=dtype)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + T - 1), 0, 256)
    assert dsv32._block(P, cfg.heads) == dsv32._block(P, cfg.swa_heads) == 4
    got, (caches, stats) = jax.jit(
        lambda p, i: _program_logits(cfg, p, i))(params, ids)
    want = _reference_logits(cfg, params, ids)
    assert got.shape == want.shape == (2, T, cfg.n_vocab)
    assert float(jnp.abs(got - want).max()) < tol
    # the carry: a full layer's latent and indexer key a position, a
    # sliding layer's ring of `window` latent rows of its own width
    full, slide = cfg.attn("full"), cfg.attn("sliding")
    assert len(caches) == len(cfg.layers) == 5
    for (_, attn), c in zip(cfg.layers, caches):
        if attn == "full":
            assert [x.shape for x in c] == [(2, P + T, full.cache_width),
                                            (2, P + T, cfg.index_head_dim)]
        else:
            assert [x.shape for x in c] == [(2, 5, slide.cache_width)]
    assert (full.cache_width, slide.cache_width) == (20, 28)
    made = 2 * (P + T - 1) * cfg.experts_per_token * dots3.n_moe(cfg)
    assert int(stats[0]) == made
    assert int(stats[1]) == made if held == (0, 16) \
        else 0 < int(stats[1]) < made


def test_bfloat16_program_stays_near_the_float32_reference():
    """bfloat16 as served, with every key kept in the full layers (a
    selection of 4 keys a rounding apart moves a logit by tenths at this
    size; at the published sizes one key of 2,048 does not): the mean
    gap bounds the rounding of the stream, the band and the ring, and
    the widest one router's choice a rounding apart (0.26 here)."""
    cfg = Dots3NoteConfig.tiny(dtype="bfloat16", index_topk=64,
                               layers=(("dense", "full"), ("moe", "sliding"),
                                       ("moe", "sliding")))
    params = _params(cfg, dtype="bfloat16")
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + T - 1), 0, 256)
    got, _ = jax.jit(lambda p, i: _program_logits(cfg, p, i))(params, ids)
    want = _reference_logits(cfg, params, ids)
    err = jnp.abs(got - want)
    assert float(err.mean()) < 0.02 and float(err.max()) < 0.5


def _mutated_attn(monkeypatch, kind, **over):
    real = Dots3NoteConfig.attn

    def attn(self, k):
        a = real(self, k)
        return dataclasses.replace(a, **over) if k == kind else a

    monkeypatch.setattr(Dots3NoteConfig, "attn", attn)


@pytest.mark.parametrize("case", [
    "full_theta", "sliding_theta", "full_scale", "sliding_scale",
    "full_q_rescale", "full_kv_rescale", "sliding_q_rescale",
    "sliding_kv_rescale", "gate", "window"])
def test_each_kinds_equation_is_held_by_the_reference(case, monkeypatch):
    """The program with one equation of one layer kind taken away — the
    other kind's rotary theta, the nope width's scale in place of
    (nope + rope)^-1/2, a LoRA rescale left out, no headwise gate, no
    window in prefill — reads at least 100 times the float32 tolerance
    against the reference; the whole program reads under it (the first
    test)."""
    cfg = Dots3NoteConfig.tiny(dtype="float32")
    params = _params(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, P + T - 1), 0, 256)
    want = _reference_logits(cfg, params, ids)
    kind = case.split("_")[0]
    if case.endswith("theta"):
        other = {"full": cfg.swa_rope_theta, "sliding": cfg.rope_theta}
        _mutated_attn(monkeypatch, kind, rope_theta=other[kind])
    elif case.endswith("scale") and "rescale" not in case:
        heads = cfg.attn(kind).heads
        real = MLA.softmax_scale.fget
        monkeypatch.setattr(MLA, "softmax_scale", property(
            lambda a: a.qk_nope_head_dim ** -0.5 if a.heads == heads
            else real(a)))
    elif case.endswith("q_rescale"):
        _mutated_attn(monkeypatch, kind, q_scale=None)
    elif case.endswith("kv_rescale"):
        _mutated_attn(monkeypatch, kind, kv_scale=None)
    elif case == "gate":
        for mod in (dsv32, dots3):
            monkeypatch.setattr(mod, "_head_gate", lambda o, *a: o)
    else:
        _mutated_attn(monkeypatch, "sliding", window=10**6)
    assert cfg.attn("full").heads != cfg.attn("sliding").heads
    got, _ = jax.jit(lambda p, i: _program_logits(cfg, p, i))(params, ids)
    assert float(jnp.abs(got - want).max()) > 1e-2


def test_the_kinds_shapes_rotary_scale_and_rescale_at_the_published_widths():
    cfg = Dots3NoteConfig.published()
    full, slide = cfg.attn("full"), cfg.attn("sliding")
    assert len(cfg.layers) == 46 and cfg.count("full") == 13 \
        and cfg.count("sliding") == 33
    assert cfg.layers[:6] == (("dense", "full"), ("moe", "full"),
                              ("moe", "sliding"), ("moe", "sliding"),
                              ("moe", "sliding"), ("moe", "full"))
    assert cfg.layers[-1] == ("moe", "full")
    assert (full.heads, full.kv_lora_rank, full.qk_head_dim,
            full.cache_width, full.window) == (128, 512, 192, 576, None)
    assert (slide.heads, slide.kv_lora_rank, slide.qk_head_dim,
            slide.cache_width, slide.window) == (64, 1024, 256, 1088, 513)
    assert full.softmax_scale == 192 ** -0.5
    assert slide.softmax_scale == 256 ** -0.5
    assert full.q_scale == slide.q_scale == math.sqrt(5.0)
    assert (full.kv_scale, slide.kv_scale) == (math.sqrt(10.0),
                                               math.sqrt(5.0))
    # plain rotary: deepseek_v32's YaRN table at factor 1 is theta^(-2i/64)
    for a in (full, slide):
        np.testing.assert_allclose(
            dsv32.yarn_freqs(a),
            a.rope_theta ** (-2.0 * np.arange(32) / 64), rtol=1e-6)
    ref = _arch(cfg)["model"]
    for attn, a in (("full", full), ("sliding", slide)):
        k = reference.kind(ref, attn)
        assert (k["heads"], k["window"], k["q_scale"], k["kv_scale"]) \
            == (a.heads, a.window, a.q_scale, a.kv_scale)
        np.testing.assert_array_equal(dsv32_reference.yarn_freqs(k),
                                      dsv32.yarn_freqs(a))


def test_eight_shares_add_up_to_the_uncut_layer():
    """16 tiny experts over 8 chips, two a chip: the shares' routed parts,
    with the shared expert counted once, add up to what the uncut layer
    gives — in the program and in the reference (float32: 1e-5, the order
    of an eight-term sum)."""
    whole = Dots3NoteConfig.tiny(dtype="float32")
    params = _params(whole)
    lp = params["layer_2"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, whole.hidden))
    full, n_full = dsv32.moe(x, lp, whole)
    shared = swiglu(x, lp["shared"])
    total, held_sum = shared, 0
    ref_total = jnp.zeros_like(x)
    for e in range(0, 16, 2):
        cfg = Dots3NoteConfig.tiny(dtype="float32", experts_held=(e, e + 2))
        share = {**lp, "experts": jax.tree_util.tree_map(
            lambda k: k[e:e + 2], lp["experts"])}
        y, n = dsv32.moe(x, share, cfg)
        total = total + (y - shared)
        held_sum += int(n)
        ref_total = ref_total + dsv32_reference.moe(
            x, share, reference.router(_arch(cfg)["model"])) \
            - dsv32_reference.swiglu(x, lp["shared"])
    assert held_sum == int(n_full) == 24 * whole.experts_per_token
    assert float(jnp.abs(total - full).max()) < 1e-5
    ref_full = dsv32_reference.moe(x, lp,
                                   reference.router(_arch(whole)["model"]))
    assert float(jnp.abs(ref_total + shared - ref_full).max()) < 1e-5
    assert float(jnp.abs(full - ref_full).max()) < 1e-5


def _band(p, window, heads, dk, dv, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (p, heads, dk), dtype),
            jax.random.normal(ks[1], (p, heads, dk), dtype),
            jax.random.normal(ks[2], (p, heads, dv), dtype))


def _exact_band(q, k, v, window, scale):
    q, k, v = (np.asarray(a, np.float32) for a in (q, k, v))
    p = q.shape[0]
    t, s = np.arange(p)[:, None], np.arange(p)[None, :]
    ok = (s <= t) & (s > t - window)
    sc = np.einsum("qhd,khd->hqk", q, k) * scale
    sc = np.where(ok[None], sc, -np.inf)
    w = np.exp(sc - sc.max(axis=-1, keepdims=True))
    w = w / w.sum(axis=-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", w, v).reshape(p, -1)


# the band at the sliding layers' joined width (nope 192 + rope 64) and
# at a narrow one; a prompt shorter than the window; a window narrower
# than a tile, so that query blocks start past key block 0. float32 to
# the order of sums (2e-5, as tests/test_selected_flash.py); bfloat16
# as served, 2e-2 of outputs of spread ~1
@pytest.mark.parametrize("p,window,heads,dk,dv,dtype,tol", [
    (640, 513, 2, 256, 128, "float32", 2e-5),
    (40, 513, 8, 256, 128, "float32", 2e-5),
    (384, 5, 4, 128, 128, "float32", 2e-5),
    (640, 513, 2, 256, 128, "bfloat16", 2e-2),
])
def test_banded_kernel_in_interpret_mode_matches_its_walk_and_exact(
        p, window, heads, dk, dv, dtype, tol):
    q, k, v = _band(p, window, heads, dk, dv, jnp.dtype(dtype))
    scale = 256 ** -0.5
    got = selected_flash.window_flash_attention(q, k, v, window=window,
                                                scale=scale, interpret=True)
    walk = selected_flash.window_walk(q, k, v, window=window, scale=scale,
                                      block=8 if p % 8 == 0 else p)
    exact = _exact_band(q, k, v, window, scale)
    assert got.shape == walk.shape == (p, heads * dv)
    assert float(jnp.abs(got.astype(jnp.float32)
                         - walk.astype(jnp.float32)).max()) < tol
    assert float(np.abs(np.asarray(got, np.float32) - exact).max()) < tol


def test_window_walk_blocks_and_the_rule():
    """At the cell's shapes a group of 8 heads walks 31 of the 256 key
    blocks a sequence of 8,192 positions has at 512-row tiles (query
    block i meets key blocks i - 1 and i), where the causal walk meets
    136; the 64 sliding heads are 8 such groups. The rule serves the
    joined 256-wide rows on a TPU from 2,048 positions only."""
    assert selected_flash.walk_blocks(8192, 8192, 8, 513) == (31, 256)
    assert selected_flash.walk_blocks(8192, 8192, 8) == (136, 256)
    assert selected_flash.walk_blocks(8192, 8192, 64, 513) == (248, 2048)
    assert selected_flash._tiles(8192, 8192) == (512, 512)
    # a band narrower than a tile still meets the block before at i >= 1
    assert selected_flash.walk_blocks(1024, 1024, 8, 5) == (3, 4)
    # the cell's full layers: 512-row calls under the selection, as
    # deepseek_v32's at 16,384
    assert selected_flash.walk_blocks(8192, 512, 128) == (16 * 136, 16 * 256)
    assert not selected_flash.kernel_serves(8192, 256, 128)   # the CPU


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 0.25)])
def test_prefill_through_both_kernels_gives_the_walks_logits_and_caches(
        dtype, tol, small_blocks, monkeypatch):
    """The TPU's side of both rules forced here (`interpret=True`): the
    full layers through the selection's kernel and the sliding layers
    through the banded one, against the walks: the same logits and
    caches, to the order of the softmax's sums."""
    cfg = Dots3NoteConfig.tiny(dtype=dtype)
    params = _params(cfg, dtype=dtype)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, P), 0, 256)
    run = lambda: jax.jit(
        lambda p, i: dots3.prefill(p, i, P + T, cfg))(params, ids)
    want, (caches, stats) = run()
    selected, banded = (selected_flash.selected_flash_attention,
                        selected_flash.window_flash_attention)
    calls = []

    def forced_selected(*a, **kw):
        calls.append("selected")
        return selected(*a, **kw, interpret=True)

    def forced_banded(*a, **kw):
        calls.append("banded")
        return banded(*a, **kw, interpret=True)

    monkeypatch.setattr(selected_flash, "kernel_serves", lambda *a: True)
    monkeypatch.setattr(selected_flash, "selected_flash_attention",
                        forced_selected)
    monkeypatch.setattr(selected_flash, "window_flash_attention",
                        forced_banded)
    got, (caches_k, stats_k) = run()
    # traced once a layer: two full layers, three sliding ones
    assert calls == ["selected"] * 2 + ["banded"] * 3
    assert float(jnp.abs(got - want).max()) < tol
    for a, b in zip(jax.tree_util.tree_leaves(caches),
                    jax.tree_util.tree_leaves(caches_k)):
        assert float(jnp.abs(a.astype(jnp.float32)
                             - b.astype(jnp.float32)).max()) < tol
    assert int(stats_k[0]) == int(stats[0])


def test_published_share_parameters_and_static_counts_at_the_cells_shapes():
    cfg = Dots3NoteConfig.published()
    assert not [f.name for f in dataclasses.fields(cfg)
                if "tile" in f.name or "block" in f.name
                or "chunk" in f.name]           # no program-shape field
    share = dataclasses.replace(cfg, layers=Dots3NoteConfig.pattern(4),
                                experts_held=(0, 32), vocab_rows=(0, 19008))
    shapes = jax.eval_shape(
        lambda: dots3.init_params(share, jax.random.PRNGKey(0)))

    def count(tree):
        return sum(math.prod(x.shape)
                   for x in jax.tree_util.tree_leaves(tree))

    assert count(shapes) == 4_087_154_176
    assert count(shapes["layer_1"]["attn"]) + count(
        shapes["layer_1"]["indexer"]) == 144_049_920
    assert count(shapes["layer_2"]["attn"]) == 90_834_944
    assert count(shapes["layer_0"]) == 356_396_800
    assert count(shapes["layer_1"]) == 923_938_816
    assert count(shapes["layer_4"]) == 870_723_840
    assert "indexer" not in shapes["layer_2"]
    assert shapes["layer_2"]["attn"]["gate"]["kernel"].shape == (5120, 64)
    assert shapes["layer_1"]["attn"]["gate"]["kernel"].shape == (5120, 128)
    # cache bytes a sequence of 8,192 + 1,024 positions: rings of 513
    # rows of 1,088 in three layers, latent and indexer rows of 576 + 128
    # in two; the rings at full length would be 9,216 rows
    window, full, window_full = share.cache_bytes(9216)
    assert (window, full, window_full) == (3 * 513 * 1088 * 2,
                                           2 * 9216 * 704 * 2,
                                           3 * 9216 * 1088 * 2)
    assert window + full == 29_301_120
    assert window_full + full == 86_114_304
    assert round(100.0 * (window + full) / (window_full + full), 2) == 34.03
    pipe = Dots3NotePipeline(share)
    attrs = pipe.bucket_attrs(16, 8192, 1024)
    assert (attrs["ffn_rows"], attrs["ffn_calls"]) == (4096, 16 * 5 * 2)
    assert dsv32._block(8192, 128) == dsv32._block(8192, 64) == 512
    assert attrs["attn_kernel_calls"] == 0        # the CPU: the walks


def test_bucket_program_is_deterministic_and_prefix_stable():
    cfg = Dots3NoteConfig.tiny()
    pipe = Dots3NotePipeline(cfg, prompt_buckets=(P,),
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
        == f"dots3_note.2.{P}.{T}.greedy"
    attrs = pipe.bucket_attrs(2, P, T)
    assert (attrs["cache_bytes_window"], attrs["cache_bytes_full"],
            attrs["cache_bytes_window_full"]) == cfg.cache_bytes(P + T)
    assert attrs["attn_kernel_calls"] == 0
    with pytest.raises(ValueError, match="bf16 only"):
        Dots3NotePipeline(cfg, precision="int8")
    with pytest.raises(ValueError, match="dots3_note ships no mesh"):
        Dots3NotePipeline(cfg, mesh=object())


@pytest.mark.parametrize("over,match", [
    ({"layers": (("dense", "band"),)}, "layer kind"),
    ({"experts_held": (8, 4)}, "experts_held"),
    ({"vocab_rows": (0, 513)}, "vocab_rows"),
    ({"window": 0}, "window"),
    ({"swa_qk_rope_head_dim": 6}, "qk_rope_head_dim"),
])
def test_config_refuses_what_is_no_share_of_the_model(over, match):
    with pytest.raises(ValueError, match=match):
        Dots3NoteConfig.tiny(**over)


def test_dots3_graph_goldens_and_rules_are_clean():
    """The four goldened programs (prefill, decode greedy and top-k, the
    composed bucket) trace to their checked-in goldens with no GRAPH4xx
    finding; this family's specs alone, through the library (the
    graphlint CLI is red on jax 0.9.0: ROADMAP D0)."""
    from arbius_tpu.analysis import graph
    from arbius_tpu.models.dots3.pipeline import trace_specs

    specs = trace_specs()
    assert sorted(s.key for s in specs) == [
        "dots3_note.decode.b2.p12.t4.greedy.single.bfloat16",
        "dots3_note.decode.b2.p12.t4.top_k.single.bfloat16",
        "dots3_note.generate.b2.p12.t4.greedy.single.bfloat16",
        "dots3_note.prefill.b2.p12.t4.single.bfloat16"]
    assert graph.audit(specs) == []


def test_factory_builds_the_runner_from_the_template_block():
    from arbius_tpu.node.factory import build_registry

    mid = "0x" + "d3" * 32
    cfg = load_config({
        "models": [{"id": mid, "template": "dots3_note", "tiny": True,
                    "weights_dtype": "bfloat16"}],
        "textgen": {"templates": {"dots3_note": {
            "prompt_buckets": [P], "decode_buckets": [T],
            "max_new_tokens": T}},
            "share": {"experts_held": [4, 12],
                      "layers": [["dense", "full"], ["moe", "sliding"]]}}})
    runner = build_registry(cfg).get(mid).runner
    assert isinstance(runner, TextGenRunner)
    assert runner.pipeline.FAMILY == "dots3_note"
    assert runner.pipeline.config.layers == (("dense", "full"),
                                             ("moe", "sliding"))
    gate = runner.params["layer_1"]["moe"]["experts"]["gate"]["kernel"]
    assert gate.shape == (8, 32, 16) and gate.dtype == jnp.bfloat16
    assert "indexer" in runner.params["layer_0"]
    assert "indexer" not in runner.params["layer_1"]
    assert runner.params["layer_1"]["attn"]["gate"]["kernel"].shape \
        == (32, 2)


def test_greedy_cids_spans_and_counters_through_a_miner_node():
    """The node path end to end: MinerNode.tick() -> TextGenRunner ->
    the family's bucket programs; the same tasks, the same CIDs with the
    staged executor on and off; `text.bucket` states the cache bytes by
    form and `/metrics` counts the rings under `form="window_latent"`."""
    from test_textgen import _text_world   # the text families' node world

    cfg = Dots3NoteConfig.tiny()

    def world(pipeline_on):
        pipe = Dots3NotePipeline(cfg, prompt_buckets=(P, 32),
                                 decode_buckets=(T,), top_k=4)
        params = pipe.init_params(seed=0, dtype="bfloat16")
        eng, node, mid, user = _text_world(pipe, params,
                                           pipeline_on=pipeline_on,
                                           template="dots3_note")
        while node.tick():
            pass
        for i in range(3):       # a full bucket and a padded one
            obj = {"prompt": f"dots3 task {i}",
                   "max_new_tokens": (T, 2)[i % 2]}
            eng.submit_task(user, 0, user, bytes.fromhex(mid[2:]),
                            (1 + i) * 10**18,
                            json.dumps(obj, sort_keys=True).encode())
        for _ in range(128):
            if node.tick() == 0:
                break
        cids = {"0x" + t.hex(): "0x" + s.cid.hex()
                for t, s in eng.solutions.items()}
        text = node.obs.registry.render()
        spans = [e for e in node.obs.journal.events()
                 if e.get("kind") == "span"]
        node.close()
        return cids, text, spans

    off, text, spans = world(False)
    on, _, _ = world(True)
    assert len(off) == 3 and on == off
    bucket = [s for s in spans if s["name"] == "text.bucket"]
    routed = [s for s in spans if s["name"] == "text.routed"]
    assert len(bucket) == len(routed) == 2
    a = bucket[0]["attrs"]
    assert (a["model"], a["prompt_bucket"], a["batch"]) \
        == ("dots3_note", 32, 2)
    window, full, _ = cfg.cache_bytes(32 + T)
    assert (a["cache_bytes_window"], a["cache_bytes_full"]) == (window, full)
    assert "cache_bytes" not in a and "kv_rows" not in a
    assert f'arbius_text_cache_bytes_total{{form="window_latent"}} ' \
        f'{2 * 2 * window}' in text
    assert f'arbius_text_cache_bytes_total{{form="latent"}} ' \
        f'{2 * 2 * full}' in text
