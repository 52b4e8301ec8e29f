"""trinity tier-1 suite (docs/text-serving.md): the Trinity (`afmoe`)
family at a tiny size on the CPU against the benchmark's plain float32
reference (perfbench/reference/trinity.py, which imports nothing of the
program) on seeded weights: prefill then decode through BOTH caches
against one full forward pass (the prompt outgrows the tiny window, so
the ring wraps), the shares' routed parts adding up to the uncut layer,
the blockwise masked prefill attention against `ops.attend`, the bucket
policy per text template, and greedy CIDs through a real MinerNode with
the staged executor on and off."""
from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

from arbius_tpu.models.trinity import TrinityConfig, TrinityPipeline
from arbius_tpu.models.trinity import model as trinity
from arbius_tpu.node.config import ConfigError, load_config
from arbius_tpu.node.solver import TextGenRunner
from arbius_tpu.ops.blockwise import block_ranges, blockwise_attention
from perfbench.reference import ops as ref_ops
from perfbench.reference import trinity as reference

P, T = 12, 6          # the prompt outgrows the tiny window (8): the ring
                      # is filled rolled and then wraps in decode


def _params(cfg, seed=0, dtype=None):
    p = trinity.init_params(cfg, jax.random.PRNGKey(seed))
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
    return {"model": {**dataclasses.asdict(cfg)},
            "prompt_buckets": [P], "decode_buckets": [T], "top_k": 4,
            "tokenizer": {"kind": "byte", "bos_id": 257, "eos_id": 258}}


def _program_logits(cfg, params, ids):
    """Teacher-forced through the program's own split: prefill on the
    first P ids, then one decode step an id, each through the caches."""
    p = ids.shape[1] - T + 1
    logits0, carry = trinity.prefill(params, ids[:, :p], p + T, cfg)
    rows = [logits0]
    for i in range(1, T):
        lg, carry = trinity.decode(params, ids[:, p + i - 1], carry,
                                   jnp.int32(p + i - 1), cfg)
        rows.append(lg)
    return jnp.stack(rows, axis=1), carry


@pytest.mark.parametrize("dtype,tol,held", [
    # float32 program against the float32 reference: only the order of
    # sums differs (blocks, ring order, grouped tiles) — 1e-4 of logits
    # whose spread is ~1; the whole model, and a share of its experts
    ("float32", 1e-4, (0, 8)),
    ("float32", 1e-4, (2, 5)),
    # bfloat16 as served: 8 mantissa bits on every activation; the
    # reference reads the same bfloat16 weights, so 0.15 bounds rounding
    # of the stream alone (a wrong mask or slot reads > 0.5 here)
    ("bfloat16", 0.15, (0, 8)),
])
def test_prefill_then_decode_through_both_caches_matches_full_forward(
        dtype, tol, held):
    cfg = TrinityConfig.tiny(dtype=dtype, experts_held=held)
    params = _params(cfg, dtype=dtype)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + T - 1), 0, 256)
    got, (kv, stats) = jax.jit(
        lambda p, i: _program_logits(cfg, p, i))(params, ids)
    want = reference.forward(params, ids, jnp.zeros((T,), jnp.int32),
                             _arch(cfg)["model"])
    assert got.shape == want.shape == (2, T, cfg.n_vocab)
    assert float(jnp.abs(got - want).max()) < tol
    # the two kinds of cache: ring rows for sliding layers, all for full
    rows = [k.shape[1] for k, _ in kv]
    assert rows == [8, 8, 8, P + T, 8]
    assert cfg.kv_rows(P + T) == (4 * 8 + P + T, 5 * (P + T))
    # every assignment is held when the whole model is, a part otherwise
    made = 2 * (P + T - 1) * cfg.experts_per_token * 4
    assert int(stats[0]) == made
    assert int(stats[1]) == made if held == (0, 8) \
        else 0 < int(stats[1]) < made


@pytest.mark.parametrize("tokens,cfg,tile", [
    # the cell's shapes: a sequence of 8192 expects 128 rows an expert,
    # a decode step of 16 a quarter of a row
    (8192, TrinityConfig.published(), 256),
    (16, TrinityConfig.published(), 8),
    (128, TrinityConfig.published(), 8),
    (1 << 20, TrinityConfig.published(), 512),
    (12, TrinityConfig.tiny(), 8),
])
def test_expert_tile_follows_the_static_shape(tokens, cfg, tile):
    assert trinity.expert_tile(tokens, cfg) == tile


def test_ring_shorter_than_window_and_window_longer_than_bucket():
    """A bucket that never reaches the window keeps every row in a
    sliding layer too, and the program still agrees with the reference."""
    cfg = TrinityConfig.tiny(dtype="float32", window=64)
    params = _params(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(4), (1, P + T - 1), 0, 256)
    got, (kv, _) = _program_logits(cfg, params, ids)
    want = reference.forward(params, ids, jnp.zeros((T,), jnp.int32),
                             _arch(cfg)["model"])
    assert float(jnp.abs(got - want).max()) < 1e-4   # order of sums only
    assert {k.shape[1] for k, _ in kv} == {P + T}


def test_eight_shares_add_up_to_the_uncut_layer():
    """Each of 8 chips holds one of the tiny model's 8 routed experts:
    the shares' routed parts, with the shared expert counted once, add
    up to what the uncut layer gives — in the program and in the
    reference (float32: 1e-5, the order of a four-term sum)."""
    whole = TrinityConfig.tiny(dtype="float32")
    params = _params(whole)
    lp = params["layer_1"]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, whole.hidden))
    full, n_full = trinity.moe(x, lp, whole)
    # a skewed load: 64 tokens all sent to experts 0 and 1 (tiles of 32
    # rows, so each group is two tiles and six experts none) against the
    # two experts computed whole
    xs = jax.random.normal(jax.random.PRNGKey(7), (64, whole.hidden))
    chosen = jnp.tile(jnp.array([[0, 1]]), (64, 1))
    w = jax.random.uniform(jax.random.PRNGKey(8), (64, 2))
    assert trinity.expert_tile(64, whole) == 32
    got, n = trinity.routed_experts(xs, chosen, w, lp["experts"], whole)
    want = sum(w[:, i:i + 1] * trinity.swiglu(xs, jax.tree_util.tree_map(
        lambda k: k[i], lp["experts"])) for i in (0, 1))
    assert int(n) == 128
    assert float(jnp.abs(got - want).max()) < 1e-5
    shared = trinity.swiglu(x, lp["shared"])
    total = shared
    held_sum = 0
    ref_total = jnp.zeros_like(x)
    for e in range(8):
        cfg = TrinityConfig.tiny(dtype="float32", experts_held=(e, e + 1))
        share = {**lp, "experts": jax.tree_util.tree_map(
            lambda k: k[e:e + 1], lp["experts"])}
        y, n = trinity.moe(x, share, cfg)
        total = total + (y - shared)
        held_sum += int(n)
        rcfg = _arch(cfg)["model"]
        ref_total = ref_total + reference.moe(x[None], share, rcfg)[0] \
            - reference.swiglu(x[None], lp["shared"])[0]
    assert held_sum == int(n_full) == 24 * whole.experts_per_token
    assert float(jnp.abs(total - full).max()) < 1e-5
    ref_full = reference.moe(x[None], lp, _arch(whole)["model"])[0]
    assert float(jnp.abs(ref_total + shared - ref_full).max()) < 1e-5
    assert float(jnp.abs(full - ref_full).max()) < 1e-5


def test_router_ties_go_to_the_lower_expert():
    cfg = TrinityConfig.tiny(dtype="float32")
    p = {"router": {"kernel": jnp.zeros((cfg.hidden, 8))},
         "expert_bias": jnp.zeros((8,))}
    chosen, w = trinity.route(jnp.ones((3, cfg.hidden)), p, cfg)
    assert chosen.tolist() == [[0, 1]] * 3       # all scores 0.5
    np.testing.assert_allclose(w, cfg.route_scale / 2, rtol=1e-6)


@pytest.mark.parametrize("window", [None, 8, 5])
@pytest.mark.parametrize("q_block", [4, 7, 64])
def test_blockwise_attention_matches_attend_with_the_same_mask(window,
                                                               q_block):
    """Sliding against full masks: the XLA block walk equals exact
    attention under the same additive mask (float32, 1e-5: the order of
    the softmax sums), whatever the block size."""
    b, s, kv, g, d = 2, 19, 2, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (b, s, kv, g, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    got = blockwise_attention(q, k, v, window=window, q_block=q_block)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    ok = j <= i
    if window is not None:
        ok &= j > i - window
    mask = jnp.where(ok, 0.0, -jnp.inf)[None, None]
    want = ref_ops.attend(
        q.reshape(b, s, kv * g, d).transpose(0, 2, 1, 3),
        jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1),
        jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1), mask=mask)
    want = want.transpose(0, 2, 1, 3).reshape(b, s, kv, g, d)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # the blocks' key ranges leave exactly the mask's pairs
    pairs = sum(reference.mask_pairs(q0, q1, k0, window)
                for q0, q1, k0 in block_ranges(s, q_block, window))
    assert pairs == int(ok.sum())


def test_bucket_program_is_deterministic_and_prefix_stable():
    cfg = TrinityConfig.tiny()
    pipe = TrinityPipeline(cfg, prompt_buckets=(P,), decode_buckets=(3, T),
                           top_k=4)
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
        # the samplers see the byte ids alone (the tiny head has 512
        # rows, so an unmasked first choice is past the bytes half the
        # time)
        assert int(a.max()) < pipe.BYTE_IDS
    assert pipe.bucket_tag(2, P, T, "greedy") == f"trinity.2.{P}.{T}.greedy"
    assert pipe.kv_rows(P, T) == (4 * 8 + P + T, 5 * (P + T))
    with pytest.raises(ValueError, match="bf16 only"):
        TrinityPipeline(cfg, precision="int8")
    with pytest.raises(ValueError, match="byte"):
        TrinityPipeline(TrinityConfig.tiny(vocab_rows=(256, 512)))


def test_trinity_graph_goldens_and_rules_are_clean():
    """The four goldened programs (prefill, decode greedy and top-k, the
    composed bucket) trace to their checked-in goldens with no GRAPH4xx
    finding: no scatter-add, float32 router and combine, PRNG from the
    seed inputs. The trinity specs alone, so the check does not depend
    on the mesh families' abstract meshes."""
    from arbius_tpu.analysis import graph
    from arbius_tpu.models.trinity.pipeline import trace_specs

    specs = trace_specs()
    assert sorted(s.key for s in specs) == [
        "trinity.decode.b2.p12.t4.greedy.single.bfloat16",
        "trinity.decode.b2.p12.t4.top_k.single.bfloat16",
        "trinity.generate.b2.p12.t4.greedy.single.bfloat16",
        "trinity.prefill.b2.p12.t4.single.bfloat16"]
    assert graph.audit(specs) == []


def test_bucket_edges_per_text_template():
    cfg = load_config({"textgen": {
        "prompt_buckets": [32, 64], "decode_buckets": [16, 32],
        "templates": {"trinity": {"prompt_buckets": [8192],
                                  "decode_buckets": [256],
                                  "max_new_tokens": 256}},
        "share": {"experts_held": [0, 32]}}})
    tg = cfg.textgen
    assert tg.for_template("textgen") is tg
    tri = tg.for_template("trinity")
    assert (tri.prompt_buckets, tri.decode_buckets, tri.max_new_tokens) \
        == ((8192,), (256,), 256)
    assert tri.top_k == tg.top_k and tri.share == {"experts_held": [0, 32]}
    with pytest.raises(ConfigError, match="max_new_tokens"):
        load_config({"textgen": {"templates": {"trinity": {
            "decode_buckets": [64], "max_new_tokens": 256}}}})
    with pytest.raises(ConfigError, match="trinity"):
        load_config({"textgen": {"templates": {"trinity": {"edges": 1}}}})
    # a share states which experts, rows and layers: no program shape
    with pytest.raises(ConfigError, match="expert_tile"):
        load_config({"textgen": {"share": {"expert_tile": [8, 8]}}})


def test_factory_builds_the_trinity_runner_from_the_template_block():
    from arbius_tpu.node.factory import build_registry

    mid = "0x" + "7a" * 32
    cfg = load_config({
        "models": [{"id": mid, "template": "trinity", "tiny": True,
                    "weights_dtype": "bfloat16"}],
        "textgen": {"templates": {"trinity": {
            "prompt_buckets": [P], "decode_buckets": [T],
            "max_new_tokens": T}},
            "share": {"experts_held": [2, 6]}}})
    runner = build_registry(cfg).get(mid).runner
    assert isinstance(runner, TextGenRunner)
    assert runner.pipeline.FAMILY == "trinity"
    assert runner.pipeline.prompt_buckets == (P,)
    assert runner.pipeline.config.experts_held == (2, 6)
    gate = runner.params["layer_1"]["moe"]["experts"]["gate"]["kernel"]
    assert gate.shape == (4, 32, 16) and gate.dtype == jnp.bfloat16
    assert "bias" not in runner.params["head"]      # untied, no bias
    bad = load_config({
        "models": [{"id": mid, "template": "trinity", "tiny": True}],
        "textgen": {"share": {"experts_held": [6, 2]}}})
    with pytest.raises(ConfigError, match="experts_held"):
        build_registry(bad)


def _world(pipe, params, pipeline_on):
    from test_textgen import _text_world   # the text families' node world

    eng, node, mid, user = _text_world(pipe, params, pipeline_on=pipeline_on,
                                       template="trinity")
    while node.tick():
        pass
    for i in range(3):       # a full bucket and a padded one
        obj = {"prompt": f"trinity task {i}", "max_new_tokens": (T, 2)[i % 2]}
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
    cfg = TrinityConfig.tiny()

    def fresh():
        return TrinityPipeline(cfg, prompt_buckets=(P, 32),
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
                a["batch"]) == ("trinity", 32, T, 2)
        assert (a["kv_rows"], a["kv_rows_full"]) == cfg.kv_rows(32 + T)
        # off the TPU the walk serves every prefill attention call: the
        # kernel's three counts are there and read nothing
        assert (a["attn_kernel_calls"], a["attn_blocks"],
                a["attn_blocks_dense"]) == (0, 0, 0)
        made = 2 * (32 + T - 1) * 2 * 4
        assert all(s["attrs"]["assignments"] == s["attrs"]["held"] == made
                   for s in routed)
        assert f'arbius_moe_assignments_total{{held="yes"}} {2 * made}' \
            in text
        assert 'arbius_moe_assignments_total{held="no"} 0' in text
        assert f'arbius_text_tokens_total{{phase="prefill"}} {2 * 2 * 32}' \
            in text
        assert f'arbius_text_tokens_total{{phase="decode"}} {2 * 2 * T}' \
            in text
