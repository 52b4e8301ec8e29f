"""joyai_llm_flash tier-1 suite (docs/text-serving.md): JoyAI-LLM-Flash
with its multi-token prediction module at a tiny size on the CPU against
the benchmark's plain float32 reference
(perfbench/reference/joyai_llm_flash.py, which imports nothing of the
program) on seeded weights: prefill then two-position steps through the
latent caches against one full forward pass, main logits and the
module's; the speculative program's tokens against a ONE-token loop
(which this family serves nowhere: it lives here and in
tools/joyai_diag.py); constructed weights under which every draft is
right; rows of unequal progress; the loop's counts; the code shared with
deepseek_v32, tied; the goldens; and CIDs through a real MinerNode."""
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

from arbius_tpu.models.deepseek_v32 import DeepSeekV32Config
from arbius_tpu.models.deepseek_v32 import model as dsv32
from arbius_tpu.models.joyai_flash import JoyAIFlashConfig, JoyAIFlashPipeline
from arbius_tpu.models.joyai_flash import model as joyai
from arbius_tpu.models.joyai_flash import pipeline as joyai_pipeline
from arbius_tpu.models.textgen.pipeline import _fold_keys
from arbius_tpu.node.config import load_config
from arbius_tpu.node.solver import TextGenRunner
from perfbench.reference import joyai_llm_flash as reference
from perfbench.reference import ops

P, T = 12, 7
PROMPTS = ["a miner asks", "the chip", "for a line"]
SEEDS = [11, 2**40 + 5, 7]


def _params(cfg, seed=0, dtype=None):
    p = joyai.init_params(cfg, jax.random.PRNGKey(seed))
    # gains and the router's bias away from their neutral init, so that
    # one left out cannot hide
    flat, treedef = jax.tree_util.tree_flatten_with_path(p)
    out = []
    for i, (path, x) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        if name in ("scale", "expert_bias"):
            x = x + 0.1 * jax.random.normal(k, x.shape)
        if name == "embedding":
            x = x * 50.0      # N(0, 1): the token leads the stream
        out.append(x.astype(dtype) if dtype else x)
    return jax.tree_util.tree_unflatten(treedef, out)


def _arch(cfg, p=P, t=T):
    return {"model": {k: list(v) if isinstance(v, tuple) else v
                      for k, v in dataclasses.asdict(cfg).items()},
            "prompt_buckets": [p], "decode_buckets": [t], "top_k": 4,
            "tokenizer": {"kind": "byte", "bos_id": 257, "eos_id": 258}}


def _pipe(cfg, p=P, t=T):
    return JoyAIFlashPipeline(cfg, prompt_buckets=(p,), decode_buckets=(t,),
                              top_k=4)


def _program_logits(cfg, params, ids, stride: int):
    """Teacher-forced through the program's own split — prefill on the
    first P ids, then TWO-position steps through the caches — → (main
    logits [B, T, V] for tokens 0 .. T-1, the module's [B, T-1, V] at
    positions P-1 .. P+T-3). `stride` 2 feeds each step the right next
    id as its draft and advances two positions (every draft accepted);
    `stride` 1 feeds it a WRONG one and advances one, so every step
    leaves a rejected draft's rows behind in both caches."""
    b, p = ids.shape[0], ids.shape[1] - T + 1
    served = ids[:, p - 1:]        # served[:, n + 1] is token n
    logits0, (caches, mtp_cache, h_last, _) = joyai.prefill(
        params, ids[:, :p], p + T, cfg)
    main = {0: logits0}
    guess, mtp_cache, _ = joyai.draft(
        params, ids[:, p:p + 1], h_last[:, None], mtp_cache,
        jnp.full((b,), p - 1, jnp.int32), cfg)
    module = {p - 1: guess[:, 0]}
    for n in range(1, T, stride):
        q = jnp.full((b,), p + n - 1, jnp.int32)
        right = served[:, min(n + 1, T - 1)]
        second = right if stride == 2 else (right + 101) % 256
        lg, h, caches, _ = joyai.step(
            params, jnp.stack([served[:, n], second], axis=1), caches, q,
            cfg)
        main[n] = lg[:, 0]
        # the module at q takes the token AFTER q: token n, which the
        # teacher knows only while n < T - 1 (ids end at token T - 2)
        nxt = jnp.stack([served[:, min(n + 1, T - 1)],
                         served[:, min(n + 2, T - 1)]], axis=1)
        guess, mtp_cache, _ = joyai.draft(params, nxt, h, mtp_cache, q, cfg)
        if n < T - 1:
            module[p + n - 1] = guess[:, 0]
        if stride == 2 and n + 1 < T:
            main[n + 1] = lg[:, 1]
            if n + 1 < T - 1:
                module[p + n] = guess[:, 1]
    return (jnp.stack([main[n] for n in range(T)], axis=1),
            jnp.stack([module[i] for i in range(p - 1, p + T - 2)], axis=1),
            caches, mtp_cache)


def _reference_logits(cfg, params, ids, weights=None):
    """(main [B, T, V], module [B, T-1, V]) by the plain reference, one
    full forward pass a sequence."""
    arch = _arch(cfg)["model"]

    def both(p, row):
        x = reference.hidden(p, row, arch)
        xm = reference.layer(p["mtp"]["layer"],
                             reference.mtp_in(p, row[1:], x[:-1], arch),
                             "moe", arch)
        n = row.shape[0] - T + 1
        return (reference.head(p, x[n - 1:], arch),
                reference.mtp_head(p, xm[n - 1:], arch))

    fn = jax.jit(ops.traced_with(both, weights))
    main, module = zip(*(fn(params, row) for row in ids))
    return jnp.stack(main), jnp.stack(module)


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 4 rows in the program's prefill (3 to the prompt) and
    in the reference, head groups of 2, experts in groups of 4, so that
    every loop over blocks runs more than once."""
    monkeypatch.setattr(dsv32, "_SCORE_BYTES", 4 * 4 * 4 * 4)
    from perfbench.reference import deepseek_v32 as ref_dsv32

    monkeypatch.setattr(ref_dsv32, "ROW_BLOCK", 4)
    monkeypatch.setattr(ref_dsv32, "COARSE", 2)
    monkeypatch.setattr(reference, "HEAD_GROUP", 2)
    monkeypatch.setattr(reference, "EXPERT_GROUP", 4)


@pytest.mark.parametrize("stride", [1, 2])
def test_prefill_then_two_position_steps_match_the_full_forward(
        stride, small_blocks):
    """float32 program against the float32 reference: only the order of
    sums differs (blocks, the running softmax, the latent form, grouped
    tiles) — 1e-4 of logits whose spread is about 1. At stride 1 every
    step writes a rejected draft's rows at q + 1 in both caches: were
    one ever read by a later softmax, the logits would be off by
    tenths."""
    cfg = JoyAIFlashConfig.tiny(dtype="float32")
    params = _params(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + T - 1), 0, 256)
    assert joyai._block(P, cfg.heads) == 4
    main, module, caches, mtp_cache = jax.jit(
        lambda p, i: _program_logits(cfg, p, i, stride))(params, ids)
    want_main, want_module = _reference_logits(cfg, params, ids)
    assert main.shape == want_main.shape == (2, T, cfg.n_vocab)
    assert module.shape == want_module.shape == (2, T - 1, cfg.n_vocab)
    assert float(jnp.abs(main - want_main).max()) < 1e-4
    assert float(jnp.abs(module - want_module).max()) < 1e-4
    # the carry: a latent row a position a layer, the module's beside
    assert len(caches) == len(cfg.layers)
    for lat in (*caches, mtp_cache):
        assert lat.shape == (2, P + T, cfg.cache_width)


def test_bfloat16_stays_within_a_bound_that_a_float8_pass_does_not(
        small_blocks):
    """bfloat16 as served, two layers and the module deep, against the
    float32 reference on the same weights: the mean distance of a logit,
    in units of the logits' spread, stays under 0.05 for the main model
    and for the module; the reference itself computed with float8
    kernels and inputs (the benchmark's control) is over it for both.
    A mean and not a maximum: a router's choice a rounding apart moves
    single logits by tenths in either precision."""
    cfg = JoyAIFlashConfig.tiny(dtype="bfloat16", layers=("dense", "moe"))
    params = _params(cfg, dtype="bfloat16")
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + T - 1), 0, 256)
    main, module, _, _ = jax.jit(
        lambda p, i: _program_logits(cfg, p, i, 2))(params, ids)
    want = _reference_logits(cfg, params, ids)
    low = _reference_logits(cfg, params, ids, weights="fp8")

    def dist(got, ref):
        return float(jnp.abs(got - ref).mean() / ref.std())

    for got, ref, control in zip((main, module), want, low):
        assert dist(got, ref) < 0.05 < dist(control, ref)


def _one_token_tokens(pipe, params, prompts, seeds, sampler, p=P, t=T):
    """The tokens of one-token-a-step decoding with the bucket's
    sampler: prefill, then the main model alone, one position a step."""
    cfg = pipe.config
    ids = jnp.asarray(pipe._tokenizer(p).encode_batch(prompts))
    seeds = np.asarray(seeds, dtype=np.uint64)
    keys = _fold_keys(jnp.asarray(seeds & 0xFFFFFFFF, jnp.uint32),
                      jnp.asarray(seeds >> np.uint64(32), jnp.uint32))
    sample = pipe._sampler_fn(sampler)
    logits, (caches, _, _, _) = joyai.prefill(params, ids, p + t, cfg)
    tok = sample(logits, keys, 0)
    out = [tok]
    for i in range(1, t):
        lg, _, caches, _ = joyai.step(
            params, tok[:, None], caches,
            jnp.full((len(prompts),), p + i - 1, jnp.int32), cfg)
        tok = sample(lg[:, 0], keys, i)
        out.append(tok)
    return np.stack([np.asarray(x) for x in out], axis=1)


@pytest.mark.parametrize("sampler", ["greedy", "top_k"])
def test_speculative_tokens_are_the_one_token_loops_whatever_the_module(
        sampler):
    """float32: the program's tokens equal one-token-a-step decoding for
    both samplers (the key folded by the token's index), and a redrawn
    module — every leaf under `mtp` from another seed — changes no
    token: a draft is taken only where it IS the sampler's choice."""
    cfg = JoyAIFlashConfig.tiny(dtype="float32")
    pipe = _pipe(cfg)
    params = _params(cfg)
    want = _one_token_tokens(pipe, params, PROMPTS, SEEDS, sampler)
    kw = dict(prompts=PROMPTS, seeds=SEEDS, prompt_bucket=P,
              decode_bucket=T, sampler=sampler)
    got, routed, spec = pipe.generate(params, **kw)
    assert np.array_equal(got, want) and got.max() < pipe.BYTE_IDS
    steps, drafts, accepted, idle = (int(x) for x in spec)
    assert T - 1 - accepted <= steps <= T - 1
    assert 3 * (T - 1) == 3 * steps - idle + accepted
    # every main layer and the module route: all held, all counted
    made = 3 * 2 * 4 * (P + 2 * steps) + 3 * 2 * (1 + 2 * steps)
    assert int(routed[0]) == int(routed[1]) == made
    redrawn = dict(params, mtp=_params(cfg, seed=9)["mtp"])
    assert np.array_equal(pipe.generate(redrawn, **kw)[0], want)


def _bigram_params(cfg, noise: float = 0.0):
    """Constructed weights: every layer adds nothing (`wo` and the down
    projections zeroed), so the main model is a bigram map head(norm(
    embed(t))); the module reads the SAME map off the next token
    (`eh_proj` = [I ; 0], its layer zeroed, its norm the main one), so
    its draft of token i+2 is what the main model will say after token
    i+1. `noise` perturbs the module's norm gains: the draft is then
    right for some tokens and wrong for others."""
    params = _params(cfg)
    d = cfg.hidden

    def mute(layer):
        layer["attn"]["wo"]["kernel"] = jnp.zeros_like(
            layer["attn"]["wo"]["kernel"])
        for mlp in ([layer["mlp"]] if "mlp" in layer else
                    [layer["moe"]["shared"], layer["moe"]["experts"]]):
            mlp["down"]["kernel"] = jnp.zeros_like(mlp["down"]["kernel"])
        return layer

    for i in range(len(cfg.layers)):
        params[f"layer_{i}"] = mute(params[f"layer_{i}"])
    mp = params["mtp"]
    mp["layer"] = mute(mp["layer"])
    mp["eh_proj"]["kernel"] = jnp.concatenate(
        [jnp.eye(d), jnp.zeros((d, d))]).astype(jnp.float32)
    mp["enorm"]["scale"] = jnp.ones((d,), jnp.float32)
    gains = params["final_norm"]["scale"]
    mp["norm"]["scale"] = gains + noise * jax.random.normal(
        jax.random.PRNGKey(5), gains.shape)
    return params


def test_drafts_that_are_always_right_halve_the_steps():
    """With the constructed weights every verified draft is accepted: the
    loop takes ceil((T - 1) / 2) steps for T tokens and the bytes are the
    one-token loop's."""
    cfg = JoyAIFlashConfig.tiny(dtype="float32")
    for t in (T, T + 1):
        pipe = _pipe(cfg, t=t)
        params = _bigram_params(cfg)
        got, _, spec = pipe.generate(params, prompts=PROMPTS, seeds=SEEDS,
                                     prompt_bucket=P, decode_bucket=t)
        assert np.array_equal(
            got, _one_token_tokens(pipe, params, PROMPTS, SEEDS, "greedy",
                                   t=t))
        steps, drafts, accepted, idle = (int(x) for x in spec)
        assert steps == -(-(t - 1) // 2) and idle == 0
        assert drafts == accepted == 3 * ((t - 1) // 2)


def test_rows_of_unequal_progress_are_exact_and_independent():
    """Seeded top-k over the constructed weights: the module drafts the
    main model's first choice and a row's sampler takes it or not by the
    row's own key, so rows advance at different rates, write their cache
    rows at their own offsets, finish at different steps and idle until
    the slowest has its tokens. The bytes are still the one-token loop's;
    a row's bytes do not depend on which rows share its bucket; the
    counts add up: tokens after the first = steps run - idle steps +
    accepted drafts, bucket by bucket and for a row alone."""
    cfg = JoyAIFlashConfig.tiny(dtype="float32")
    t = 24
    pipe = _pipe(cfg, t=t)
    params = _bigram_params(cfg)
    kw = dict(prompt_bucket=P, decode_bucket=t, sampler="top_k")
    got, _, spec = pipe.generate(params, prompts=PROMPTS, seeds=SEEDS, **kw)
    assert np.array_equal(
        got, _one_token_tokens(pipe, params, PROMPTS, SEEDS, "top_k", t=t))
    assert len({tuple(r) for r in got.tolist()}) == 3
    steps, drafts, accepted, idle = (int(x) for x in spec)
    assert 0 < accepted < drafts and idle > 0
    assert -(-(t - 1) // 2) < steps < t - 1
    assert 3 * (t - 1) == 3 * steps - idle + accepted
    # each row in another bucket (the other rows other prompts and
    # seeds): the same bytes
    for i, prompt in enumerate(PROMPTS):
        alone, _, _ = pipe.generate(
            params, prompts=[prompt, "something else", "and another"],
            seeds=[SEEDS[i], 3, 4], **kw)
        assert np.array_equal(alone[0], got[i])
    # a row three times over is a bucket of one pace: its own counts
    same, _, one = pipe.generate(params, prompts=[PROMPTS[0]] * 3,
                                 seeds=[SEEDS[0]] * 3, **kw)
    s1, _, a1, i1 = (int(x) for x in one)
    assert i1 == 0 and a1 % 3 == 0 and t - 1 == s1 + a1 // 3
    assert np.array_equal(same[0], got[0])


def test_a_loop_that_takes_the_second_token_after_a_rejected_draft_is_wrong(
        monkeypatch):
    """The fault the benchmark keeps as a test
    (tests/perfbench/test_joyai_rehearsal.py), here at the model: with
    `accept` ignoring whether the draft was the sampler's choice, tokens
    come from logits computed on a token that was never served."""
    cfg = JoyAIFlashConfig.tiny(dtype="float32")
    params = _params(cfg)
    kw = dict(prompts=PROMPTS, seeds=SEEDS, prompt_bucket=P, decode_bucket=T)
    want = _pipe(cfg).generate(params, **kw)[0]
    monkeypatch.setattr(joyai_pipeline, "accept",
                        lambda t_n, drafted, room: room)
    got, _, spec = _pipe(cfg).generate(params, **kw)
    assert int(spec[0]) == -(-(T - 1) // 2)
    assert not np.array_equal(got, want)
    assert np.array_equal(got[:, :2], want[:, :2])     # t0 and t1 are sound


def test_latent_attention_without_selection_is_deepseek_v32s_on_the_same_weights():
    """The shared code, tied: deepseek_v32's prefill and decode with
    `index_topk` past the length (its selection keeps every causal key),
    one routing group and no rotary scaling, on this family's main-layer
    weights with any indexer beside them, give this family's prefill and
    one-position steps — the same functions, so to the last bit."""
    cfg = JoyAIFlashConfig.tiny(dtype="float32")
    twin = DeepSeekV32Config.tiny(
        dtype="float32", index_topk=64, n_group=1, topk_group=1,
        rope_theta=cfg.rope_theta, rope_factor=1.0, rope_original=4096)
    assert twin.softmax_scale == cfg.softmax_scale
    assert np.array_equal(dsv32.yarn_freqs(twin), dsv32.yarn_freqs(cfg))
    assert np.allclose(
        dsv32.yarn_freqs(cfg),
        cfg.rope_theta ** (-2.0 * np.arange(2) / cfg.qk_rope_head_dim))
    params = _params(cfg)
    theirs = dsv32.init_params(twin, jax.random.PRNGKey(4))
    theirs = {k: ({**v, **{n: params[k][n] for n in params[k]}}
                  if k.startswith("layer_") else params[k])
              for k, v in theirs.items()}
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + 2), 0, 256)
    lg, (caches, _, h_last, _) = joyai.prefill(params, ids[:, :P], P + T,
                                               cfg)
    lg2, carry = dsv32.prefill(theirs, ids[:, :P], P + T, twin)
    assert np.array_equal(lg, lg2)
    for i in range(2):
        lg, _, caches, _ = joyai.step(
            params, ids[:, P + i:P + i + 1], caches,
            jnp.full((2,), P + i, jnp.int32), cfg)
        lg2, carry = dsv32.decode(theirs, ids[:, P + i], carry,
                                  jnp.int32(P + i), twin)
        assert np.allclose(lg[:, 0], lg2, atol=1e-6)
        for mine, (lat, _) in zip(caches, carry[0]):
            assert np.allclose(mine, lat, atol=1e-6)


def test_published_widths_and_the_cells_static_counts():
    cfg = JoyAIFlashConfig.published()
    assert (len(cfg.layers), cfg.layers[:2], cfg.hidden, cfg.heads,
            cfg.cache_width, cfg.qk_head_dim, cfg.num_experts,
            cfg.experts_per_token, cfg.n_vocab) \
        == (40, ("dense", "moe"), 2048, 32, 576, 192, 256, 8, 129280)
    assert cfg.softmax_scale == 192 ** -0.5
    cell = dataclasses.replace(cfg, layers=("dense",) + ("moe",) * 4,
                               vocab_rows=(0, 16160))
    shapes = joyai.param_shapes(cell)
    n = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert n == 6_342_751_488
    assert "indexer" not in shapes["layer_1"]
    assert set(shapes["mtp"]) == {"enorm", "hnorm", "eh_proj", "layer",
                                  "norm"}
    assert shapes["mtp"]["eh_proj"]["kernel"] == (4096, 2048)
    # 32 sequences x 2,560 positions x (5 + 1) layers x 1,152 B
    assert 32 * cell.cache_bytes(2048 + 512) == 566_231_040
    with pytest.raises(ValueError, match="experts_held"):
        JoyAIFlashConfig.tiny(experts_held=(6, 2))
    with pytest.raises(ValueError, match="vocab_rows"):
        JoyAIFlashConfig.tiny(vocab_rows=(0, 9999))
    with pytest.raises(ValueError, match="unknown layer kind"):
        JoyAIFlashConfig.tiny(layers=("dense", "sparse"))


def test_the_family_serves_one_program_and_says_so():
    cfg = JoyAIFlashConfig.tiny()
    pipe = _pipe(cfg)
    assert pipe.bucket_tag(2, P, T, "greedy") \
        == f"joyai_llm_flash.2.{P}.{T}.greedy"
    with pytest.raises(NotImplementedError, match="two-position"):
        pipe._decode(None, None, None, 0)
    attrs = pipe.bucket_attrs(2, P, T)
    # off the TPU the tile loop walks every routed call: prefill's one
    # block a layer a sequence, the first draft, then at most T - 1
    # steps of four expert layers and the module
    assert attrs == {"latent_bytes": cfg.cache_bytes(P + T),
                     "attn_kernel_calls": 0, "attn_blocks": 0,
                     "attn_blocks_dense": 0, "expert_calls_grouped": 0,
                     "expert_calls_loop": 2 * 4 + 1 + (T - 1) * 5}
    with pytest.raises(ValueError, match="bf16 only"):
        JoyAIFlashPipeline(cfg, precision="int8")
    with pytest.raises(ValueError, match="joyai_llm_flash ships no mesh"):
        JoyAIFlashPipeline(cfg, mesh=object())
    # no knob: the node's config has no key that picks another loop
    from arbius_tpu.node.config import ConfigError

    for bad in ("speculative", "drafts", "mtp"):
        with pytest.raises(ConfigError):
            load_config({"textgen": {"templates": {"joyai_llm_flash": {
                bad: False}}}})
        with pytest.raises(ConfigError, match=bad):
            load_config({"textgen": {"share": {bad: 0}}})


def test_joyai_graph_goldens_and_rules_are_clean():
    """The four goldened programs (prefill with the module's cache rows,
    the speculative loop greedy and top-k, the composed bucket) trace to
    their checked-in goldens with no GRAPH4xx finding; through the
    library (the graphlint CLI is red on jax 0.9.0: ROADMAP D0)."""
    from arbius_tpu.analysis import graph
    from arbius_tpu.models.joyai_flash.pipeline import trace_specs

    specs = trace_specs()
    assert sorted(s.key for s in specs) == [
        "joyai_llm_flash.decode.b2.p12.t4.greedy.single.bfloat16",
        "joyai_llm_flash.decode.b2.p12.t4.top_k.single.bfloat16",
        "joyai_llm_flash.generate.b2.p12.t4.greedy.single.bfloat16",
        "joyai_llm_flash.prefill.b2.p12.t4.single.bfloat16"]
    assert graph.audit(specs) == []


def test_factory_builds_the_runner_from_the_template_block():
    from arbius_tpu.node.factory import build_registry

    mid = "0x" + "7c" * 32
    cfg = load_config({
        "models": [{"id": mid, "template": "joyai_llm_flash", "tiny": True,
                    "weights_dtype": "bfloat16"}],
        "textgen": {"templates": {"joyai_llm_flash": {
            "prompt_buckets": [P], "decode_buckets": [T],
            "max_new_tokens": T}},
            "share": {"layers": ["dense", "moe"]}}})
    tg = cfg.textgen.for_template("joyai_llm_flash")
    assert (tg.prompt_buckets, tg.decode_buckets, tg.max_new_tokens) \
        == ((P,), (T,), T)
    runner = build_registry(cfg).get(mid).runner
    assert isinstance(runner, TextGenRunner)
    assert isinstance(runner.pipeline, JoyAIFlashPipeline)
    assert runner.pipeline.config.layers == ("dense", "moe")
    gate = runner.params["mtp"]["layer"]["moe"]["experts"]["gate"]["kernel"]
    assert gate.shape == (16, 32, 16) and gate.dtype == jnp.bfloat16
    assert "indexer" not in runner.params["layer_0"]


def _world(pipe, params, pipeline_on):
    from test_textgen import _text_world   # the text families' node world

    eng, node, mid, user = _text_world(pipe, params, pipeline_on=pipeline_on,
                                       template="joyai_llm_flash")
    while node.tick():
        pass
    for i in range(3):       # a full bucket and a padded one
        obj = {"prompt": f"joyai task {i}", "max_new_tokens": (T, 2)[i % 2]}
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
    """The node path end to end: a devnet task in, MinerNode.tick() ->
    TextGenRunner -> the speculative bucket program, commitment and reveal
    landed; the same tasks, the same CIDs twice;
    the loop's counts on `text.speculate` and on the counters."""
    cfg = JoyAIFlashConfig.tiny()

    def fresh():
        return JoyAIFlashPipeline(cfg, prompt_buckets=(P, 32),
                                  decode_buckets=(T,), top_k=4)

    params = fresh().init_params(seed=0, dtype="bfloat16")
    off, text, spans = _world(fresh(), params, False)
    on, text_on, spans_on = _world(fresh(), params, True)
    assert len(off) == 3 and on == off
    for text, spans in ((text, spans), (text_on, spans_on)):
        bucket = [s for s in spans if s["name"] == "text.bucket"]
        spec = [s for s in spans if s["name"] == "text.speculate"]
        routed = [s for s in spans if s["name"] == "text.routed"]
        assert len(bucket) == len(spec) == len(routed) == 2
        a = bucket[0]["attrs"]
        assert (a["model"], a["prompt_bucket"], a["decode_bucket"],
                a["batch"]) == ("joyai_llm_flash", 32, T, 2)
        assert a["latent_bytes"] == cfg.cache_bytes(32 + T)
        steps = drafts = accepted = 0
        for s in spec:
            a = s["attrs"]
            assert (a["model"], a["batch"], a["tokens"]) \
                == ("joyai_llm_flash", 2, 2 * T)
            # the rows' tokens after the first, from the loop's counts
            assert a["batch"] * (T - 1) == a["batch"] * a["steps"] \
                - a["idle_row_steps"] + a["accepted"]
            assert a["accepted"] <= a["drafts"] <= 2 * a["steps"]
            steps += a["steps"]
            drafts += a["drafts"]
            accepted += a["accepted"]
        assert f"arbius_text_decode_steps_total {steps}" in text
        assert 'arbius_text_spec_drafts_total{outcome="accepted"} ' \
            f"{accepted}" in text
        assert 'arbius_text_spec_drafts_total{outcome="rejected"} ' \
            f"{drafts - accepted}" in text
        assert all(s["attrs"]["assignments"] == s["attrs"]["held"] > 0
                   for s in routed)
