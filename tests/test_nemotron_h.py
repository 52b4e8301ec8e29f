"""nemotron_h tier-1 suite (docs/text-serving.md): Nemotron 3 Super with
its multi-token prediction module at a tiny size on the CPU against the
benchmark's plain float32 reference (perfbench/reference/nemotron_h.py,
which imports nothing of the program) on seeded weights: the chunked
scan against the step-by-step recurrence; prefill, and prefill then
two-position steps through the carry, against one full forward pass;
a forced reject and a forced accept against one-position stepping; the
speculative program's tokens against a ONE-token loop (which exists here
only); four shares of the routed experts against the uncut layer; the
squared-ReLU experts on both walks; the other families' SwiGLU lowering
against the code before the expert form became an argument; the goldens;
and CIDs through a real MinerNode."""
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

from arbius_tpu.models.nemotron_h import NemotronHConfig, NemotronHPipeline
from arbius_tpu.models.nemotron_h import model as nemotron
from arbius_tpu.models.textgen.pipeline import _fold_keys
from arbius_tpu.models.trinity import model as trinity
from arbius_tpu.node.config import load_config
from arbius_tpu.node.solver import TextGenRunner
from arbius_tpu.ops import grouped
from perfbench.reference import nemotron_h as reference
from perfbench.reference import ops

P, T = 10, 7          # a prompt of two and a half chunks of 4
PROMPTS = ["a miner asks", "the chip", "for a line"]
SEEDS = [11, 2**40 + 5, 7]


def _params(cfg, seed=0, dtype=None):
    p = nemotron.init_params(cfg, jax.random.PRNGKey(seed))
    # gains and the router's bias away from their neutral init, so that
    # one left out cannot hide
    flat, treedef = jax.tree_util.tree_flatten_with_path(p)
    out = []
    for i, (path, x) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        if name in ("scale", "expert_bias", "conv_bias"):
            x = x + 0.1 * jax.random.normal(k, x.shape)
        if name == "embedding":
            x = x * 50.0      # N(0, 1): the token leads the stream
        out.append(x.astype(dtype) if dtype else x)
    return jax.tree_util.tree_unflatten(treedef, out)


def _arch(cfg):
    return {k: list(v) if isinstance(v, tuple) else v
            for k, v in dataclasses.asdict(cfg).items()}


def _pipe(cfg, p=P, t=T):
    return NemotronHPipeline(cfg, prompt_buckets=(p,), decode_buckets=(t,),
                             top_k=4)


def _program_logits(cfg, params, ids, stride: int):
    """Teacher-forced through the program's own split — prefill on the
    first P ids, then TWO-position steps through the carry, each
    committed — → (main logits [B, T, V] for tokens 0 .. T-1, the
    module's [B, T-1, V] at positions P-1 .. P+T-3, caches). `stride` 2
    feeds each step the right next id as its draft and commits both
    positions (every draft taken); `stride` 1 feeds it a WRONG one and
    commits the first, so every step leaves a rejected draft behind: a
    K/V row at q + 1 and a state after it, which the commit must drop."""
    b, p = ids.shape[0], ids.shape[1] - T + 1
    served = ids[:, p - 1:]        # served[:, n + 1] is token n
    logits0, (caches, mtp_cache, h_last, _) = nemotron.prefill(
        params, ids[:, :p], p + T, cfg)
    main = {0: logits0}
    guess, mtp_cache, _ = nemotron.draft(
        params, ids[:, p:p + 1], h_last[:, None], mtp_cache,
        jnp.full((b,), p - 1, jnp.int32), cfg)
    module = {p - 1: guess[:, 0]}
    for n in range(1, T, stride):
        q = jnp.full((b,), p + n - 1, jnp.int32)
        right = served[:, min(n + 1, T - 1)]
        second = right if stride == 2 else (right + 101) % 256
        lg, h, caches, _ = nemotron.step(
            params, jnp.stack([served[:, n], second], axis=1), caches, q,
            cfg)
        caches = nemotron.commit(caches, jnp.full((b,), stride == 2), cfg)
        main[n] = lg[:, 0]
        nxt = jnp.stack([served[:, min(n + 1, T - 1)],
                         served[:, min(n + 2, T - 1)]], axis=1)
        guess, mtp_cache, _ = nemotron.draft(params, nxt, h, mtp_cache, q,
                                             cfg)
        if n < T - 1:
            module[p + n - 1] = guess[:, 0]
        if stride == 2 and n + 1 < T:
            main[n + 1] = lg[:, 1]
            if n + 1 < T - 1:
                module[p + n] = guess[:, 1]
    return (jnp.stack([main[n] for n in range(T)], axis=1),
            jnp.stack([module[i] for i in range(p - 1, p + T - 2)], axis=1),
            caches)


def _reference_logits(cfg, params, ids, weights=None):
    """(main [B, T, V], module [B, T-1, V]) by the plain reference, one
    full forward pass a sequence."""
    arch = _arch(cfg)

    def both(p, row):
        x = reference.hidden(p, row, arch)
        xm = reference.mtp_layers(p, reference.mtp_in(p, row[1:], x[:-1],
                                                      arch), arch)
        n = row.shape[0] - T + 1
        return (reference.head(p, x[n - 1:], arch),
                reference.mtp_head(p, xm[n - 1:], arch))

    fn = jax.jit(ops.traced_with(both, weights))
    main, module = zip(*(fn(params, row) for row in ids))
    return jnp.stack(main), jnp.stack(module)


# -- the state-space layer ---------------------------------------------------
@pytest.mark.parametrize("p", [3, 9, 12, 13])
def test_chunked_scan_matches_the_step_by_step_recurrence(p):
    """`ssd_chunked` at chunks of 4 against s_t = exp(dt_t·A)·s_{t-1} +
    dt_t·x_t⊗B_t, y_t = s_t·C_t one position at a time, in float32: only
    the order of sums differs (1e-5 of outputs of order 1), prompts of
    whole and of partial chunks, the state after the last position
    included."""
    g, r, pd, n = 2, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(p), 5)
    x = jax.random.normal(ks[0], (p, g, r, pd))
    b = jax.random.normal(ks[1], (p, g, n))
    c = jax.random.normal(ks[2], (p, g, n))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (p, g, r)) - 1.0)
    a = -jax.random.uniform(ks[4], (g, r), minval=1.0, maxval=16.0)
    y, last = nemotron.ssd_chunked(x, dt, a, b, c, 4)
    s = np.zeros((g, r, pd, n), np.float32)
    want = []
    for t in range(p):
        s = np.exp(np.asarray(dt[t] * a))[..., None, None] * s \
            + np.asarray(dt[t][..., None] * x[t])[..., None] \
            * np.asarray(b[t])[:, None, None, :]
        want.append((s * np.asarray(c[t])[:, None, None, :]).sum(-1))
    assert y.shape == (p, g, r, pd) and last.shape == s.shape
    assert np.allclose(y, np.stack(want), atol=1e-5, rtol=1e-5)
    assert np.allclose(last, s, atol=1e-5, rtol=1e-5)


def test_a_mamba_layers_prefill_is_its_step_one_position_at_a_time():
    """One layer on a 10-position sequence: the chunked prefill's output,
    final state and conv window against the decode step run from a zero
    state and window one position at a time, float32."""
    cfg = NemotronHConfig.tiny(dtype="float32")
    lp = _params(cfg)["layer_0"]
    x = jax.random.normal(jax.random.PRNGKey(2), (P, cfg.hidden))
    out, state, window = nemotron._mamba_prefill(x, lp, cfg)
    cache = nemotron._mamba_cache(
        jnp.zeros((1, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)),
        jnp.zeros((1, cfg.conv_kernel - 1, cfg.conv_dim)), cfg)
    outs = []
    for t in range(P):
        o, cache = nemotron._mamba_step(x[None, t:t + 1], lp, cache, cfg)
        outs.append(o[0, 0])
    st, win = nemotron.settle(cache, cfg)
    assert np.allclose(out, jnp.stack(outs), atol=1e-5)
    assert np.allclose(state, st[0], atol=1e-5)
    assert np.array_equal(window, win[0])


def test_the_published_ssm_initialisation():
    """A = exp(A_log) in [1, 16], dt = softplus(dt_bias) in [dt_min,
    dt_max], D = 1: the decays a trained model's lie among, not N(0, σ²)."""
    cfg = NemotronHConfig.tiny(mamba_heads=64, n_groups=8)
    mp = nemotron.init_params(cfg, jax.random.PRNGKey(4))["layer_2"]["mixer"]
    a = np.exp(np.asarray(mp["A_log"]))
    dt = np.asarray(jax.nn.softplus(mp["dt_bias"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 2.0
    assert 0.001 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert np.array_equal(mp["D"], np.ones(64))


# -- the model against the reference -----------------------------------------
@pytest.mark.parametrize("p", [10, 12])
def test_prefill_logits_match_the_reference(p):
    """float32 prefill — the chunked scan over two and a half or three
    whole chunks, the causal walk, the latent experts — against the
    reference's last prompt position: only the order of sums differs,
    1e-4 of logits whose spread is about 1."""
    cfg = NemotronHConfig.tiny(dtype="float32")
    params = _params(cfg)
    arch = _arch(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, p), 0, 256)
    got, carry = jax.jit(lambda q, i: nemotron.prefill(q, i, p + 4, cfg))(
        params, ids)
    want = jnp.stack([reference.head(params, reference.hidden(
        params, row, arch)[-1:], arch)[0] for row in ids])
    assert got.shape == want.shape == (2, cfg.n_vocab)
    assert float(jnp.abs(got - want).max()) < 1e-4
    caches, mtp_cache, _, stats = carry
    mc = caches[0]
    assert mc.state.dtype == jnp.float32 and mc.state.shape == (
        2, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state)
    # the conv's window, then a row for the input no step has run yet
    assert mc.conv.shape == (2, cfg.conv_kernel, cfg.conv_dim)
    assert not mc.took.any()
    assert caches[1] == () and len(caches[3]) == 2
    assert mtp_cache[0].shape == (2, p + 4, cfg.kv_heads, cfg.head_dim)


@pytest.mark.parametrize("stride", [1, 2])
def test_prefill_then_two_position_steps_match_the_full_forward(stride):
    """float32 program against the float32 reference: only the order of
    sums differs — 1e-4 of logits whose spread is about 1. At stride 1
    every step leaves a rejected draft behind, a K/V row at q + 1 and a
    state after it: were the row read by a later softmax or the state
    kept, the logits would be off by tenths."""
    cfg = NemotronHConfig.tiny(dtype="float32")
    params = _params(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + T - 1), 0, 256)
    main, module, caches = jax.jit(
        lambda p, i: _program_logits(cfg, p, i, stride))(params, ids)
    want_main, want_module = _reference_logits(cfg, params, ids)
    assert main.shape == want_main.shape == (2, T, cfg.n_vocab)
    assert module.shape == want_module.shape == (2, T - 1, cfg.n_vocab)
    assert float(jnp.abs(main - want_main).max()) < 1e-4
    assert float(jnp.abs(module - want_module).max()) < 1e-4
    assert len(caches) == len(cfg.layers)


@pytest.mark.parametrize("took", [(False, False), (True, True),
                                  (True, False)])
def test_a_forced_reject_or_accept_leaves_the_state_of_one_position_stepping(
        took):
    """A two-position step and its commit against one-position steps from
    the same carry: where a row took both positions, its state and conv
    window are those after two one-position steps; where it did not,
    after one — row by row in one batch, every Mamba layer, float32 —
    and the K/V rows it keeps are the same."""
    cfg = NemotronHConfig.tiny(dtype="float32")
    params = _params(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(6), (2, P + 2), 0, 256)
    _, (caches, _, _, _) = nemotron.prefill(params, ids[:, :P], P + 4, cfg)
    q = jnp.full((2,), P, jnp.int32)
    _, _, two, _ = nemotron.step(params, ids[:, P:P + 2], caches, q, cfg)
    two = nemotron.commit(two, jnp.asarray(took), cfg)
    one = caches
    after = []
    for s in range(2):
        _, _, one, _ = nemotron.step(params, ids[:, P + s:P + s + 1], one,
                                     q + s, cfg)
        one = nemotron.commit(one, jnp.zeros((2,), bool), cfg)
        after.append(one)
    for row, t in enumerate(took):
        want = after[int(t)]
        for kind, got, ref in zip(cfg.layers, two, want):
            if kind == "mamba":
                for g, r in zip(nemotron.settle(got, cfg),
                                nemotron.settle(ref, cfg)):
                    assert np.allclose(g[row], r[row], atol=1e-6)
            elif kind == "attention":
                for g, r in zip(got, ref):
                    rows = P + 1 + int(t)
                    assert np.allclose(g[row, :rows], r[row, :rows],
                                       atol=1e-6)


def _mamba_step_before(x, lp, state, window, cfg):
    """`_mamba_step` as it was before the fold was deferred: the state
    after EACH position stacked [B, S, H, Pd, N] and the conv buffer
    [B, K - 1 + S, C], for `_commit_before` to choose from."""
    mp = lp["mixer"]
    bsz, s, _ = x.shape
    f32 = jnp.float32
    z, xbc, dt_raw = nemotron._mamba_in(
        trinity.rms_norm(x, lp["norm"]["scale"], cfg.eps), mp, cfg)
    buf = jnp.concatenate([window, xbc.astype(window.dtype)], axis=1)
    xs, b, c, dt, a = nemotron._ssm_inputs(nemotron._conv(buf, mp, s, cfg),
                                           dt_raw, mp, cfg)
    xs, b, c = (v.astype(f32) for v in (xs, b, c))
    st = state.reshape(bsz, *a.shape, cfg.mamba_head_dim, cfg.ssm_state)
    states, ys = [], []
    for i in range(s):
        st = jnp.exp(dt[:, i] * a)[..., None, None] * st \
            + (dt[:, i, ..., None] * xs[:, i])[..., None] \
            * b[:, i, :, None, None, :]
        ys.append((st * c[:, i, :, None, None, :]).sum(axis=-1))
        states.append(st.reshape(state.shape))
    y = jnp.stack(ys, axis=1) \
        + mp["D"].astype(f32).reshape(a.shape)[..., None] * xs
    x = x + nemotron._mamba_out(y.reshape(bsz, s, cfg.d_inner), z, mp, cfg)
    return x, jnp.stack(states, axis=1), buf


def _commit_before(caches, took, cfg):
    keep = took.astype(jnp.int32)
    k = cfg.conv_kernel
    out = []
    for kind, c in zip(cfg.layers, caches):
        if kind == "mamba":
            states, buf = c
            out.append((
                jax.vmap(lambda st, i: st[i])(states, keep),
                jax.vmap(lambda bf, i: jax.lax.dynamic_slice_in_dim(
                    bf, i + 1, k - 1))(buf, keep)))
        else:
            out.append(c)
    return tuple(out)


def _step_before(params, toks, caches, q, cfg):
    x = nemotron._embed(params, toks, cfg)
    new = []
    for i, kind in enumerate(cfg.layers):
        lp = params[f"layer_{i}"]
        if kind == "mamba":
            x, states, buf = _mamba_step_before(x, lp, *caches[i], cfg)
            new.append((states, buf))
        elif kind == "attention":
            x, kv = nemotron._attn_step(x, lp, caches[i], q, cfg)
            new.append(kv)
        else:
            x = nemotron._moe_rows(x, lp, cfg)[0]
            new.append(())
    return nemotron._logits(params, x, cfg), tuple(new)


@pytest.mark.parametrize("form", ["op_by_op", "jitted"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_deferred_fold_is_the_stacked_candidates_commit(dtype, form):
    """Four two-position steps from one prefill, three rows taking their
    draft in a different pattern each step: `step` + `commit`, the
    taken increment folded by the next step, against the step that
    stacks both candidate states and the commit that picks one a row.
    The fold is the same float32 expression on the same operands, so op
    by op the logits, the state and conv window a row keeps and the K/V
    rows are equal to the bit. Jitted whole, XLA's CPU backend contracts
    a·b + c·d into one fused multiply-add inside a fusion, and which
    product it contracts follows the fusion, which the two programs draw
    differently: the float32 state then differs in its last bits (under
    1e-6 of values of order 0.1) and float32 logits by under 1e-5 (of
    order 1); bfloat16's logits round those bits away."""
    cfg = NemotronHConfig.tiny(dtype=dtype)
    params = _params(cfg, dtype=dtype)
    ids = jax.random.randint(jax.random.PRNGKey(12), (3, P + 8), 0, 256)
    _, (caches, _, _, _) = nemotron.prefill(params, ids[:, :P], P + 9, cfg)
    before = tuple(nemotron.settle(c, cfg) if kind == "mamba" else c
                   for kind, c in zip(cfg.layers, caches))

    def old(p, t, c, q, took):
        lg, c = _step_before(p, t, c, q, cfg)
        return lg, _commit_before(c, took, cfg)

    def new(p, t, c, q, took):
        lg, _, c, _ = nemotron.step(p, t, c, q, cfg)
        return lg, nemotron.commit(c, took, cfg)

    def settle(c):
        return nemotron.settle(c, cfg)

    exact = form == "op_by_op"
    if not exact:
        old, new, settle = jax.jit(old), jax.jit(new), jax.jit(settle)

    def same(got, want, atol):
        if exact:
            assert np.array_equal(got, want)
        else:
            assert np.allclose(got, want, rtol=0, atol=atol)

    q = jnp.full((3,), P, jnp.int32)
    pattern = [(True, False, True), (False, True, True),
               (True, True, False), (False, False, True)]
    with jax.disable_jit(exact):
        for i, took in enumerate(map(jnp.asarray, pattern)):
            toks = jax.random.randint(jax.random.PRNGKey(20 + i), (3, 2), 0,
                                      256)
            lg, caches = new(params, toks, caches, q, took)
            lg_b, before = old(params, toks, before, q, took)
            same(lg, lg_b, 1e-5 if dtype == "float32" else 0.0)
            for kind, got, want in zip(cfg.layers, caches, before):
                got = settle(got) if kind == "mamba" else got
                for g, w in zip(got, want):
                    same(g, w, 1e-6)
            q = q + 1 + took.astype(jnp.int32)


def test_bfloat16_stays_within_a_bound_that_a_float8_pass_does_not():
    """bfloat16 as served against the float32 reference on the same
    weights: the mean distance of a logit, in units of the logits'
    spread, stays under 0.05 for the main model and for the module; the
    reference itself computed with float8 kernels and inputs (the
    benchmark's control) is over it for both. A mean and not a maximum:
    a router's choice a rounding apart moves single logits by tenths in
    either precision."""
    cfg = NemotronHConfig.tiny(dtype="bfloat16")
    params = _params(cfg, dtype="bfloat16")
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, P + T - 1), 0, 256)
    main, module, _ = jax.jit(
        lambda p, i: _program_logits(cfg, p, i, 2))(params, ids)
    want = _reference_logits(cfg, params, ids)
    low = _reference_logits(cfg, params, ids, weights="fp8")

    def dist(got, ref):
        return float(jnp.abs(got - ref).mean() / ref.std())

    for got, ref, control in zip((main, module), want, low):
        assert dist(got, ref) < 0.05 < dist(control, ref)


# -- the speculative loop ----------------------------------------------------
def _one_token_tokens(pipe, params, prompts, seeds, sampler, p=P, t=T):
    """The tokens of one-token-a-step decoding with the bucket's
    sampler: prefill, then the main model alone, one position a step."""
    cfg = pipe.config
    ids = jnp.asarray(pipe._tokenizer(p).encode_batch(prompts))
    seeds = np.asarray(seeds, dtype=np.uint64)
    keys = _fold_keys(jnp.asarray(seeds & 0xFFFFFFFF, jnp.uint32),
                      jnp.asarray(seeds >> np.uint64(32), jnp.uint32))
    sample = pipe._sampler_fn(sampler)
    logits, (caches, _, _, _) = nemotron.prefill(params, ids, p + t, cfg)
    tok = sample(logits, keys, 0)
    out = [tok]
    b = len(prompts)
    for i in range(1, t):
        lg, _, caches, _ = nemotron.step(
            params, tok[:, None], caches,
            jnp.full((b,), p + i - 1, jnp.int32), cfg)
        caches = nemotron.commit(caches, jnp.zeros((b,), bool), cfg)
        tok = sample(lg[:, 0], keys, i)
        out.append(tok)
    return np.stack([np.asarray(x) for x in out], axis=1)


@pytest.mark.parametrize("sampler", ["greedy", "top_k"])
def test_speculative_tokens_are_the_one_token_loops_whatever_the_module(
        sampler):
    """float32: the shared loop's tokens equal one-token-a-step decoding
    for both samplers, and a redrawn module — every leaf under `mtp`
    from another seed — changes no token."""
    cfg = NemotronHConfig.tiny(dtype="float32")
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
    # two expert layers and the module route: all held, all counted
    made = 3 * 2 * 2 * (P + 2 * steps) + 3 * 2 * (1 + 2 * steps)
    assert int(routed[0]) == int(routed[1]) == made
    redrawn = dict(params, mtp=_params(cfg, seed=9)["mtp"])
    assert np.array_equal(pipe.generate(redrawn, **kw)[0], want)


# -- the routed experts ------------------------------------------------------
def test_four_shares_sum_to_the_uncut_reference_layer():
    """An expert layer cut as the cell cuts it, each of four shares
    holding a quarter of the routed experts (2 of 8): the four partial
    results, with what every share computes alike — the residual and the
    shared expert — counted once, add up to the uncut reference layer,
    float32."""
    whole = NemotronHConfig.tiny(dtype="float32")
    lp = _params(whole)["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(8), (P, whole.hidden))

    def share(i):
        sp = jax.tree_util.tree_map(lambda a: a, lp)
        sp["moe"]["experts"] = jax.tree_util.tree_map(
            lambda k: k[2 * i:2 * i + 2], lp["moe"]["experts"])
        return dataclasses.replace(whole, experts_held=(2 * i, 2 * i + 2)), sp

    h = trinity.rms_norm(x, lp["norm"]["scale"], whole.eps)
    common = x + trinity.relu2(h, lp["moe"]["shared"])
    want = reference.layer(lp, x, "moe", _arch(whole))
    got = sum(nemotron._moe(x, sp, cfg)[0] for cfg, sp in map(share, range(4)))
    assert float(jnp.abs(got - 3 * common - want).max()) < 1e-4
    # and the reference's own shares add up the same way
    refs = sum(reference.layer(sp, x, "moe", _arch(cfg))
               for cfg, sp in map(share, range(4)))
    assert float(jnp.abs(refs - 3 * common - want).max()) < 1e-4
    # a share alone is not the layer
    one = nemotron._moe(x, *reversed(share(0)))[0]
    assert float(jnp.abs(one - want).max()) > 1e-2


def test_squared_relu_experts_walk_the_grouped_product_as_the_loop(
        monkeypatch):
    """`routed_experts` in its squared-ReLU form, bfloat16, a verify
    step's 2 x 3 rows: the grouped product (megablox's gmm in Pallas's
    interpreter) against the tile loop — the products' float32 sums in
    another order, each rounded to bfloat16 before the float32 combine."""
    cfg = NemotronHConfig.tiny(dtype="bfloat16", num_experts=16,
                               experts_held=(4, 12), experts_per_token=4)
    mp = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                _params(cfg)["layer_1"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(2), (6, cfg.latent),
                          jnp.bfloat16)
    h = jax.random.normal(jax.random.PRNGKey(3), (6, cfg.hidden),
                          jnp.bfloat16)
    chosen, w = trinity.route(h, mp, cfg)
    loop = trinity.routed_experts(x, chosen, w, mp["experts"], cfg,
                                  form="relu2")
    monkeypatch.setattr(grouped, "kernel_serves", lambda *_: True)
    kernel = trinity.routed_experts(x, chosen, w, mp["experts"], cfg,
                                    form="relu2")
    (y, n), (y0, n0) = kernel, loop
    assert int(n) == int(n0) > 0
    scale = float(jnp.abs(y0.astype(jnp.float32)).max())
    assert scale > 0
    assert float(jnp.abs(y.astype(jnp.float32) - y0.astype(jnp.float32))
                 .max()) <= 2 ** -7 * scale


def _routed_experts_before(x, chosen, w, experts, cfg):
    """`routed_experts` as it was before the expert form became an
    argument (SwiGLU alone), kept here to pin the other families'
    lowering."""
    t, k = chosen.shape
    d = x.shape[-1]
    lo, _ = cfg.experts_held
    h = cfg.n_held
    a = t * k
    i32 = jnp.int32
    tile = trinity.expert_tile(t, cfg)
    e = chosen.reshape(a).astype(i32) - lo
    held = (e >= 0) & (e < h)
    key = jnp.where(held, e, h)
    onehot = (key[:, None] == jnp.arange(h, dtype=i32)[None]).astype(i32)
    counts = onehot.sum(axis=0)
    local = jnp.minimum(key, h - 1)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - onehot,
                               local[:, None], axis=1)[:, 0]
    tiles_e = (counts + tile - 1) // tile
    tile_end = jnp.cumsum(tiles_e)
    tile_start = tile_end - tiles_e
    n_tiles = tile_end[-1]
    max_tiles = -(-a // tile) + h
    rows = max_tiles * tile
    row = tile_start[local] * tile + rank
    order = jnp.argsort(key, stable=True).astype(i32)
    group_start = jnp.cumsum(counts) - counts
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(max_tiles, dtype=i32),
                         side="right").astype(i32), h - 1)
    slot = jnp.arange(rows, dtype=i32)
    se = tile_expert[slot // tile]
    off = slot - tile_start[se] * tile
    valid = (slot // tile < n_tiles) & (off < counts[se])
    src = order[jnp.clip(group_start[se] + off, 0, a - 1)]
    tok = jnp.where(valid, src // k, t)
    xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[tok]
    gate, up, down = (experts[n]["kernel"] for n in ("gate", "up", "down"))
    if trinity.grouped_walk(t, cfg):
        sizes = tiles_e * tile
        g, u = (grouped.grouped_dot(xs, kern, sizes, tile)
                for kern in (gate, up))
        hs = (jax.nn.silu(g.astype(jnp.float32))
              * u.astype(jnp.float32)).astype(x.dtype)
        out = jnp.concatenate([grouped.grouped_dot(hs, down, sizes, tile),
                               jnp.zeros((1, d), x.dtype)])
    else:
        def body(j, out):
            ex = tile_expert[j]
            xt = jax.lax.dynamic_slice(xs, (j * tile, 0), (tile, d))
            p = {n: {"kernel": jax.lax.dynamic_index_in_dim(
                     kern, ex, 0, keepdims=False)}
                 for n, kern in (("gate", gate), ("up", up),
                                 ("down", down))}
            return jax.lax.dynamic_update_slice(out, trinity.swiglu(xt, p),
                                                (j * tile, 0))

        out = jax.lax.fori_loop(0, n_tiles, body,
                                jnp.zeros((rows + 1, d), x.dtype))
    y = out[jnp.where(held, row, rows)].reshape(t, k, d)
    y = (y.astype(jnp.float32) * w[..., None]).sum(axis=1)
    return y.astype(x.dtype), held.sum(dtype=i32)


def _family_configs():
    from arbius_tpu.models.deepseek_v32 import DeepSeekV32Config
    from arbius_tpu.models.dots3 import Dots3NoteConfig
    from arbius_tpu.models.joyai_flash import JoyAIFlashConfig
    from arbius_tpu.models.trinity import TrinityConfig

    return {"trinity": TrinityConfig.tiny(experts_held=(2, 6)),
            "deepseek_v32": DeepSeekV32Config.tiny(),
            "joyai_llm_flash": JoyAIFlashConfig.tiny(),
            "dots3_note": Dots3NoteConfig.tiny()}


@pytest.mark.parametrize("walk", ["loop", "grouped"])
@pytest.mark.parametrize("family", ["trinity", "deepseek_v32",
                                    "joyai_llm_flash", "dots3_note"])
def test_the_other_families_swiglu_lowering_is_unchanged(
        family, walk, monkeypatch):
    """The four SwiGLU families' `routed_experts` call, on each walk,
    traces to the same jaxpr as the code before the expert form became
    an argument: their programs cannot have moved."""
    cfg = _family_configs()[family]
    if walk == "grouped":
        monkeypatch.setattr(grouped, "kernel_serves", lambda *_: True)
    d, f = cfg.hidden, cfg.expert_ff
    experts = {n: {"kernel": jax.ShapeDtypeStruct(s, jnp.bfloat16)}
               for n, s in (("gate", (cfg.n_held, d, f)),
                            ("up", (cfg.n_held, d, f)),
                            ("down", (cfg.n_held, f, d)))}
    t, k = 6, cfg.experts_per_token
    args = (jax.ShapeDtypeStruct((t, d), jnp.bfloat16),
            jax.ShapeDtypeStruct((t, k), jnp.int32),
            jax.ShapeDtypeStruct((t, k), jnp.float32), experts)
    now = jax.make_jaxpr(
        lambda *a: trinity.routed_experts(*a, cfg))(*args)
    before = jax.make_jaxpr(
        lambda *a: _routed_experts_before(*a, cfg))(*args)
    assert str(now) == str(before)
    assert ("pallas_call" in str(now)) == (walk == "grouped")


# -- the node ----------------------------------------------------------------
def test_published_widths_and_the_cells_parameter_count():
    cfg = NemotronHConfig.published()
    assert (len(cfg.layers), cfg.count("mamba"), cfg.count("moe"),
            cfg.count("attention")) == (88, 40, 40, 8)
    assert cfg.layers[:11] == NemotronHConfig.pattern("MEMEMEM*EME") == (
        "mamba", "moe", "mamba", "moe", "mamba", "moe", "mamba",
        "attention", "moe", "mamba", "moe")
    assert (cfg.hidden, cfg.d_inner, cfg.conv_dim, cfg.mamba_heads,
            cfg.mamba_head_dim, cfg.ssm_state, cfg.n_groups,
            cfg.heads_per_group, cfg.conv_kernel, cfg.chunk_size) \
        == (4096, 8192, 10240, 128, 64, 128, 8, 16, 4, 128)
    assert (cfg.heads, cfg.kv_heads, cfg.head_dim, cfg.latent,
            cfg.expert_ff, cfg.shared_ff, cfg.num_experts,
            cfg.experts_per_token, cfg.route_scale) \
        == (32, 2, 128, 1024, 2688, 5376, 512, 22, 5.0)
    cell = dataclasses.replace(
        cfg, layers=NemotronHConfig.pattern("MEMEMEM*EME"),
        experts_held=(0, 128), vocab_rows=(0, 16384))
    shapes = nemotron.param_shapes(cell)

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    assert count(shapes) == 5_342_342_016
    assert count(shapes["layer_0"]) == 109_640_064
    assert count(shapes["layer_7"]) == 35_655_680
    assert count(shapes["layer_1"]) == 759_173_632
    assert count(shapes["layer_1"]["moe"]["experts"]) == 128 * 5_505_024
    assert count(shapes["mtp"]) == 828_396_032
    # a layer: the state 4,194,304 B, the pending increment (128 + 8,192
    # + 1,024) × 4 B and took's byte, 4 conv inputs of 10,240 × 2 B
    assert cell.ssm_state_bytes() == 5 * (4_194_304 + 37_377 + 81_920) \
        == 21_568_005
    # the attention layer's and the module's K and V, 1 KB a position each
    assert cell.kv_bytes(2048 + 1024) == 2 * 3072 * 1024
    with pytest.raises(ValueError, match="experts_held"):
        NemotronHConfig.tiny(experts_held=(6, 2))
    with pytest.raises(ValueError, match="vocab_rows"):
        NemotronHConfig.tiny(vocab_rows=(0, 9999))
    with pytest.raises(ValueError, match="unknown layer kind"):
        NemotronHConfig.tiny(layers=("mamba", "dense"))


def test_the_family_serves_one_program_and_says_so():
    cfg = NemotronHConfig.tiny()
    pipe = _pipe(cfg)
    assert pipe.bucket_tag(2, P, T, "greedy") \
        == f"nemotron_h.2.{P}.{T}.greedy"
    with pytest.raises(NotImplementedError, match="two-position"):
        pipe._decode(None, None, None, 0)
    # off the TPU the tile loop walks every routed call: prefill's one a
    # layer a sequence, the first draft, then at most T - 1 steps of two
    # expert layers and the module
    assert pipe.bucket_attrs(2, P, T) == {
        "ssm_state_bytes": cfg.ssm_state_bytes(),
        "kv_bytes": cfg.kv_bytes(P + T), "attn_kernel_calls": 0,
        "attn_blocks": 0, "attn_blocks_dense": 0,
        "expert_calls_grouped": 0, "expert_calls_loop": 2 * 2 + 1 + (T - 1)
        * 3}
    with pytest.raises(ValueError, match="bf16 only"):
        NemotronHPipeline(cfg, precision="int8")
    with pytest.raises(ValueError, match="nemotron_h ships no mesh"):
        NemotronHPipeline(cfg, mesh=object())


def test_nemotron_graph_goldens_and_rules_are_clean():
    """The four goldened programs (prefill with the chunked scan and the
    module's K/V rows, the speculative loop with its per-row commit of
    state greedy and top-k, the composed bucket) trace to their
    checked-in goldens with no GRAPH4xx finding; through the library."""
    from arbius_tpu.analysis import graph
    from arbius_tpu.models.nemotron_h.pipeline import trace_specs

    specs = trace_specs()
    assert sorted(s.key for s in specs) == [
        "nemotron_h.decode.b2.p12.t4.greedy.single.bfloat16",
        "nemotron_h.decode.b2.p12.t4.top_k.single.bfloat16",
        "nemotron_h.generate.b2.p12.t4.greedy.single.bfloat16",
        "nemotron_h.prefill.b2.p12.t4.single.bfloat16"]
    assert graph.audit(specs) == []


def test_factory_builds_the_runner_from_the_template_block():
    from arbius_tpu.node.factory import build_registry

    mid = "0x" + "4c" * 32
    cfg = load_config({
        "models": [{"id": mid, "template": "nemotron_h", "tiny": True,
                    "weights_dtype": "bfloat16"}],
        "textgen": {"templates": {"nemotron_h": {
            "prompt_buckets": [P], "decode_buckets": [T],
            "max_new_tokens": T}},
            "share": {"layers": ["mamba", "attention", "moe"],
                      "experts_held": [0, 4]}}})
    runner = build_registry(cfg).get(mid).runner
    assert isinstance(runner, TextGenRunner)
    assert isinstance(runner.pipeline, NemotronHPipeline)
    assert runner.pipeline.config.layers == ("mamba", "attention", "moe")
    up = runner.params["layer_2"]["moe"]["experts"]["up"]["kernel"]
    assert up.shape == (4, 16, 16) and up.dtype == jnp.bfloat16
    assert "gate" not in runner.params["layer_2"]["moe"]["experts"]


def _world(pipe, params, pipeline_on):
    from test_textgen import _text_world   # the text families' node world

    eng, node, mid, user = _text_world(pipe, params, pipeline_on=pipeline_on,
                                       template="nemotron_h")
    while node.tick():
        pass
    for i in range(3):       # a full bucket and a padded one
        obj = {"prompt": f"nemotron task {i}", "max_new_tokens": (T, 2)[i % 2]}
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


def test_two_runs_of_the_tasks_through_the_node_give_one_cid_each():
    """The node path end to end: devnet tasks in, MinerNode.tick() ->
    TextGenRunner -> the shared speculative bucket program, commitment
    and reveal landed; the same tasks, the same CIDs twice (the staged
    executor off and on); the state's bytes on `text.bucket` and on the
    counter, the loop's counts on `text.speculate`."""
    cfg = NemotronHConfig.tiny()

    def fresh():
        return NemotronHPipeline(cfg, prompt_buckets=(P, 32),
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
                a["batch"]) == ("nemotron_h", 32, T, 2)
        assert a["ssm_state_bytes"] == cfg.ssm_state_bytes() == 3490
        assert a["kv_bytes"] == cfg.kv_bytes(32 + T)
        state = sum(s["attrs"]["batch"] * s["attrs"]["ssm_state_bytes"]
                    for s in bucket)
        assert 'arbius_text_cache_bytes_total{form="ssm_state"} ' \
            f"{state}" in text
        for s in spec:
            a = s["attrs"]
            assert a["batch"] * (T - 1) == a["batch"] * a["steps"] \
                - a["idle_row_steps"] + a["accepted"]
        assert all(s["attrs"]["assignments"] == s["attrs"]["held"] > 0
                   for s in routed)
