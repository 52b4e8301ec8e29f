"""FLOP and byte counts against hand counts; the peaks table."""
from __future__ import annotations

import json
import os

import pytest

from pb_paths import ROOT


def test_one_convolution_by_hand():
    import jax
    import jax.numpy as jnp

    from perfbench.reference import ops

    p = {"kernel": jax.ShapeDtypeStruct((3, 3, 320, 640), jnp.bfloat16),
         "bias": jax.ShapeDtypeStruct((640,), jnp.bfloat16)}
    x = jax.ShapeDtypeStruct((2, 96, 96, 320), jnp.float32)
    with ops.count_flops() as c:
        out = jax.eval_shape(ops.conv, x, p)
    assert out.shape == (2, 96, 96, 640)
    # 2 x (batch 2 x 96 x 96 outputs x 640 channels) x (3 x 3 x 320) taps
    assert c.conv == 2 * (2 * 96 * 96 * 640) * (3 * 3 * 320) == c.total
    with ops.count_flops() as c2:
        jax.eval_shape(lambda a, b: ops.conv(a, b, stride=2), x, p)
    assert c2.conv == c.conv / 4


def test_one_attention_by_hand():
    import jax
    import jax.numpy as jnp

    from perfbench.reference import ops

    q = jax.ShapeDtypeStruct((8, 8, 9216, 40), jnp.float32)
    kv = jax.ShapeDtypeStruct((8, 8, 77, 40), jnp.float32)
    with ops.count_flops() as c:
        jax.eval_shape(ops.attend, q, q, q)
        jax.eval_shape(ops.attend, q, kv, kv)
    self_f = 2 * 2 * (8 * 8) * 9216 * 9216 * 40      # QK^T and PV
    cross_f = 2 * 2 * (8 * 8) * 9216 * 77 * 40
    assert c.attn == self_f + cross_f
    assert c.attn_calls == [(8, 8, 9216, 9216, 40), (8, 8, 9216, 77, 40)]
    assert ops.attention_bytes(8, 8, 9216, 9216, 40) \
        == 2 * 64 * 40 * 4 * 9216
    assert ops.dense_flops(77, 768, 3072) == 2 * 77 * 768 * 3072


def test_a_masked_attention_counts_the_pairs_its_mask_leaves():
    """The door for a causal or windowed reference: `pairs` of a head's
    Sq·Sk (query, key) pairs are counted, such calls are recorded apart
    from the whole ones the families' `kernel_calls` index, and work that
    is none of dense, conv or attend is counted under a name of its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import flops, peaks
    from perfbench.reference import ops

    s, d = 1024, 64
    q = jax.ShapeDtypeStruct((2, 4, s, d), jnp.float32)
    causal = jnp.where(jnp.tril(jnp.ones((s, s), bool)), 0.0, -jnp.inf)
    with ops.count_flops() as whole:
        jax.eval_shape(lambda a: ops.attend(a, a, a, mask=causal), q)
    with ops.count_flops() as c:
        jax.eval_shape(lambda a: ops.attend(a, a, a, mask=causal,
                                            pairs=s * s // 2), q)
        jax.eval_shape(lambda a: ops.attend(a, a, a, mask=causal,
                                            pairs=s * (s + 1) // 2), q)
    assert whole.attn == 4 * 2 * 4 * s * s * d
    assert whole.attn_calls == [(2, 4, s, s, d)]
    assert whole.masked_attn_calls == []
    assert c.attn == whole.attn / 2 + whole.attn * (s + 1) / (2 * s)
    assert c.attn_calls == []
    assert c.masked_attn_calls == [(2, 4, s, s, d, s * s // 2),
                                   (2, 4, s, s, d, s * (s + 1) // 2)]
    # the result is the masked attention either way
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 8, 4), jnp.float32)
    m = jnp.where(jnp.tril(jnp.ones((8, 8), bool)), 0.0, -jnp.inf)
    assert np.array_equal(ops.attend(x, x, x, mask=m),
                          ops.attend(x, x, x, mask=m, pairs=36))
    with ops.count_flops() as c:
        ops.count("experts", 3e9)
        ops.count("experts", 1e9)
        jax.eval_shape(lambda a: ops.attend(a, a, a), q)
    assert c.other == {"experts": 4e9} and c.total == 4e9 + c.attn
    ops.count("experts", 1.0)                 # no count open: no effect
    pk = peaks.peaks_for("TPU v5 lite")
    full, _ = flops.attention_floor_seconds(2, 4, s, s, d, pk)
    half, bound = flops.attention_floor_seconds(2, 4, s, s, d, pk,
                                                pairs=s * s // 2)
    assert bound == "flops" and half == full / 2


@pytest.mark.parametrize("config", ["kandinsky2", "anythingv3-kandinsky2"])
def test_no_group_of_an_accepted_configuration_is_over_the_ceiling(config):
    """So both trees are drawn group by group, exactly as before there was
    a ceiling (and `rules` that name no `fan_in_axes`, as before)."""
    import math

    import jax

    from perfbench import manifest, weights

    with open(os.path.join(ROOT, "perfbench", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    assert not any("fan_in_axes" in r for r in cfg["weights"]["init"])
    for m in cfg["models"]:
        pipe, _ = manifest.family(m["family"]).build(m["arch"], "bf16")
        shapes = jax.eval_shape(
            lambda: pipe.init_params(seed=0, dtype="bfloat16"))
        draws = [len(idx) * math.prod(g[0]) for g, idx
                 in weights.plan(shapes, cfg["weights"]["init"])[0]]
        assert sum(draws) == weights.count(shapes)
        assert 2 ** 26 < max(draws) <= weights.CEILING, max(draws)


def test_attention_in_row_blocks_is_exact_attention():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.reference import ops

    k = jax.random.split(jax.random.PRNGKey(0), 3)
    shape = (1, 2, 2 * ops.ATTN_ROW_BLOCK, 8)
    q, kk, v = (jax.random.normal(x, shape, jnp.float32) for x in k)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                   precision=ops.HIGHEST) / np.sqrt(8)
    want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v,
                      precision=ops.HIGHEST)
    assert np.allclose(ops.attend(q, kk, v), want, atol=1e-5)


@pytest.mark.parametrize("name,lo,hi", [
    ("kandinsky2", 100e12, 400e12), ("anythingv3", 40e12, 200e12)])
def test_full_size_solution_flops(name, lo, hi):
    """Counted from shapes at the cells' sizes; the parts add up and the
    denoiser dominates."""
    import jax

    from perfbench import flops, manifest

    with open(os.path.join(ROOT, "perfbench", "configs",
                           "anythingv3-kandinsky2.json")) as f:
        cfg = json.load(f)
    m = next(m for m in cfg["models"] if m["template"] == name)
    fam = manifest.family(m["family"])
    pipe, _ = fam.build(m["arch"], "bf16")
    shapes = jax.eval_shape(
        lambda: pipe.init_params(seed=0, dtype="bfloat16"))
    task = dict(m["defaults"], prompt="x")
    parts = flops.count_parts(fam.reference, m["arch"], task, shapes)
    total = flops.solution_flops(fam.reference, m["arch"], task, shapes)
    assert total == sum(v["flops"] * v["calls"] for v in parts.values())
    assert lo < total < hi, total
    den = "decoder" if name == "kandinsky2" else "unet"
    assert parts[den]["flops"] * parts[den]["calls"] > 0.8 * total
    assert all(v["flops"] == v["dense"] + v["conv"] + v["attn"]
               for v in parts.values())
    # at the canonical batch the same parts do four times the work
    four = flops.count_parts(fam.reference, m["arch"], task, shapes, batch=4)
    assert abs(four[den]["flops"] / parts[den]["flops"] - 4) < 1e-6
    calls = fam.kernel_calls(four[den]["attn_calls"])
    if name == "anythingv3":
        assert (8, 8, 9216, 9216, 40) in calls and (8, 8, 9216, 77, 40) in calls
        assert (8, 8, 2304, 2304, 80) in calls
    else:
        # level 1's added-KV attention (10 context tokens beside the 2304
        # spatial ones) is a kernel call since PR 29: 7 a UNet forward; the
        # 576- and 144-row levels are under the program's 1024-row rule
        assert calls == [(8, 12, 2304, 2314, 64)] * 7
        assert fam.kernel_calls(four["movq"]["attn_calls"]) \
            == [(4, 1, 9216, 9216, 512)]


@pytest.mark.parametrize("family", ["anythingv3", "kandinsky2", "trinity"])
def test_a_familys_copied_kernel_threshold_is_the_programs(family):
    """A family states by hand from how many query rows the program takes
    its kernel (the benchmark's count imports nothing of the program);
    this pins each copy to the program's own rule, so that a change of the
    rule turns a test red and not a roofline share silently wrong."""
    from arbius_tpu.ops import causal_flash, flash

    from perfbench import manifest

    fam = manifest.family(family)
    if family == "trinity":
        assert fam.CAUSAL_KERNEL_MIN_ROWS == causal_flash._KERNEL_MIN_ROWS
        n = causal_flash._KERNEL_MIN_ROWS
        arch = {"prompt_buckets": [n - 1, n], "decode_buckets": [2]}
        task = {"max_new_tokens": 2}
        assert fam.causal_kernel_calls(
            [(1, 1, n - 1, n - 1, 8, 5), (1, 1, 1, n, 8, 1)], arch,
            {**task, "prompt": "x"}) == []
        assert fam.causal_kernel_calls(
            [(1, 1, n, n, 8, 5), (1, 1, 1, n + 1, 8, 1)], arch,
            {**task, "prompt": "x" * (n - 2)}) == [(1, 1, n, n, 8, 5)]
        assert fam.kernel_calls([(1, 1, 4096, 4096, 8)]) == []
    else:
        n = flash._KERNEL_MIN_ROWS
        under, at = (2, 4, n - 1, n - 1, 64), (2, 4, n, 77, 64)
        assert fam.kernel_calls([under, at, under]) == [at]


def test_attention_floor_says_which_bound_binds():
    from perfbench import flops, peaks

    pk = peaks.peaks_for("TPU v5 lite")
    t, bound = flops.attention_floor_seconds(8, 8, 9216, 9216, 40, pk)
    assert bound == "flops" and abs(t - 4 * 64 * 9216**2 * 40 / 197e12) < 1e-9
    t, bound = flops.attention_floor_seconds(8, 8, 9216, 77, 40, pk)
    assert bound == "bytes"


def test_unknown_device_is_an_error_never_a_default():
    from perfbench import peaks

    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(peaks.UnknownDevice):
            peaks.peaks_for(kind)


def test_flash_roofline_reader_on_the_mix_cells_shapes():
    """The kernel's reader on a made-up trace of the two-model cell: the
    floor is the family's kernel calls at the canonical batch, once for
    each dispatched bucket; the time is every event of the kernel, whatever
    instance number the compiler gave it; no such event, no metric."""
    import jax

    from perfbench import flops, harness, manifest, peaks, system

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, "mix-768-backlog")
    run = harness.Run()
    run.cell, run.peaks = cell, peaks.peaks_for("TPU v5 lite")
    batch = cell.config["node"]["canonical_batch"]
    models = [system.Model(e) for e in cell.config["models"]]
    for m in models:
        pipe, _ = m.family.build(m.arch, "bf16")
        shapes = jax.eval_shape(
            lambda p=pipe: p.init_params(seed=0, dtype="bfloat16"))
        task = m.hydrated(cell.traffic["tasks"][m.template]["input"])
        run.parts[m.template] = {batch: flops.count_parts(
            m.family.reference, m.arch, task, shapes, batch=batch)}
    run.system = type("S", (), {"canonical_batch": batch, "models": models})
    run.spans = [{"name": "bench.dispatch", "t0": 0, "t1": 1,
                  "attrs": {"model": name}}
                 for name in ["anythingv3"] * 7 + ["kandinsky2"] * 2]
    run.trace = {"events": [("flash_attention.98", 0.0, 30.0),
                            ("flash_attention_7", 30.0, 10.0),
                            ("flash_attention", 40.0, 7.0),
                            ("fusion.12", 47.0, 11.0)]}
    read = cell.reader("flash_roofline_pct")
    # anythingv3: 20 UNet calls and one VAE call of kernel attention a
    # bucket; kandinsky2: 50 decoder calls of 7 added-KV calls each (the
    # CFG pair of the batch: b = 2 x 2) and MOVQ's mid-block
    unet = run.parts["anythingv3"][batch]["unet"]
    floor_unet = sum(flops.attention_floor_seconds(*c, run.peaks)[0]
                     for c in unet["attn_calls"] if c[2] >= 1024)
    assert unet["calls"] == 20 and 0.01 < floor_unet < 0.02
    dec = run.parts["kandinsky2"][batch]["decoder"]
    added_kv = (2 * batch, 12, 2304, 2314, 64)
    assert dec["calls"] == 50 and dec["attn_calls"].count(added_kv) == 7
    floor_dec = 7 * flops.attention_floor_seconds(*added_kv, run.peaks)[0]
    assert floor_dec == pytest.approx(7 * 4 * 4 * 12 * 2304 * 2314 * 64
                                      / 197e12)
    value = read(run)
    least = (7 * 20 * floor_unet + 2 * 50 * floor_dec) / 47.0
    assert least < value / 100 < 1.1 * least
    run.trace = {"events": [("fusion.12", 0.0, 11.0)]}
    assert read(run) is None
