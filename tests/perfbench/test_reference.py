"""The plain references against the program's pipelines at tiny sizes on
the CPU (both computed in float32 there, so they agree to rounding), and
the host-side protocol arithmetic against the program's."""
from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest

from pb_paths import ROOT, TINY_MANIFEST

TINY_DIR = os.path.join(ROOT, "tests", "perfbench", "tiny")
TINY = os.path.join(TINY_DIR, "configs", "tiny-anythingv3-kandinsky2.json")


def _f32(arch):
    arch = copy.deepcopy(arch)
    for part in ("text", "prior", "movq", "vae", "unet"):
        if part in arch:
            arch[part]["dtype"] = "float32"
    if "decoder" in arch:
        arch["decoder"]["unet"]["dtype"] = "float32"
    return arch


@pytest.mark.parametrize("family,steps", [("anythingv3", 3), ("kandinsky2", 2)])
def test_reference_agrees_with_the_pipeline_in_float32(family, steps):
    import jax

    from perfbench import manifest, weights

    with open(TINY) as f:
        cfg = json.load(f)
    m = next(m for m in cfg["models"] if m["family"] == family)
    fam = manifest.family(family)
    pipe, _ = fam.build(_f32(m["arch"]), "bf16")
    shapes = jax.eval_shape(lambda: pipe.init_params(seed=0))
    params = weights.make(shapes, 2**31 + 17, cfg["weights"]["init"])
    task = dict(m["defaults"], prompt="a cat mining on a tpu",
                num_inference_steps=steps)
    task.setdefault("negative_prompt", "")
    seeds = [0x1234567890AB, 77]
    kw = dict(width=task["width"], height=task["height"],
              num_inference_steps=steps,
              guidance_scale=float(task["guidance_scale"]))
    if family == "anythingv3":
        got = pipe.generate(params, [task["prompt"]] * 2,
                            [task["negative_prompt"]] * 2, seeds,
                            scheduler=task["scheduler"], **kw)
    else:
        got = pipe.generate(params, [task["prompt"]] * 2, None, seeds, **kw)
    for i, seed in enumerate(seeds):
        ref = fam.reference.image(params, m["arch"], task, seed)
        diff = np.abs(got[i].astype(int) - ref.astype(int))
        assert ref.std() > 10                  # a picture, not a constant
        assert diff.max() <= 1 and diff.mean() < 0.01, (diff.max(),
                                                        diff.mean())
    # and another seed is another picture
    other = fam.reference.image(params, m["arch"], task, seeds[0] + 1)
    assert np.abs(other.astype(int) - ref.astype(int)).mean() > 5


def test_text_reference_agrees_with_the_pipeline_in_float32():
    """The full forward pass, no cache, against the program's prefill and
    cached decode loop: every id the float32 program serves is the
    reference's first choice, and another prompt's ids are not."""
    import jax

    from perfbench import manifest, system, weights

    with open(os.path.join(TINY_DIR, "configs", "tiny-textgen.json")) as f:
        cfg = json.load(f)
    entry = cfg["models"][0]
    cell = manifest.Cell(TINY_MANIFEST, "tiny-text-backlog")
    model = system.Model(entry, cell.family)
    arch = copy.deepcopy(entry["arch"])
    arch["model"]["dtype"] = "float32"
    pipe, _ = model.family.build(arch, "bf16")
    shapes = jax.eval_shape(lambda: pipe.init_params(seed=0))
    model.params = weights.make(shapes, 2**31 + 23, cfg["weights"]["init"])
    prompts = ["a miner asks the chip for a line of text, please",
               "zephyr yarrow xenon willow violet umbra tundra sa"]
    got = pipe.generate(model.params, prompts, [11, 2**40 + 5],
                        prompt_bucket=64, decode_bucket=32)
    assert got.shape == (2, 32) and got.max() < 256
    assert not np.array_equal(got[0], got[1])
    recs = [{"input": {"prompt": p, "max_new_tokens": 32}} for p in prompts]
    for rec, ids in zip(recs, got):
        out = model.family.compare(model, rec, ids)["logit_gap"]
        assert out["value"] == 0.0 and out["positions"] == 32
        assert 0.5 < out["spread"] < 2
    crossed = model.family.compare(model, recs[0], got[1])["logit_gap"]
    assert crossed["value"] > 3 * entry["limits"]["logit_gap"]
    assert crossed["not_first"] >= 2
    # the text inverts to the ids, a byte each
    text = bytes(int(t) for t in got[0])
    assert np.array_equal(
        model.family.decode(text, {"max_new_tokens": 32}), got[0])
    with pytest.raises(ValueError):
        model.family.decode(text[:-1], {"max_new_tokens": 32})


# the trees of the tests' configurations at seed 2147484001, model by model,
# as the parent of PR 27 drew them (sha256 over each leaf's path, dtype and
# bytes): the draw of a group under the ceiling is what it was
DIGESTS = {
    ("tiny-kandinsky2", "kandinsky2"):
        "d719810ec9081d87c42e542d788f113be4e2cc3a0305e179250ed66d9ef21f02",
    ("tiny-anythingv3-kandinsky2", "anythingv3"):
        "231fb7d2ccc24986a065ec47837328a4129d2c3c538de90c40e10c9ae35f47e7",
    ("tiny-anythingv3-kandinsky2", "kandinsky2"):
        "c509b0eb51c50daf28a114c7e9daad2d91aa74c2259880e4009edce5902c9d17",
}


@pytest.mark.parametrize("config,template", sorted(DIGESTS))
def test_trees_under_the_ceiling_are_the_parents(config, template):
    import hashlib

    import jax

    from perfbench import manifest, weights

    with open(os.path.join(TINY_DIR, "configs", config + ".json")) as f:
        cfg = json.load(f)
    i, m = next((i, m) for i, m in enumerate(cfg["models"])
                if m["template"] == template)
    pipe, _ = manifest.family(m["family"]).build(m["arch"], "bf16")
    dtype = cfg["weights"]["dtype"]
    shapes = jax.eval_shape(lambda: pipe.init_params(seed=0, dtype=dtype))
    params = weights.make(shapes, 2147484001 * 16 + i,
                          cfg["weights"]["init"])
    h = hashlib.sha256()
    for keys, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(weights._path(keys).encode())
        h.update(str(leaf.dtype).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == DIGESTS[config, template]


def test_a_group_over_the_ceiling_is_drawn_in_pieces():
    """Leaf by leaf, and a single leaf over it in slices of its first axis:
    no array of the group's size exists in the program; seeded; by the
    rules."""
    import jax
    import jax.numpy as jnp

    from perfbench import weights

    sds = jax.ShapeDtypeStruct
    shapes = {f"layer_{i}": {n: {"kernel": sds((4, 32, 48), jnp.bfloat16)}
                             for n in ("gate", "up", "down")}
              for i in range(2)}
    shapes["head"] = {"kernel": sds((100, 64), jnp.bfloat16),
                      "bias": sds((100,), jnp.bfloat16)}
    rules = [{"match": "layer_.*/kernel$", "dist": "fan_in",
              "fan_in_axes": [1]},
             {"match": "/kernel$", "dist": "fan_in"},
             {"match": "/bias$", "dist": "rows", "std": 0.001,
              "mean": [float(i) for i in range(100)]}]
    group = 6 * 4 * 32 * 48
    ceiling = 2048          # under a stacked leaf (6144), over the bias

    def largest(c):
        key = jax.random.key(0, impl="rbg")
        jaxpr = jax.make_jaxpr(weights.builder(shapes, rules, c))(key)
        return max(v.aval.size for e in jaxpr.jaxpr.eqns
                   for v in e.outvars if hasattr(v.aval, "size"))

    assert largest(weights.CEILING) == group
    assert largest(ceiling) == 6400 < group  # the head, whole once joined

    def make(seed, c, shapes=shapes):
        key = jax.random.fold_in(jax.random.key(seed, impl="rbg"), 0)
        return jax.jit(weights.builder(shapes, rules, c))(key)

    a, b, other = make(7, ceiling), make(7, ceiling), make(8, ceiling)
    flat = jax.tree_util.tree_leaves
    assert all(jnp.array_equal(x, y) for x, y in zip(flat(a), flat(b)))
    assert not any(jnp.array_equal(x, y)
                   for x, y in zip(flat(a), flat(other)))
    kernels = [np.asarray(a[f"layer_{i}"][n]["kernel"], np.float32)
               for i in range(2) for n in ("gate", "up", "down")]
    assert all(k.shape == (4, 32, 48) and k.dtype == np.float32
               for k in kernels)
    assert len({k.tobytes() for k in kernels}) == 6     # no leaf twice
    assert len({k[e].tobytes() for k in kernels for e in range(4)}) == 24
    std = np.concatenate([k.ravel() for k in kernels]).std()
    assert abs(std - 32 ** -0.5) < 0.01      # fan-in 32, not 4 x 32
    head = np.asarray(a["head"]["kernel"], np.float32)
    assert abs(head.std() - 0.1) < 0.01      # fan-in 100, as without axes
    # sliced rows keep their own constants, under either ceiling
    bias_only = {"head": {"bias": shapes["head"]["bias"]}}
    for tree in (a, make(7, 30, bias_only)):
        bias = np.asarray(tree["head"]["bias"], np.float32)
        assert np.abs(bias - np.arange(100)).max() < 0.3
    # and under the ceiling nothing changed: the group is one draw
    whole = make(7, weights.CEILING)
    assert not jnp.array_equal(whole["layer_0"]["gate"]["kernel"],
                               a["layer_0"]["gate"]["kernel"])


def test_weights_follow_the_seed_and_the_rules():
    import jax
    import jax.numpy as jnp

    from perfbench import weights

    shapes = {"a": {"kernel": jax.ShapeDtypeStruct((64, 32), jnp.bfloat16),
                    "bias": jax.ShapeDtypeStruct((32,), jnp.bfloat16)},
              "b": {"kernel": jax.ShapeDtypeStruct((64, 32), jnp.bfloat16),
                    "scale": jax.ShapeDtypeStruct((32,), jnp.bfloat16)},
              "prior_stats": jax.ShapeDtypeStruct((2, 8), jnp.bfloat16)}
    with open(TINY) as f:
        rules = json.load(f)["weights"]["init"]
    w1 = weights.make(shapes, 2**31 + 5, rules)
    w2 = weights.make(shapes, 2**31 + 5, rules)
    w3 = weights.make(shapes, 6, rules)
    assert jnp.array_equal(w1["a"]["kernel"], w2["a"]["kernel"])
    assert not jnp.array_equal(w1["a"]["kernel"], w3["a"]["kernel"])
    assert not jnp.array_equal(w1["a"]["kernel"], w1["b"]["kernel"])
    assert w1["a"]["kernel"].dtype == jnp.bfloat16
    k = np.asarray(w1["a"]["kernel"], np.float32)
    assert abs(k.std() - 1 / 8) < 0.02         # N(0, 1/fan_in), fan_in 64
    assert abs(float(np.asarray(w1["b"]["scale"], np.float32).mean()) - 1) < .1
    stats = np.asarray(w1["prior_stats"], np.float32)
    assert abs(stats[0].mean()) < 0.1 and abs(stats[1].mean() - 1) < 0.1
    with pytest.raises(ValueError):
        weights.make({"odd": jax.ShapeDtypeStruct((3,), jnp.float32)}, 1,
                     rules)


@pytest.mark.parametrize("size", [0, 1, 100, 262144, 262145, 1_800_000])
def test_solution_cid_matches_the_programs(size):
    from arbius_tpu.l0.cid import cid_of_solution_files

    from perfbench.reference import l0

    files = {"out-1.png": bytes((i * 31 + size) % 251 for i in range(size))}
    assert l0.solution_cid(files) == cid_of_solution_files(files)


def test_commitment_seed_and_keccak_match_the_programs():
    from arbius_tpu.l0.commitment import generate_commitment, taskid2seed
    from arbius_tpu.l0.keccak import keccak256

    from perfbench.reference import l0

    assert l0.keccak256(b"").hex() == (
        "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470")
    for n in (3, 135, 136, 137, 500):
        assert l0.keccak256(b"x" * n) == keccak256(b"x" * n)
    tid = bytes(range(32))
    cid = l0.solution_cid({"out-1.png": b"abc"})
    addr = "0x" + "aa" * 20
    assert l0.commitment(addr, tid, cid) == generate_commitment(addr, tid, cid)
    assert l0.task_seed(tid) == taskid2seed(tid)
    assert l0.task_seed(b"\xff" * 32) == taskid2seed(b"\xff" * 32)


def test_samplers_match_the_programs_tables():
    from arbius_tpu.schedulers import get_sampler

    from perfbench.reference import schedules

    for name, steps in (("DDIM", 50), ("DPMSolverMultistep", 20),
                        ("DPMSolverMultistep", 5)):
        mine = schedules.SAMPLERS[name](steps)
        theirs = get_sampler(name, steps)
        assert mine.calls == theirs.num_model_calls
        assert np.allclose(mine.timesteps, np.asarray(theirs.timesteps))
        x = np.linspace(-1, 1, 12, dtype=np.float32).reshape(3, 4)
        xs, xt = x, x
        state, carry = mine.start(x), theirs.init_carry(x)
        for i in range(steps):
            eps = (0.3 * np.sin(xs + i)).astype(np.float32)
            xs, state = mine.step(i, xs, eps, state)
            xt, carry = theirs.step(i, xt, (0.3 * np.sin(xt + i)).astype(
                np.float32), carry, None)
            xt = np.asarray(xt)
        assert np.allclose(xs, xt, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape,kernel,stride", [
    ((2, 13, 16, 5), (3, 3, 5, 7), 1),     # odd height
    ((2, 12, 16, 5), (3, 3, 5, 7), 2),     # the SD down-sampling conv
    ((1, 9, 9, 4), (1, 1, 4, 6), 1),       # a 1x1 skip projection
])
def test_conv_as_a_loop_of_tap_matmuls_is_the_convolution(shape, kernel,
                                                          stride):
    import jax
    import jax.numpy as jnp

    from perfbench.reference import ops

    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, shape, jnp.float32)
    p = {"kernel": jax.random.normal(k2, kernel).astype(jnp.bfloat16),
         "bias": jnp.arange(kernel[-1], dtype=jnp.float32)}
    kh, kw = kernel[:2]
    want = jax.lax.conv_general_dilated(
        x, p["kernel"].astype(jnp.float32), (stride, stride),
        [(kh // 2, kh // 2), (kw // 2, kw // 2)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST) + p["bias"]
    got = ops.conv(x, p, stride)
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < 1e-4
