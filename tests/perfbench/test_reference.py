"""The plain references against the program's pipelines at tiny sizes on
the CPU (both computed in float32 there, so they agree to rounding), and
the host-side protocol arithmetic against the program's."""
from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest

from pb_paths import ROOT

TINY = os.path.join(ROOT, "tests", "perfbench", "tiny", "configs",
                    "tiny-anythingv3-kandinsky2.json")


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
