"""What PR 34 added to the benchmark, as files and appended entries only:
BENCHMARK.json's new configuration, cell and two per-layer metrics, the
traffic file of the cell, and the deepseek_v32 reference against the
program's pipeline at tiny size in float32."""
from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest

from pb_paths import ROOT

from perfbench import manifest as mf

TINY_DSV32 = os.path.join(ROOT, "tests", "perfbench", "tiny-dsv32")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_dsv32_entries_are_appended_and_find_their_files(bench):
    """Appended, each new metric read in the new cell alone; the accepted
    expert-layer metric gained the cell's name."""
    assert bench["configs"][-1]["name"] == "deepseek-v32-ep16"
    assert bench["workloads"][-1] == {
        **bench["workloads"][-1], "name": "dsv32-ep16-16k-backlog",
        "config": "deepseek-v32-ep16", "traffic": "backlog16-16k-256",
        "chips": 1}
    new = {m["name"]: m for m in bench["per_layer"][-2:]}
    assert sorted(new) == ["index_pairs_kept_pct", "latent_cache_pct"]
    assert (new["latent_cache_pct"]["layer"],
            new["index_pairs_kept_pct"]["layer"]) \
        == ("cache", "sparse attention")
    for m in new.values():
        assert m["workloads"] == ["dsv32-ep16-16k-backlog"]
        assert (m["source"], m["moves"], m["better"]) \
            == ("program_counter", "sol_per_hour", "lower")
    held = next(m for m in bench["per_layer"]
                if m["name"] == "expert_assign_held_pct")
    assert held["workloads"] == ["trinity-ep8-8k-backlog",
                                 "dsv32-ep16-16k-backlog"]
    text = mf.Cell(mf.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    dsv = mf.Cell(mf.DEFAULT_MANIFEST, "dsv32-ep16-16k-backlog")
    fam = dsv.family("deepseek_v32")
    assert fam.gaps is text.family("trinity").gaps      # imported, not copied
    assert not hasattr(fam, "causal_kernel_calls")


def test_dsv32_traffic_file_builds_and_states_its_window():
    from perfbench.traffic import Traffic

    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "backlog16-16k-256.json")) as f:
        spec = json.load(f)
    gen = Traffic(spec, 2**31 + 77)
    assert gen.min_ticks == spec.get("min_ticks", 1) >= 1
    assert gen.outstanding == 16
    assert set(spec) <= {"loop", "outstanding", "min_ticks", "cycle",
                         "tasks", "check"}
    assert sum(n for _, n in gen.cycle) % gen.outstanding == 0 \
        or gen.outstanding % sum(n for _, n in gen.cycle) == 0


def test_dsv32_reference_agrees_with_the_pipeline_in_float32():
    """The deepseek_v32 reference — one full forward pass, the per-head
    form at every position, `lax.top_k`'s selection, no cache — against
    the program's prefill in blocks and its decode in the latent form
    over the caches, through the family's own `compare`: in float32
    every id the program serves is the reference's first choice (the
    selection keeps 16 of up to 63 keys here), and another prompt's ids
    are not."""
    import jax

    from perfbench import system, weights

    with open(os.path.join(TINY_DSV32, "configs", "tiny-dsv32.json")) as f:
        cfg = json.load(f)
    entry = cfg["models"][0]
    cell = mf.Cell(os.path.join(TINY_DSV32, "manifest.json"),
                   "tiny-dsv32-backlog")
    model = system.Model(entry, cell.family)
    arch = copy.deepcopy(entry["arch"])
    arch["model"]["dtype"] = "float32"
    model.arch = arch
    pipe, _ = model.family.build(arch, "bf16")
    shapes = jax.eval_shape(lambda: pipe.init_params(seed=0))
    model.params = weights.make(shapes, 2**31 + 23, cfg["weights"]["init"])
    prompts = ["a miner asks the chip for a line", "zephyr yarrow xenon willow"]
    got, routed = pipe.generate(model.params, prompts, [11, 2**40 + 5],
                                prompt_bucket=32, decode_bucket=32)
    assert got.shape == (2, 32) and got.max() < 256
    assert not np.array_equal(got[0], got[1])
    assert 0 < routed[1] < routed[0] == 2 * 63 * 2 * 2
    recs = [{"input": {"prompt": p, "max_new_tokens": 32}} for p in prompts]
    for rec, ids in zip(recs, got):
        out = model.family.compare(model, rec, ids)
        assert out["logit_gap"]["value"] == 0.0 == out["gap_rms"]["value"]
        assert out["logit_gap"]["positions"] == 32
    crossed = model.family.compare(model, recs[0], got[1])["logit_gap"]
    assert crossed["value"] > 3 * entry["limits"]["logit_gap"]
    assert crossed["not_first"] >= 2
    text = bytes(int(t) for t in got[0])
    assert np.array_equal(
        model.family.decode(text, {"max_new_tokens": 32}), got[0])
