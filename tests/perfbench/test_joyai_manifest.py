"""What PR 36 added to the benchmark, as files and appended entries only:
BENCHMARK.json's new configuration, cell and two per-layer metrics, the
traffic file of the cell, the configuration file against the catalog's
floors and against the harness's count, and the joyai_llm_flash
reference against the program's pipeline at tiny size in float32."""
from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest

from pb_paths import ROOT

from perfbench import manifest as mf

TINY_JOYAI = os.path.join(ROOT, "tests", "perfbench", "tiny-joyai")
REAL = "joyai-ep1-2k-512-backlog"
NEW = ("mtp_accept_pct", "spec_row_steps_idle_pct")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_joyai_entries_are_appended_and_find_their_files(bench):
    """Appended behind what PR 34 left (the fifth configuration, the
    fifth cell, per-layer metrics 18 and 19: found by POSITION counted
    from the front, which a later PR's appended entries do not move —
    `test_dsv32_manifest.py` counts from the back and so turns red with
    every configuration added after its own), each new metric read in
    the new cell alone; no accepted metric's list changed."""
    assert [c["name"] for c in bench["configs"]][:5] == [
        "kandinsky2", "anythingv3-kandinsky2", "trinity-large-ep8",
        "deepseek-v32-ep16", "joyai-llm-flash-ep1"]
    assert bench["configs"][4] == {
        **bench["configs"][4],
        "file": "perfbench/configs/joyai-llm-flash-ep1.json",
        "reduced": ["num_hidden_layers", "vocab_size"]}
    assert bench["workloads"][4] == {
        **bench["workloads"][4], "name": REAL,
        "config": "joyai-llm-flash-ep1", "traffic": "backlog64-2k-512",
        "chips": 1}
    assert bench["workloads"][3]["name"] == "dsv32-ep16-16k-backlog"
    assert len(bench["workloads"][4]["why"]) <= 200
    assert [m["name"] for m in bench["per_layer"][15:19]] == [
        "latent_cache_pct", "index_pairs_kept_pct", *NEW]
    for m, better in zip(bench["per_layer"][17:19], ("higher", "lower")):
        assert m == {"name": m["name"], "unit": "%", "better": better,
                     "source": "program_counter",
                     "layer": "speculative decode",
                     "moves": "sol_per_hour", "workloads": [REAL]}
    for m in bench["per_layer"][:17]:
        assert REAL not in m.get("workloads", [])
    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    fam = cell.family("joyai_llm_flash")
    text = mf.Cell(mf.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    assert fam.gaps is text.family("trinity").gaps      # imported, not copied
    assert fam.kernel_calls([]) == []
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= names
    # every metric that lists no cells is this cell's too
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= names
    assert not names & {"kv_rows_held_pct", "latent_cache_pct",
                        "index_pairs_kept_pct", "expert_assign_held_pct",
                        "flash_roofline_pct", "causal_flash_roofline_pct"}
    for other in (w["name"] for w in bench["workloads"][:4]):
        assert not set(NEW) & {m["name"] for m in mf.Cell(
            mf.DEFAULT_MANIFEST, other).per_layer()}
    for name in NEW:
        assert callable(cell.reader(name))


def test_joyai_traffic_file_builds_and_states_its_window():
    from perfbench.traffic import Traffic

    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    gen = Traffic(cell.traffic, 2**31 + 77)
    assert (gen.outstanding, gen.min_ticks) == (64, 4)
    assert set(cell.traffic) <= {"loop", "outstanding", "min_ticks",
                                 "cycle", "tasks", "check"}
    assert gen.models() == ["joyai_llm_flash"]
    assert cell.traffic["check"] == {"buckets": {"joyai_llm_flash": 1}}
    tasks = [gen.task()[1] for _ in range(128)]
    lengths = [len(t["prompt"]) for t in tasks]
    assert 1000 <= min(lengths) and max(lengths) <= 2000
    assert {(t["max_new_tokens"], t["sampler"]) for t in tasks} \
        == {(512, "greedy")}
    # two buckets' worth a tick (the node takes in 50 solves a tick, so
    # they run as three: PERF.md section 5), one prompt and decode edge
    assert gen.outstanding == 2 * cell.config["node"]["canonical_batch"]


def test_the_configuration_keeps_the_catalogs_numbers_and_the_floors():
    """Every number of the source's config under its own key, but the
    two keys `reduced` names; the cut obeys the guide's floors (a whole
    period and four expert layers after the dense one, at least 8 routed
    experts, at least an eighth of the vocabulary) and holds ALL the
    experts and the module; `parameters` adds up term by term to the
    harness's count of the tree a node loads."""
    import jax

    from perfbench import weights

    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    cfg = cell.config
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
        "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"] \
        == cell.config_entry["reduced"]
    assert cfg["source"] == cell.config_entry["source"]
    for key, was in (("num_hidden_layers", 40), ("vocab_size", 129280)):
        assert {"published", "held", "how"} <= set(cfg[key])
        assert cfg[key]["published"] == was
    arch = cfg["models"][0]["arch"]["model"]
    assert arch["layers"] == ["dense"] + ["moe"] * 4
    assert cfg["num_hidden_layers"]["held"] == len(arch["layers"]) == 5
    assert arch["experts_held"] == [0, 256] and arch["num_experts"] == 256
    assert arch["vocab_rows"] == [0, cfg["vocab_size"]["held"]]
    assert cfg["vocab_size"]["held"] * 8 >= 129280
    share = cfg["node"]["textgen"]["share"]
    assert share == {k: arch[k] for k in ("experts_held", "vocab_rows",
                                          "layers")}
    assert (arch["hidden"], arch["heads"], arch["q_lora_rank"],
            arch["kv_lora_rank"], arch["qk_nope_head_dim"],
            arch["qk_rope_head_dim"], arch["v_head_dim"], arch["dense_ff"],
            arch["expert_ff"], arch["experts_per_token"],
            arch["route_scale"], arch["rope_theta"], arch["eps"]) \
        == (2048, 32, 1536, 512, 128, 64, 128, 7168, 768, 8, 2.5, 32e6,
            1e-6)
    assert cfg["node"]["canonical_batch"] == 32
    seed = cfg["weights"]["seed"]
    assert isinstance(seed, int) and 0 <= seed < 2**31 + 2**20
    assert set(cfg["models"][0]["limits"]) == {"logit_gap", "gap_rms"}
    assert len(cfg["assumed"]) >= 8
    assert {"logit_gap", "gap_rms"} <= set(cfg["limit_readings"])

    fam = cell.family("joyai_llm_flash")
    pipe, _ = fam.build(cfg["models"][0]["arch"], "bf16")
    shapes = jax.eval_shape(
        lambda: pipe.init_params(seed=0, dtype="bfloat16"))
    par = {k.split(" (")[0]: v for k, v in cfg["parameters"].items()}
    assert weights.count(shapes) == par["total"] == 6_342_751_488
    assert par["bytes bfloat16"] == 2 * par["total"] >= 10e9
    assert weights.count(shapes["layer_0"]) == par["dense layer"]
    assert weights.count(shapes["layer_1"]) == par["expert layer"]
    assert weights.count(shapes["mtp"]) \
        == par["multi-token prediction module"]
    assert weights.count(shapes["layer_1"]["attn"]) \
        == par["attention of a layer"]
    assert weights.count(shapes["layer_1"]["moe"]["experts"]) \
        == par["of it routed experts held"] == 256 * par["one expert"]
    assert par["total"] == par["dense layer"] + 4 * par["expert layer"] \
        + par["multi-token prediction module"] \
        + par["embedding and head"] + par["final norm"]
    # every leaf has an init rule, and no rule is for a leaf of another
    # family (the indexer's LayerNorm bias)
    weights.plan(shapes, cfg["weights"]["init"])
    assert not any("k_norm" in r["match"] for r in cfg["weights"]["init"])


def test_flop_count_at_the_cells_shapes_leaves_the_module_out():
    """From shapes alone (`jax.eval_shape`): what a solution needs is the
    main model's pass over prompt + 512 positions, once; the module's
    pass is listed under `other` "mtp" with no calls, outside the count
    that `model_mfu_pct` reads."""
    import jax

    from perfbench import flops

    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    model = cell.config["models"][0]
    fam = cell.family(model["family"])
    arch = model["arch"]
    pipe, _ = fam.build(arch, "bf16")
    shapes = jax.eval_shape(
        lambda: pipe.init_params(seed=0, dtype="bfloat16"))
    task = {**model["defaults"], "prompt": "x" * 1500}
    s = 2048 + 512 - 1
    parts = flops.count_parts(fam.reference, arch, task, shapes)
    assert set(parts) == {"forward", "mtp"}
    fwd, mtp = parts["forward"], parts["mtp"]
    assert (fwd["calls"], mtp["calls"]) == (1, 0)
    causal = s * (s + 1) // 2
    per_token = 3 * 2 * 2048 * 768
    assert fwd["other"] == {
        "attention": 5 * 2.0 * 32 * (192 + 128) * causal,
        "experts": 4 * (s * 8) * per_token}
    assert fwd["attn_calls"] == [] and fwd["masked_attn_calls"] == []
    assert set(mtp["other"]) == {"mtp"}
    assert mtp["flops"] == mtp["other"]["mtp"] and mtp["dense"] == 0.0
    # the module is one expert layer, eh_proj and a head over the same
    # positions: about a quarter of the five main layers' work
    assert 0.2 < mtp["flops"] / fwd["flops"] < 0.35
    one = flops.total(parts)
    assert one == fwd["flops"] and 1.9e12 < one < 2.4e12
    assert flops.solution_flops(fam.reference, arch, task, shapes) == one
    both = flops.count_parts(fam.reference, arch, task, shapes, batch=2)
    assert flops.total(both) == pytest.approx(2 * one, rel=1e-12)


def test_joyai_reference_agrees_with_the_pipeline_in_float32():
    """The reference — one full forward pass, the per-head form at every
    position, no cache, no speculation — against the program's prefill
    in blocks and its two-position steps in the latent form, through the
    family's own `compare`: in float32 every id the speculative program
    serves is the reference's first choice, and another prompt's ids are
    not; the module's half of `both_logits` has a row for every draft."""
    import jax

    from perfbench import system, weights

    with open(os.path.join(TINY_JOYAI, "configs", "tiny-joyai.json")) as f:
        cfg = json.load(f)
    entry = cfg["models"][0]
    cell = mf.Cell(os.path.join(TINY_JOYAI, "manifest.json"),
                   "tiny-joyai-backlog")
    model = system.Model(entry, cell.family)
    arch = copy.deepcopy(entry["arch"])
    arch["model"]["dtype"] = "float32"
    model.arch = arch
    pipe, _ = model.family.build(arch, "bf16")
    shapes = jax.eval_shape(lambda: pipe.init_params(seed=0))
    model.params = weights.make(shapes, 2**31 + 23, cfg["weights"]["init"])
    prompts = ["a miner asks the chip for a line", "zephyr yarrow xenon willow"]
    got, routed, spec = pipe.generate(model.params, prompts, [11, 2**40 + 5],
                                      prompt_bucket=32, decode_bucket=32)
    assert got.shape == (2, 32) and got.max() < 256
    assert not np.array_equal(got[0], got[1])
    assert int(routed[0]) == int(routed[1]) > 0
    assert 2 * 31 == 2 * int(spec[0]) - int(spec[3]) + int(spec[2])
    recs = [{"input": {"prompt": p, "max_new_tokens": 32}} for p in prompts]
    for rec, ids in zip(recs, got):
        out = model.family.compare(model, rec, ids)
        assert out["logit_gap"]["value"] == 0.0 == out["gap_rms"]["value"]
        assert out["logit_gap"]["positions"] == 32
    crossed = model.family.compare(model, recs[0], got[1])["logit_gap"]
    assert crossed["value"] > 3 * entry["limits"]["logit_gap"]
    main, guess = model.family.reference.both_logits(
        model.params, arch, model.hydrated(recs[0]["input"]), got[0])
    assert main.shape == (32, 256) and guess.shape == (31, 256)
    assert np.array_equal(main.argmax(axis=-1), got[0])
    text = bytes(int(t) for t in got[0])
    assert np.array_equal(
        model.family.decode(text, {"max_new_tokens": 32}), got[0])
