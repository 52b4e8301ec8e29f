"""What the dots3_note cell added to the benchmark, as files and appended
entries only: BENCHMARK.json's new configuration, cell and three
per-layer metrics (found by position counted from the FRONT, which a
later PR's appended entries do not move), the traffic file of the cell,
the configuration file against the catalog's numbers and floors and
against the harness's count, and the reference against the program's
pipeline at tiny size in float32."""
from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest

from pb_paths import ROOT

from perfbench import manifest as mf

TINY = os.path.join(ROOT, "tests", "perfbench", "tiny-dots3")
REAL = "dots3-ep8-8k-1k-backlog"
NEW = ("window_attention_s_per_sol", "window_cache_held_pct",
       "window_flash_roofline_pct")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_dots3_entries_are_appended_and_find_their_files(bench):
    assert [c["name"] for c in bench["configs"]][:6] == [
        "kandinsky2", "anythingv3-kandinsky2", "trinity-large-ep8",
        "deepseek-v32-ep16", "joyai-llm-flash-ep1", "dots3-note-prev-ep8"]
    assert bench["configs"][5] == {
        **bench["configs"][5],
        "file": "perfbench/configs/dots3-note-prev-ep8.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"]}
    assert len(bench["configs"][5]["why"]) <= 200
    assert [w["name"] for w in bench["workloads"]][:6] == [
        "k2-768-backlog", "mix-768-backlog", "trinity-ep8-8k-backlog",
        "dsv32-ep16-16k-backlog", "joyai-ep1-2k-512-backlog", REAL]
    assert bench["workloads"][5] == {
        **bench["workloads"][5], "config": "dots3-note-prev-ep8",
        "traffic": "backlog32-8k-1k", "chips": 1}
    assert len(bench["workloads"][5]["why"]) <= 200
    assert "8x" in bench["workloads"][5]["why"]
    assert [m["name"] for m in bench["per_layer"][25:28]] == list(NEW)
    for m, unit, better, source, layer in zip(
            bench["per_layer"][25:28], ("s", "%", "%"),
            ("lower", "lower", "higher"),
            ("device_trace", "program_counter", "device_trace"),
            ("window attention", "cache", "kernels")):
        assert m == {"name": m["name"], "unit": unit, "better": better,
                     "source": source, "layer": layer,
                     "moves": "sol_per_hour", "workloads": [REAL]}
    for m in bench["per_layer"][:25]:
        assert REAL not in m.get("workloads", [])
    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    fam = cell.family("dots3_note")
    text = mf.Cell(mf.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    assert fam.gaps is text.family("trinity").gaps      # imported, not copied
    assert fam.kernel_calls([]) == []
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= names
    # every metric that lists no cells is this cell's too
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= names
    for other in (w["name"] for w in bench["workloads"][:5]):
        assert not set(NEW) & {m["name"] for m in mf.Cell(
            mf.DEFAULT_MANIFEST, other).per_layer()}
    for name in NEW:
        assert callable(cell.reader(name))


def test_dots3_traffic_file_builds_and_states_its_window():
    from perfbench.traffic import Traffic

    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    gen = Traffic(cell.traffic, 2**31 + 77)
    assert gen.outstanding == 32 and gen.min_ticks in (1, 4)
    assert gen.models() == ["dots3_note"]
    assert cell.traffic["check"] == {"buckets": {"dots3_note": 1}}
    tasks = [gen.task()[1] for _ in range(64)]
    lengths = [len(t["prompt"]) for t in tasks]
    assert 6000 <= min(lengths) and max(lengths) <= 8000
    assert {(t["max_new_tokens"], t["sampler"]) for t in tasks} \
        == {(1024, "greedy")}
    # two buckets a tick, one prompt edge and one decode edge
    assert gen.outstanding == 2 * cell.config["node"]["canonical_batch"]


def test_the_configuration_keeps_the_catalogs_numbers_and_the_floors():
    """Every number of the source's config under its own key, but the
    three keys `reduced` names; the cut obeys the guide's floors (the
    dense layer and one whole period of four expert layers, at least 8
    routed experts, at least an eighth of the vocabulary); `parameters`
    adds up term by term to the harness's count of the tree a node
    loads."""
    import jax

    from perfbench import weights

    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    cfg = cell.config
    published = {
        "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
        "attention_gate_type": "headwise", "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
        "kv_lora_rank": 512, "max_position_embeddings": 524288,
        "model_type": "dots3_note", "moe_intermediate_size": 1536,
        "moe_layer_freq": 1, "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 128, "num_experts_per_tok": 8,
        "num_key_value_heads": 128, "q_lora_rank": 1024,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 80000000, "routed_scaling_factor": 1,
        "scoring_func": "sigmoid", "sliding_window_size": 513,
        "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 1024,
        "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
        "swa_q_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
        "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000,
        "swa_v_head_dim": 128, "tie_word_embeddings": False,
        "topk_method": "noaux_tc", "v_head_dim": 128}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == cell.config_entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["source"] == cell.config_entry["source"]
    types = cfg["layer_types"]
    assert len(types) == 46 and types.count("full_attention") == 13
    for key, was in (("num_hidden_layers", 46), ("n_routed_experts", 256),
                     ("vocab_size", 152064)):
        assert {"published", "held", "how"} <= set(cfg[key])
        assert cfg[key]["published"] == was
    arch = cfg["models"][0]["arch"]["model"]
    kinds = {"full_attention": "full", "sliding_attention": "sliding"}
    assert arch["layers"] == [["dense", "full"]] + [
        ["moe", kinds[t]] for t in types[1:5]]
    assert cfg["num_hidden_layers"]["held"] == len(arch["layers"]) == 5
    assert arch["experts_held"] == [0, cfg["n_routed_experts"]["held"]]
    assert cfg["n_routed_experts"]["held"] * 8 == 256
    assert arch["vocab_rows"] == [0, cfg["vocab_size"]["held"]]
    assert cfg["vocab_size"]["held"] * 8 == 152064
    share = cfg["node"]["textgen"]["share"]
    assert share == {k: arch[k] for k in ("experts_held", "vocab_rows",
                                          "layers")}
    assert (arch["hidden"], arch["heads"], arch["q_lora_rank"],
            arch["kv_lora_rank"], arch["qk_nope_head_dim"],
            arch["qk_rope_head_dim"], arch["v_head_dim"],
            arch["index_heads"], arch["index_head_dim"], arch["index_topk"],
            arch["rope_theta"]) \
        == (5120, 128, 1024, 512, 128, 64, 128, 64, 128, 2048, 8e7)
    assert (arch["swa_heads"], arch["swa_q_lora_rank"],
            arch["swa_kv_lora_rank"], arch["swa_qk_nope_head_dim"],
            arch["swa_qk_rope_head_dim"], arch["swa_v_head_dim"],
            arch["swa_rope_theta"], arch["window"]) \
        == (64, 1024, 1024, 192, 64, 128, 5e4, 513)
    assert (arch["dense_ff"], arch["expert_ff"], arch["num_experts"],
            arch["experts_per_token"], arch["route_scale"], arch["eps"]) \
        == (13824, 1536, 256, 8, 1.0, 1e-5)
    assert cfg["node"]["canonical_batch"] == 16
    seed = cfg["weights"]["seed"]
    assert isinstance(seed, int) and 0 <= seed < 2**31 + 2**20
    assert set(cfg["models"][0]["limits"]) == {"logit_gap", "gap_rms"}
    assert len(cfg["assumed"]) >= 8
    assert {"logit_gap", "gap_rms"} <= set(cfg["limit_readings"])

    fam = cell.family("dots3_note")
    pipe, _ = fam.build(cfg["models"][0]["arch"], "bf16")
    shapes = jax.eval_shape(
        lambda: pipe.init_params(seed=0, dtype="bfloat16"))
    par = {k.split(" (")[0]: v for k, v in cfg["parameters"].items()}
    assert weights.count(shapes) == par["total"] == 4_087_154_176
    assert par["bytes bfloat16"] == 2 * par["total"] >= 0.25 * 16e9
    assert weights.count(shapes["layer_0"]) == par["layer 0, dense full"]
    assert weights.count(shapes["layer_1"]) == par["layer 1, expert full"]
    for i in (2, 3, 4):
        assert weights.count(shapes[f"layer_{i}"]) \
            == par["layers 2-4, expert sliding, each"]
    assert weights.count(shapes["layer_1"]["attn"]) \
        == par["full attention of a layer"]
    assert weights.count(shapes["layer_1"]["indexer"]) \
        == par["indexer of a full layer"]
    assert weights.count(shapes["layer_2"]["attn"]) \
        == par["sliding attention of a layer"]
    assert weights.count(shapes["layer_1"]["moe"]["experts"]) \
        == par["of an expert layer routed experts held"] \
        == 32 * par["one expert"]
    # every leaf has an init rule
    weights.plan(shapes, cfg["weights"]["init"])


def test_dots3_reference_agrees_with_the_pipeline_in_float32():
    """The reference — one full forward pass, the per-head form at every
    position, the band as a mask, no cache, no ring — against the
    program's prefill and decode steps in the latent form, through the
    family's own `compare`: in float32 every id the program serves is
    the reference's first choice, and another prompt's ids are not."""
    import jax

    from perfbench import system, weights

    with open(os.path.join(TINY, "configs", "tiny-dots3.json")) as f:
        cfg = json.load(f)
    entry = cfg["models"][0]
    cell = mf.Cell(os.path.join(TINY, "manifest.json"),
                   "tiny-dots3-backlog")
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
    assert 0 < int(routed[1]) < int(routed[0])
    recs = [{"input": {"prompt": p, "max_new_tokens": 32}} for p in prompts]
    for rec, ids in zip(recs, got):
        out = model.family.compare(model, rec, ids)
        assert out["logit_gap"]["value"] == 0.0 == out["gap_rms"]["value"]
        assert out["logit_gap"]["positions"] == 32
    crossed = model.family.compare(model, recs[0], got[1])["logit_gap"]
    assert crossed["value"] > 3 * entry["limits"]["logit_gap"]
    text = bytes(int(t) for t in got[0])
    assert np.array_equal(
        model.family.decode(text, {"max_new_tokens": 32}), got[0])
