"""What the nemotron_h cell added to the benchmark, as files and appended
entries only: BENCHMARK.json's new configuration, cell and two per-layer
metrics (found by position counted from the FRONT, which a later PR's
appended entries do not move), the traffic file of the cell, the
configuration file against the catalog's numbers and floors and against
the harness's count, and the reference against the program's pipeline at
tiny size in float32."""
from __future__ import annotations

import copy
import json
import math
import os

import numpy as np
import pytest

from pb_paths import ROOT

from perfbench import manifest as mf

TINY = os.path.join(ROOT, "tests", "perfbench", "tiny-nemotron")
REAL = "nemotron3-ep4-2k-1k-backlog"
NEW = ("mamba_mixer_s_per_sol", "latent_moe_s_per_sol")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_nemotron_entries_are_appended_and_find_their_files(bench):
    assert [c["name"] for c in bench["configs"]][:7] == [
        "kandinsky2", "anythingv3-kandinsky2", "trinity-large-ep8",
        "deepseek-v32-ep16", "joyai-llm-flash-ep1", "dots3-note-prev-ep8",
        "nemotron3-super-ep4"]
    assert bench["configs"][6] == {
        **bench["configs"][6],
        "source": "https://huggingface.co/nvidia/"
                  "NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/"
                  "config.json",
        "file": "perfbench/configs/nemotron3-super-ep4.json",
        "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"]}
    assert len(bench["configs"][6]["why"]) <= 200
    assert [w["name"] for w in bench["workloads"]][:7] == [
        "k2-768-backlog", "mix-768-backlog", "trinity-ep8-8k-backlog",
        "dsv32-ep16-16k-backlog", "joyai-ep1-2k-512-backlog",
        "dots3-ep8-8k-1k-backlog", REAL]
    assert bench["workloads"][6] == {
        **bench["workloads"][6], "config": "nemotron3-super-ep4",
        "traffic": "backlog64-2k-1k", "chips": 1}
    why = bench["workloads"][6]["why"]
    assert len(why) <= 200 and "1/4 load" in why and "4x" in why
    assert [m["name"] for m in bench["per_layer"][28:30]] == list(NEW)
    for m, layer in zip(bench["per_layer"][28:30],
                        ("state-space layer", "expert layer")):
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": "device_trace", "layer": layer,
                     "moves": "sol_per_hour", "workloads": [REAL]}
    for m in bench["per_layer"][:28]:
        assert REAL not in m.get("workloads", [])
    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    fam = cell.family("nemotron_h")
    text = mf.Cell(mf.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    assert fam.gaps is text.family("trinity").gaps      # imported, not copied
    assert fam.kernel_calls([]) == []
    names = {m["name"] for m in cell.per_layer()}
    assert set(NEW) <= names
    # every metric that lists no cells is this cell's too
    assert {m["name"] for m in bench["per_layer"]
            if "workloads" not in m} <= names
    for other in (w["name"] for w in bench["workloads"][:6]):
        assert not set(NEW) & {m["name"] for m in mf.Cell(
            mf.DEFAULT_MANIFEST, other).per_layer()}
    for name in NEW:
        assert callable(cell.reader(name))


def test_nemotron_traffic_file_builds_and_states_its_window():
    from perfbench.traffic import Traffic

    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    gen = Traffic(cell.traffic, 2**31 + 77)
    assert gen.outstanding == 64 and gen.min_ticks in (1, 2)
    assert gen.models() == ["nemotron_h"]
    assert cell.traffic["check"] == {"buckets": {"nemotron_h": 1}}
    tasks = [gen.task()[1] for _ in range(128)]
    lengths = [len(t["prompt"]) for t in tasks]
    assert 1000 <= min(lengths) and max(lengths) <= 2000
    assert {(t["max_new_tokens"], t["sampler"]) for t in tasks} \
        == {(1024, "greedy")}
    # two buckets a tick, one prompt edge and one decode edge
    assert gen.outstanding == 2 * cell.config["node"]["canonical_batch"]


# the source's config as the catalog row gives it, every key but the
# three `reduced` names
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True}


def test_the_configuration_keeps_the_catalogs_numbers_and_the_floors():
    """Every number of the source's config under its own key, but the
    three keys `reduced` names; the cut obeys the guide's floors (one
    whole period of the pattern — five Mamba-2, five expert, one
    attention layer —, at least 8 routed experts, at least an eighth of
    the vocabulary); `parameters` adds up term by term to the harness's
    count of the tree a node loads; every leaf has an init rule, and the
    state-space leaves are the published initialisation's."""
    import jax

    from perfbench import weights

    cell = mf.Cell(mf.DEFAULT_MANIFEST, REAL)
    cfg = cell.config
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["reduced"] == cell.config_entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["source"] == cell.config_entry["source"]
    for key, was in (("num_hidden_layers", 88), ("n_routed_experts", 512),
                     ("vocab_size", 131072)):
        assert {"published", "held", "how"} <= set(cfg[key])
        assert cfg[key]["published"] == was
    arch = cfg["models"][0]["arch"]["model"]
    kinds = {"M": "mamba", "E": "moe", "*": "attention"}
    assert arch["layers"] == [kinds[c] for c in
                              PUBLISHED["hybrid_override_pattern"][:11]]
    assert cfg["num_hidden_layers"]["held"] == len(arch["layers"]) == 11
    assert arch["experts_held"] == [0, cfg["n_routed_experts"]["held"]]
    assert cfg["n_routed_experts"]["held"] * 4 == 512
    assert arch["vocab_rows"] == [0, cfg["vocab_size"]["held"]]
    assert cfg["vocab_size"]["held"] * 8 == 131072
    share = cfg["node"]["textgen"]["share"]
    assert share == {k: arch[k] for k in ("experts_held", "vocab_rows",
                                          "layers")}
    assert (arch["hidden"], arch["mamba_heads"], arch["mamba_head_dim"],
            arch["ssm_state"], arch["n_groups"], arch["conv_kernel"],
            arch["chunk_size"], arch["heads"], arch["kv_heads"],
            arch["head_dim"], arch["latent"], arch["expert_ff"],
            arch["shared_ff"], arch["num_experts"],
            arch["experts_per_token"], arch["route_scale"], arch["eps"]) \
        == (4096, 128, 64, 128, 8, 4, 128, 32, 2, 128, 1024, 2688, 5376,
            512, 22, 5.0, 1e-5)
    assert (arch["time_step_min"], arch["time_step_max"],
            arch["time_step_floor"]) == (0.001, 0.1, 0.0001)
    assert cfg["node"]["canonical_batch"] == 32
    seed = cfg["weights"]["seed"]
    assert isinstance(seed, int) and 0 <= seed < 2**31 + 2**20
    assert set(cfg["models"][0]["limits"]) == {"logit_gap", "gap_rms"}
    assert len(cfg["assumed"]) >= 8
    assert {"logit_gap", "gap_rms"} <= set(cfg["limit_readings"])

    fam = cell.family("nemotron_h")
    pipe, _ = fam.build(cfg["models"][0]["arch"], "bf16")
    shapes = jax.eval_shape(
        lambda: pipe.init_params(seed=0, dtype="bfloat16"))
    par = {k.split(" (")[0]: v for k, v in cfg["parameters"].items()}
    assert weights.count(shapes) == par["total"] == 5_342_342_016
    assert par["bytes bfloat16"] == 2 * par["total"] >= 0.25 * 16e9
    for i, kind in enumerate(arch["layers"]):
        want = {"mamba": par["Mamba layer"],
                "attention": par["attention layer"],
                "moe": par["expert layer with 128 held"]}[kind]
        assert weights.count(shapes[f"layer_{i}"]) == want
    moe = shapes["layer_1"]
    assert weights.count(moe) - weights.count(moe["moe"]["experts"]) \
        == par["expert layer's non-routed part"]
    assert weights.count(moe["moe"]["experts"]) \
        == par["of it routed experts held"] == 128 * par["one expert"]
    assert weights.count(shapes["mtp"]) == par["multi-token prediction module"]
    assert weights.count({k: shapes[k] for k in ("embed", "head")}) \
        == par["embedding and head"]
    # every leaf has an init rule; the state-space leaves are drawn as
    # the published model initialises them, a list a Mamba layer
    weights.plan(shapes, cfg["weights"]["init"])
    rules = cfg["weights"]["init"]
    for i, kind in enumerate(arch["layers"]):
        if kind != "mamba":
            continue
        by = {leaf: next(r for r in rules
                         if r["match"] == f"^layer_{i}/mixer/{leaf}$")
              for leaf in ("A_log", "dt_bias")}
        a = np.exp(by["A_log"]["mean"])
        dt = np.log1p(np.exp(by["dt_bias"]["mean"]))
        assert len(a) == len(dt) == 128
        assert by["A_log"]["std"] == by["dt_bias"]["std"] == 0.0
        assert 1.0 <= a.min() < 2.0 and 15.0 < a.max() <= 16.0
        assert 0.001 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
        assert math.log(dt.max() / dt.min()) > 3.0


def test_nemotron_reference_agrees_with_the_pipeline_in_float32():
    """The reference — one full forward pass, the recurrence one position
    at a time, full causal attention, experts masked by routing, no cache
    — against the program's chunked prefill and speculative steps with
    their commits, through the family's own `compare`: in float32 every
    id the program serves is the reference's first choice, and another
    prompt's ids are not."""
    import jax

    from perfbench import system, weights

    with open(os.path.join(TINY, "configs", "tiny-nemotron.json")) as f:
        cfg = json.load(f)
    entry = cfg["models"][0]
    cell = mf.Cell(os.path.join(TINY, "manifest.json"),
                   "tiny-nemotron-backlog")
    model = system.Model(entry, cell.family)
    arch = copy.deepcopy(entry["arch"])
    arch["model"]["dtype"] = "float32"
    model.arch = arch
    pipe, _ = model.family.build(arch, "bf16")
    shapes = jax.eval_shape(lambda: pipe.init_params(seed=0))
    model.params = weights.make(shapes, 2**31 + 23, cfg["weights"]["init"])
    prompts = ["a miner asks the chip for a line",
               "zephyr yarrow xenon willow"]
    got, routed, spec = pipe.generate(model.params, prompts, [11, 2**40 + 5],
                                      prompt_bucket=32, decode_bucket=32)
    assert got.shape == (2, 32) and got.max() < 256
    assert not np.array_equal(got[0], got[1])
    assert 0 < int(routed[1]) < int(routed[0])
    recs = [{"input": {"prompt": p, "max_new_tokens": 32}} for p in prompts]
    for rec, ids in zip(recs, got):
        out = model.family.compare(model, rec, ids)
        assert out["logit_gap"]["value"] == 0.0 == out["gap_rms"]["value"]
        assert out["logit_gap"]["positions"] == 32
    crossed = model.family.compare(model, recs[0],
                                   (got[0] + 101) % 256)["logit_gap"]
    assert crossed["value"] > 3 * entry["limits"]["logit_gap"]
    text = bytes(int(t) for t in got[0])
    assert np.array_equal(
        model.family.decode(text, {"max_new_tokens": 32}), got[0])
