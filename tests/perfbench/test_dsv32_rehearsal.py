"""The cell `dsv32-ep16-16k-backlog` rehearsed on the CPU through the
harness, from a manifest of its own (`tiny-dsv32/manifest.json`: the
family's tiny topology, half its experts and part of its vocabulary
held, prompts that outgrow the tiny `index_topk`): the served run comes
out correct with the two new counts on its traced line, the fp8 control
and a program whose selection keeps every key do not; the two new
readers on hand-built spans; and the reference's FLOP count at the REAL
cell's shapes, from shapes alone: the selection's pairs, the index
scores' and the routed experts at the expected load."""
from __future__ import annotations

import argparse
import os
import time

import pytest

from pb_paths import ROOT

MANIFEST = os.path.join(ROOT, "tests", "perfbench", "tiny-dsv32",
                        "manifest.json")
CELL = "tiny-dsv32-backlog"
REAL = "dsv32-ep16-16k-backlog"


def _run(control=None, trace=0, seed=2147484001):
    from perfbench import harness

    code, line = harness.run_cell(argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.0, trace=trace,
        manifest=MANIFEST, control=control), time.perf_counter())
    assert code == 0
    return line


@pytest.mark.parametrize("case", ["served", "fp8", "dropped"])
def test_rehearsal_served_control_and_dropped_selection(
        case, monkeypatch, compile_cache_restored):
    if case == "dropped":
        import jax.numpy as jnp

        from arbius_tpu.models.deepseek_v32 import model as dsv32

        # the program attends to every causal key: a dense fallback
        monkeypatch.setattr(dsv32, "select_topk",
                            lambda scores, k: jnp.ones(scores.shape, bool))
    line = _run(control="fp8" if case == "fp8" else None,
                trace=int(case == "served"))
    assert line["compared"]["chain_mismatch"] == {"value": 0, "limit": 0}
    assert set(line["compared"]) == {
        "chain_mismatch", "logit_gap.deepseek_v32", "gap_rms.deepseek_v32"}
    c = line["compared"]["logit_gap.deepseek_v32"]
    r = line["compared"]["gap_rms.deepseek_v32"]
    assert line["attempted"] == line["solved"] == 4 and line["failed"] == 0
    assert line["compile_cache"]["lookups_in_window"] == 0
    if case == "served":
        assert line["correct"] is True
        assert c["value"] <= c["limit"] and r["value"] <= r["limit"]
        m = line["metrics"]
        # a 20-wide latent row and an 8-wide indexer key against 4 heads'
        # 12-wide keys and 8-wide values
        assert m["latent_cache_pct"]["value"] == 100.0 * 28 / 80
        # min(t + 1, 16) of the 63 positions a bucket computes
        assert m["index_pairs_kept_pct"]["value"] == pytest.approx(
            100.0 * (16 * 17 // 2 + 47 * 16) / (63 * 64 // 2))
        # 8 of 16 experts held, two routing groups of the four
        assert 35.0 < m["expert_assign_held_pct"]["value"] < 65.0
        assert m["padded_slot_pct"]["value"] == 0.0
        assert "kv_rows_held_pct" not in m
    else:
        assert line["correct"] is False
        assert c["value"] > c["limit"] and r["value"] > r["limit"]
        detail = line["window_detail"]
        assert detail["latent_cache_pct"] == 100.0 * 28 / 80
        if case == "dropped":
            assert c["value"] > 3 * c["limit"]


def _span(name, **attrs):
    return {"name": name, "t0": 0.0, "t1": 1.0, "attrs": attrs}


class _Run:
    def __init__(self, spans):
        self.spans = spans


@pytest.mark.parametrize("name,spans,value", [
    ("latent_cache_pct",
     [_span("text.bucket", batch=8, cache_bytes=117145600,
            cache_bytes_per_head=6815744000),
      _span("text.bucket", batch=3, cache_bytes=117145600,
            cache_bytes_per_head=6815744000)], 1.71875),
    # a change that expands the cache per head reads 100
    ("latent_cache_pct",
     [_span("text.bucket", batch=8, cache_bytes=50, cache_bytes_per_head=50)],
     100.0),
    ("index_pairs_kept_pct",
     [_span("text.bucket", batch=8, attn_pairs=30, attn_pairs_causal=120),
      _span("text.bucket", batch=4, attn_pairs=60, attn_pairs_causal=120)],
     100.0 * (8 * 30 + 4 * 60) / (12 * 120)),
    # trinity's buckets carry other attributes: nothing to read
    ("latent_cache_pct",
     [_span("text.bucket", batch=16, kv_rows=24832, kv_rows_full=42240)],
     None),
    ("index_pairs_kept_pct",
     [_span("text.bucket", batch=16, kv_rows=24832, kv_rows_full=42240),
      _span("solve.dispatch", n=16)], None),
    ("index_pairs_kept_pct", [], None),
])
def test_the_two_readers_on_hand_built_spans(name, spans, value):
    from perfbench import manifest

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, REAL)
    assert name in {m["name"] for m in cell.per_layer()}
    got = cell.reader(name)(_Run(spans))
    assert got == (None if value is None else pytest.approx(value))


def test_the_new_metrics_are_the_new_cells_alone():
    from perfbench import manifest

    real = {m["name"] for m in manifest.Cell(manifest.DEFAULT_MANIFEST,
                                             REAL).per_layer()}
    assert {"latent_cache_pct", "index_pairs_kept_pct",
            "expert_assign_held_pct", "model_mfu_pct",
            "device_idle_pct"} <= real
    assert not real & {"kv_rows_held_pct", "causal_flash_roofline_pct",
                       "flash_roofline_pct"}
    for other in ("k2-768-backlog", "mix-768-backlog",
                  "trinity-ep8-8k-backlog"):
        names = {m["name"] for m in manifest.Cell(
            manifest.DEFAULT_MANIFEST, other).per_layer()}
        assert not names & {"latent_cache_pct", "index_pairs_kept_pct"}


def test_flop_count_at_the_cells_shapes_selected_pairs_and_expert_load():
    """From shapes alone (`jax.eval_shape`), at the published widths and
    the cell's prompt edge and decode edge; the harness's count of
    parameters on the device is the configuration file's."""
    import jax

    from perfbench import flops, manifest, weights

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, REAL)
    model = cell.config["models"][0]
    fam = cell.family(model["family"])
    arch = model["arch"]
    pipe, _ = fam.build(arch, "bf16")
    shapes = jax.eval_shape(
        lambda: pipe.init_params(seed=0, dtype="bfloat16"))
    assert weights.count(shapes) == cell.config["parameters"]["total"] \
        == 4_635_518_208
    task = {**model["defaults"], "prompt": "x" * 14000}
    s, k = 16384 + 256 - 1, 2048
    fwd = flops.count_parts(fam.reference, arch, task, shapes)["forward"]
    assert fwd["calls"] == 1
    assert fwd["attn_calls"] == [] and fwd["masked_attn_calls"] == []
    kept = k * (k + 1) // 2 + (s - k) * k
    causal = s * (s + 1) // 2
    per_token = 3 * 2 * 7168 * 2048
    assert fwd["other"] == {
        "attention": 5 * 2.0 * 128 * (192 + 128) * kept,
        "indexer": 5 * 2.0 * 64 * 128 * causal,
        "experts": 4 * (s * 8 * 16 / 256) * per_token}
    assert fwd["attn"] == fwd["conv"] == 0.0
    one = flops.total({"forward": fwd})
    # projections, MLPs, shared experts and the head dominate; the
    # selected attention and the index scores are a third of the rest
    assert 79e12 < one < 81e12
    assert 0.29 < (fwd["other"]["attention"] + fwd["other"]["indexer"]) \
        / one < 0.31
    assert fam.kernel_calls(fwd["attn_calls"]) == []
    # a batch of 8 is eight sequences, each walked alone
    eight = flops.count_parts(fam.reference, arch, task, shapes, batch=8)
    assert flops.total(eight) == pytest.approx(8 * one, rel=1e-12)


def test_the_cell_states_its_draw_its_window_and_its_share():
    from perfbench import manifest
    from perfbench.traffic import Traffic

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, REAL)
    seed = cell.config["weights"]["seed"]
    assert isinstance(seed, int) and 0 <= seed < 2**31 + 2**20
    gen = Traffic(cell.traffic, 2**31 + 5)
    assert (gen.outstanding, gen.min_ticks) == (16, 1)
    lengths = [len(gen.task()[1]["prompt"]) for _ in range(64)]
    assert 12000 <= min(lengths) and max(lengths) <= 16000
    assert cell.config["node"]["canonical_batch"] == 8
    share = cell.config["node"]["textgen"]["share"]
    arch = cell.config["models"][0]["arch"]["model"]
    assert share == {k: arch[k] for k in ("experts_held", "vocab_rows",
                                          "layers")}
    assert cell.config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    for key in cell.config["reduced"]:
        assert {"published", "held", "how"} <= set(cell.config[key])
    # every width as published
    for key, want in (("hidden_size", 7168), ("num_attention_heads", 128),
                      ("q_lora_rank", 1536), ("kv_lora_rank", 512),
                      ("qk_nope_head_dim", 128), ("qk_rope_head_dim", 64),
                      ("v_head_dim", 128), ("index_n_heads", 64),
                      ("index_head_dim", 128), ("index_topk", 2048),
                      ("moe_intermediate_size", 2048),
                      ("intermediate_size", 18432),
                      ("num_experts_per_tok", 8), ("n_group", 8),
                      ("topk_group", 4)):
        assert cell.config[key] == want
    assert (arch["hidden"], arch["heads"], arch["index_topk"],
            arch["expert_ff"], arch["dense_ff"], arch["num_experts"]) \
        == (7168, 128, 2048, 2048, 18432, 256)
