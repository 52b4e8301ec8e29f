"""The cell `nemotron3-ep4-2k-1k-backlog` rehearsed on the CPU through the
harness, from a manifest of its own (`tiny-nemotron/manifest.json`: the
family's tiny topology — two Mamba-2 layers, one attention layer, two
latent expert layers and the module —, half its experts and part of its
vocabulary held, batch 4, two buckets a tick): the served run comes out
correct with its counts on the traced line, the fp8 control does not,
and a loop that keeps the recurrent state after a REJECTED draft does
not; the two new block readers on a hand-built split; the reference's
FLOP count at the REAL cell's shapes, from shapes alone; and the chip
diagnostic at the rehearsal's size."""
from __future__ import annotations

import argparse
import os
import time

import pytest

from pb_paths import ROOT

MANIFEST = os.path.join(ROOT, "tests", "perfbench", "tiny-nemotron",
                        "manifest.json")
CELL = "tiny-nemotron-backlog"
REAL = "nemotron3-ep4-2k-1k-backlog"
NEW = ("mamba_mixer_s_per_sol", "latent_moe_s_per_sol")


def _run(control=None, trace=0, seed=2147484401):
    from perfbench import harness

    code, line = harness.run_cell(argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.0, trace=trace,
        manifest=MANIFEST, control=control), time.perf_counter())
    assert code == 0
    return line


@pytest.mark.parametrize("case", ["served", "fp8", "state_after_reject"])
def test_rehearsal_served_control_and_a_state_kept_after_a_rejected_draft(
        case, monkeypatch, compile_cache_restored):
    if case == "state_after_reject":
        import jax.numpy as jnp

        from arbius_tpu.models.nemotron_h import model

        # the fault kept as a test: every row keeps the state and conv
        # window after q + 1, whether or not it took the draft
        real = model.commit
        monkeypatch.setattr(model, "commit", lambda caches, took, cfg: real(
            caches, jnp.ones_like(took), cfg))
    line = _run(control="fp8" if case == "fp8" else None,
                trace=int(case == "served"))
    assert line["compared"]["chain_mismatch"] == {"value": 0, "limit": 0}
    assert set(line["compared"]) == {
        "chain_mismatch", "logit_gap.nemotron_h", "gap_rms.nemotron_h"}
    c = line["compared"]["logit_gap.nemotron_h"]
    r = line["compared"]["gap_rms.nemotron_h"]
    assert line["attempted"] == line["solved"] == 8 and line["failed"] == 0
    assert line["compile_cache"]["lookups_in_window"] == 0
    if case == "served":
        assert line["correct"] is True
        assert c["value"] <= c["limit"] and r["value"] <= r["limit"]
        m = line["metrics"]
        # half the experts held: about half the assignments
        assert 35.0 < m["expert_assign_held_pct"]["value"] < 65.0
        assert 0.0 <= m["mtp_accept_pct"]["value"] < 10.0
        assert m["padded_slot_pct"]["value"] == 0.0
        # off the chip only counts are printed
        assert not set(NEW) & set(m)
    else:
        assert line["correct"] is False
        assert c["value"] > c["limit"] and r["value"] > r["limit"]
        if case == "state_after_reject":
            assert c["value"] > 5 * c["limit"]


def test_the_two_block_readers_on_a_hand_built_split(monkeypatch):
    """Each reads its block's seconds over the whole chunks' solutions,
    and nothing where no chunk holds the block (another family's
    program, or the parent's, which has no such scope)."""
    from perfbench import blocks, manifest

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, REAL)
    whole = [{"program": "nemotron_h.32.2048.1024.greedy", "n": 32,
              "ops_s": 20.0, "unblocked_s": 0.01,
              "blocks": {"mamba_mixer": 4.8, "latent_moe": 11.2,
                         "routed_experts": 10.4}},
             {"program": "nemotron_h.32.2048.1024.greedy", "n": 32,
              "ops_s": 20.0, "unblocked_s": 0.01,
              "blocks": {"mamba_mixer": 4.8, "latent_moe": 11.2}}]
    monkeypatch.setattr(blocks, "split", lambda run: whole)
    assert cell.reader("mamba_mixer_s_per_sol")(None) \
        == pytest.approx(9.6 / 64)
    assert cell.reader("latent_moe_s_per_sol")(None) \
        == pytest.approx(22.4 / 64)
    monkeypatch.setattr(blocks, "split", lambda run: [
        {**whole[0], "blocks": {"routed_experts": 3.0}}])
    for name in NEW:
        assert cell.reader(name)(None) is None


def test_flop_count_at_the_cells_shapes_scan_attention_and_expert_load():
    """From shapes alone (`jax.eval_shape`), at the published widths and
    the cell's prompt edge and decode edge: the chunked scan's products
    at chunk 128, attention's causal pairs, the held experts at the
    expected load (a quarter of 22 a token); the module left out of a
    solution."""
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
        == 5_342_342_016
    task = {**model["defaults"], "prompt": "x" * 1500}
    s = 2048 + 1024 - 1
    parts = flops.count_parts(fam.reference, arch, task, shapes)
    fwd = parts["forward"]
    assert fwd["calls"] == 1 and parts["mtp"]["calls"] == 0
    assert fwd["attn_calls"] == [] and fwd["masked_attn_calls"] == []
    # 23 whole chunks of 128 and one of 127
    pairs = 23 * 128 * 129 // 2 + 127 * 128 // 2
    assert fwd["other"] == {
        "ssd": 5 * (2.0 * (8 * 128 + 128 * 64) * pairs
                    + 4.0 * 128 * 64 * 128 * s),
        "attention": 2.0 * 32 * 256 * (s * (s + 1) // 2),
        "experts": 5 * (s * 22 * 128 / 512) * 2 * 2 * 1024 * 2688}
    one = flops.total(parts)
    # 6.49 TFLOP a solution: projections, shared experts and the head
    # most of it, the held experts a seventh, the scan and attention a
    # fiftieth each
    assert 6.4e12 < one < 6.6e12
    assert 0.12 < fwd["other"]["experts"] / one < 0.16
    assert fwd["other"]["ssd"] / one < 0.02
    assert fam.kernel_calls(fwd["attn_calls"]) == []
    two = flops.count_parts(fam.reference, arch, task, shapes, batch=2)
    assert flops.total(two) == pytest.approx(2 * one, rel=1e-12)


def test_the_diagnostic_runs_at_the_rehearsals_size(tmp_path, monkeypatch):
    """tools/nemotron_diag.py, the chip script that chose the weight
    draw, on the tiny configuration: a scan reads the routers' held share
    and the loop's counts of a draw."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "nemotron_diag", os.path.join(ROOT, "tools", "nemotron_diag.py"))
    diag = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(diag)
    monkeypatch.setattr(diag, "ROOT", str(tmp_path))
    assert diag.main(["scan", "--tiny", "--draws", "2147484401,2147484402",
                      "--decode", "8", "--out", "diag.jsonl"]) == 0
    with open(tmp_path / "chiprun_out" / "diag.jsonl") as f:
        first, second = (json.loads(x) for x in f)
    for rec, draw in ((first, 2147484401), (second, 2147484402)):
        assert rec["what"] == "scan" and rec["draw"] == draw
        assert (rec["prompt_bucket"], rec["decode_bucket"]) == (32, 8)
        # a bucket of 4: prefill's 32 positions in two expert layers,
        # the first draft, then a step's two positions in two layers and
        # the module, 2 experts a token
        assert rec["assignments"] == 4 * 2 * (32 * 2 + 1
                                              + 2 * 3 * rec["steps"])
        assert rec["held"] <= rec["assignments"]
        assert rec["held_pct"] == pytest.approx(
            100.0 * rec["held"] / rec["assignments"])
        assert 8 - 1 - rec["accepted"] <= rec["steps"] <= 8 - 1
    assert first["compiled_in_it"] and not second["compiled_in_it"]
