"""The cell `dots3-ep8-8k-1k-backlog` rehearsed on the CPU through the
harness, from a manifest of its own (`tiny-dots3/manifest.json`: the
family's tiny topology — two full layers, three sliding layers with a
ring of 9 latent rows —, half its experts and part of its vocabulary
held, prompts that outgrow the tiny window and `index_topk`): the served
run comes out correct with the cache count on its traced line, the fp8
control and a program whose sliding layers drop the window (every
causal key attended, the ring as long as the sequence) do not; the new
readers on hand-built spans and peaks; and the reference's FLOP count at
the REAL cell's shapes, from shapes alone."""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import pytest

from pb_paths import ROOT

MANIFEST = os.path.join(ROOT, "tests", "perfbench", "tiny-dots3",
                        "manifest.json")
CELL = "tiny-dots3-backlog"
REAL = "dots3-ep8-8k-1k-backlog"
NEW = ("window_attention_s_per_sol", "window_cache_held_pct",
       "window_flash_roofline_pct")


def _run(control=None, trace=0, seed=2147484301):
    from perfbench import harness

    code, line = harness.run_cell(argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.0, trace=trace,
        manifest=MANIFEST, control=control), time.perf_counter())
    assert code == 0
    return line


# rings of 9 latent rows of 28 in three layers, latent and indexer rows
# of 20 + 8 in two, against the rings' layers at 64 positions
HELD = 100.0 * (3 * 9 * 28 + 2 * 64 * 28) / (3 * 64 * 28 + 2 * 64 * 28)


@pytest.mark.parametrize("case", ["served", "fp8", "dropped"])
def test_rehearsal_served_control_and_dropped_window(
        case, monkeypatch, compile_cache_restored):
    if case == "dropped":
        from arbius_tpu.models.dots3.model import Dots3NoteConfig

        # the sliding layers attend to every causal key, through a cache
        # as long as the sequence: a dense fallback
        real = Dots3NoteConfig.attn
        monkeypatch.setattr(Dots3NoteConfig, "attn", lambda self, k: (
            dataclasses.replace(real(self, k), window=10**9)
            if k == "sliding" else real(self, k)))
        monkeypatch.setattr(Dots3NoteConfig, "cache_rows",
                            lambda self, attn, total: total)
    line = _run(control="fp8" if case == "fp8" else None,
                trace=int(case == "served"))
    assert line["compared"]["chain_mismatch"] == {"value": 0, "limit": 0}
    assert set(line["compared"]) == {
        "chain_mismatch", "logit_gap.dots3_note", "gap_rms.dots3_note"}
    c = line["compared"]["logit_gap.dots3_note"]
    r = line["compared"]["gap_rms.dots3_note"]
    assert line["attempted"] == line["solved"] == 4 and line["failed"] == 0
    assert line["compile_cache"]["lookups_in_window"] == 0
    if case == "served":
        assert line["correct"] is True
        assert c["value"] <= c["limit"] and r["value"] <= r["limit"]
        m = line["metrics"]
        assert m["window_cache_held_pct"]["value"] == pytest.approx(HELD)
        assert 35.0 < m["expert_assign_held_pct"]["value"] < 65.0
        assert m["padded_slot_pct"]["value"] == 0.0
        # off the chip only counts are printed
        assert "window_flash_roofline_pct" not in m
        assert "window_attention_s_per_sol" not in m
    else:
        assert line["correct"] is False
        assert c["value"] > c["limit"] and r["value"] > r["limit"]
        detail = line["window_detail"]
        if case == "dropped":
            assert c["value"] > 3 * c["limit"]
            assert detail["window_cache_held_pct"] == 100.0
        else:
            assert detail["window_cache_held_pct"] == pytest.approx(HELD)


def _span(name, **attrs):
    return {"name": name, "t0": 0.0, "t1": 1.0, "attrs": attrs}


class _Run:
    def __init__(self, spans):
        self.spans = spans


@pytest.mark.parametrize("spans,value", [
    # the cell's bucket: 29,301,120 of 86,114,304 B a sequence
    ([_span("text.bucket", batch=16, cache_bytes_window=3348864,
            cache_bytes_full=25952256, cache_bytes_window_full=60162048)],
     100.0 * 29301120 / 86114304),
    # a change that drops the ring reads 100
    ([_span("text.bucket", batch=16, cache_bytes_window=60162048,
            cache_bytes_full=25952256, cache_bytes_window_full=60162048)],
     100.0),
    # another family's buckets carry other attributes: nothing to read
    ([_span("text.bucket", batch=8, cache_bytes=117145600,
            cache_bytes_per_head=6815744000)], None),
    ([], None),
])
def test_the_cache_reader_on_hand_built_spans(spans, value):
    from perfbench import manifest

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, REAL)
    got = cell.reader("window_cache_held_pct")(_Run(spans))
    assert got == (None if value is None else pytest.approx(value))
    assert round(100.0 * 29301120 / 86114304, 2) == 34.03


def test_the_roofline_reader_on_a_hand_built_trace():
    """One bucket of 16 dispatched, its three sliding layers' banded
    calls taking 96 ms on the device in all: the least time is the
    band's FLOPs at the bf16 peak (0.200 TFLOP a layer a sequence, 1.016
    ms at 197 TFLOP/s; the 0.74 GB of bytes would take 0.90 ms at 819
    GB/s), 48 calls of it."""
    from perfbench import manifest, peaks
    from perfbench.reference import dots3_note as ref

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, REAL)
    fam = cell.family("dots3_note")
    model = cell.config["models"][0]
    task = {**model["defaults"], "prompt": "x" * 7000}
    pk = peaks.peaks_for("TPU v5 lite")
    flops, nbytes = ref.window_work(64, 8192, 513, 192, 64, 128)
    assert ref.band_pairs(8192, 513) == 4_071_168
    assert flops == 64 * 384 * 2 * 4_071_168
    assert nbytes == 739_246_080
    one = fam.window_kernel_floor_s(model["arch"], task, pk)
    assert one == pytest.approx(3 * flops / pk["bf16_flops"])
    assert 3 * nbytes / pk["hbm_bytes_per_s"] < one

    class System:
        canonical_batch = 16
        models = [type("M", (), {"template": "dots3_note", "family": fam,
                                 "arch": model["arch"]})()]

    class Run:
        trace = {"events": [("window_flash_attention.3", 0.0, 0.032)] * 3
                 + [("fusion.1", 0.0, 1.0)]}
        peaks = pk
        system = System
        parts = {"dots3_note": {16: {}}}
        first_task = {"dots3_note": task}
        spans = [_span("bench.dispatch", model="dots3_note")]

    got = cell.reader("window_flash_roofline_pct")(Run)
    assert got == pytest.approx(100.0 * 16 * one / 0.096)
    Run.trace = {"events": [("fusion.1", 0.0, 1.0)]}
    assert cell.reader("window_flash_roofline_pct")(Run) is None


def test_flop_count_at_the_cells_shapes_band_selection_and_expert_load():
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
        == 4_087_154_176
    task = {**model["defaults"], "prompt": "x" * 7000}
    s, k, w = 8192 + 1024 - 1, 2048, 513
    fwd = flops.count_parts(fam.reference, arch, task, shapes)["forward"]
    assert fwd["calls"] == 1
    assert fwd["attn_calls"] == [] and fwd["masked_attn_calls"] == []
    kept = k * (k + 1) // 2 + (s - k) * k
    band = w * (w + 1) // 2 + (s - w) * w
    causal = s * (s + 1) // 2
    assert fwd["other"] == {
        "attention": 2 * 2.0 * 128 * (192 + 128) * kept,
        "indexer": 2 * 2.0 * 64 * 128 * causal,
        "window_attention": 3 * 2.0 * 64 * (256 + 128) * band,
        "experts": 4 * (s * 8 * 32 / 256) * 3 * 2 * 5120 * 1536}
    one = flops.total({"forward": fwd})
    # 22.84 TFLOP a solution: projections, gates, MLPs, shared experts
    # and the head most of it; the band's attention 3 %
    assert 22.5e12 < one < 23.2e12
    assert 0.02 < fwd["other"]["window_attention"] / one < 0.04
    assert fam.kernel_calls(fwd["attn_calls"]) == []
    two = flops.count_parts(fam.reference, arch, task, shapes, batch=2)
    assert flops.total(two) == pytest.approx(2 * one, rel=1e-12)


def test_the_diagnostic_runs_at_the_rehearsals_size(tmp_path, monkeypatch):
    """tools/dots3_diag.py, the chip script that chose the weight draw
    and timed the banded kernel, on the tiny configuration: a scan reads
    the routers' held share of a draw; the kernel (interpreted) and the
    walk agree exactly in float32 arithmetic on bfloat16 inputs of a
    32-position prompt under a window of 9."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "dots3_diag", os.path.join(ROOT, "tools", "dots3_diag.py"))
    diag = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(diag)
    monkeypatch.setattr(diag, "ROOT", str(tmp_path))
    assert diag.main(["scan", "--tiny", "--draws", "2147484301,2147484302",
                      "--out", "diag.jsonl"]) == 0
    assert diag.main(["kernel", "--tiny", "--out", "diag.jsonl"]) == 0
    with open(tmp_path / "chiprun_out" / "diag.jsonl") as f:
        first, second, kernel = (json.loads(x) for x in f)
    for rec, draw in ((first, 2147484301), (second, 2147484302)):
        assert rec["what"] == "scan" and rec["draw"] == draw
        # a bucket of 2, 32 + 32 - 1 positions, 2 experts a token, 4
        # expert layers
        assert rec["held"] <= rec["assignments"] == 2 * 63 * 2 * 4
        assert rec["held_pct"] == pytest.approx(
            100.0 * rec["held"] / rec["assignments"])
    assert first["compiled_in_it"] and not second["compiled_in_it"]
    assert (kernel["what"], kernel["positions"], kernel["window"]) \
        == ("kernel", 32, 9)
    assert kernel["max_abs_diff"] == 0.0
