"""The cell `joyai-ep1-2k-512-backlog` rehearsed on the CPU through the
harness, from a manifest of its own (`tiny-joyai/manifest.json`: the
family's tiny topology with the multi-token prediction module, batch 4,
two buckets a tick): the served run comes out correct with the loop's
two counts on its traced line, the fp8 control does not, and a program
that takes the token after next from the second position's logits after
a REJECTED draft does not; the two new readers on hand-built spans."""
from __future__ import annotations

import argparse
import os
import time

import pytest

from pb_paths import ROOT

MANIFEST = os.path.join(ROOT, "tests", "perfbench", "tiny-joyai",
                        "manifest.json")
CELL = "tiny-joyai-backlog"
REAL = "joyai-ep1-2k-512-backlog"


def _run(control=None, trace=0, seed=2147484101):
    from perfbench import harness

    code, line = harness.run_cell(argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.0, trace=trace,
        manifest=MANIFEST, control=control), time.perf_counter())
    assert code == 0
    return line


@pytest.mark.parametrize("case", ["served", "fp8", "second_after_reject"])
def test_rehearsal_served_control_and_a_wrongly_taken_token(
        case, monkeypatch, compile_cache_restored):
    if case == "second_after_reject":
        from arbius_tpu.models.joyai_flash import pipeline

        # the fault kept as a test: the loop takes t_{n+1} from L1
        # whether or not the draft was the sampler's choice
        monkeypatch.setattr(pipeline, "accept",
                            lambda t_n, drafted, room: room)
    line = _run(control="fp8" if case == "fp8" else None,
                trace=int(case == "served"))
    assert line["compared"]["chain_mismatch"] == {"value": 0, "limit": 0}
    assert set(line["compared"]) == {
        "chain_mismatch", "logit_gap.joyai_llm_flash",
        "gap_rms.joyai_llm_flash"}
    c = line["compared"]["logit_gap.joyai_llm_flash"]
    r = line["compared"]["gap_rms.joyai_llm_flash"]
    assert line["attempted"] == line["solved"] == 8 and line["failed"] == 0
    assert line["compile_cache"]["lookups_in_window"] == 0
    if case == "served":
        assert line["correct"] is True
        assert c["value"] <= c["limit"] and r["value"] <= r["limit"]
        m = line["metrics"]
        # chance over the ids the answers visit, and nearly no waiting:
        # two buckets of 4 rows x 31 steps less the accepted drafts
        assert 0.0 <= m["mtp_accept_pct"]["value"] < 10.0
        assert 0.0 <= m["spec_row_steps_idle_pct"]["value"] < 10.0
        assert m["padded_slot_pct"]["value"] == 0.0
        assert not set(m) & {"kv_rows_held_pct", "latent_cache_pct",
                             "index_pairs_kept_pct"}
    else:
        assert line["correct"] is False
        assert c["value"] > c["limit"]
        detail = line["window_detail"]
        if case == "fp8":
            # the control is the reference's own pass: the program, and
            # so its counts, are the served run's; a lower precision is
            # not correct by the mean alone at this size
            assert r["value"] <= r["limit"]
            assert detail["mtp_accept_pct"] < 10.0
        else:
            # every draft with room "accepted": half the steps, and
            # wrong tokens by both numbers
            assert detail["mtp_accept_pct"] == 100.0
            assert c["value"] > 5 * c["limit"] and r["value"] > r["limit"]


def _span(name, **attrs):
    return {"name": name, "t0": 0.0, "t1": 1.0, "attrs": attrs}


class _Run:
    def __init__(self, spans):
        self.spans = spans


@pytest.mark.parametrize("name,spans,value", [
    ("mtp_accept_pct",
     [_span("text.speculate", batch=32, steps=500, drafts=15800,
            accepted=316, idle_row_steps=40, tokens=16384),
      _span("text.speculate", batch=32, steps=505, drafts=15900,
            accepted=318, idle_row_steps=0, tokens=16384)],
     100.0 * 634 / 31700),
    ("spec_row_steps_idle_pct",
     [_span("text.speculate", batch=32, steps=500, drafts=15800,
            accepted=316, idle_row_steps=40, tokens=16384),
      _span("text.speculate", batch=16, steps=250, drafts=1, accepted=0,
            idle_row_steps=60, tokens=16384)],
     100.0 * 100 / (32 * 500 + 16 * 250)),
    # a trained module: nine drafts in ten accepted, rows wait
    ("mtp_accept_pct",
     [_span("text.speculate", batch=2, steps=270, drafts=500, accepted=450,
            idle_row_steps=30, tokens=1024)], 90.0),
    # the other text families' buckets carry no such span
    ("mtp_accept_pct",
     [_span("text.routed", assignments=10, held=10),
      _span("text.bucket", batch=16, kv_rows=24832, kv_rows_full=42240)],
     None),
    ("spec_row_steps_idle_pct", [_span("solve.dispatch", n=16)], None),
    ("spec_row_steps_idle_pct", [], None),
])
def test_the_two_readers_on_hand_built_spans(name, spans, value):
    from perfbench import manifest

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, REAL)
    assert name in {m["name"] for m in cell.per_layer()}
    got = cell.reader(name)(_Run(spans))
    assert got == (None if value is None else pytest.approx(value))


def test_the_diagnostic_runs_at_the_rehearsals_size(tmp_path, monkeypatch):
    """tools/joyai_diag.py, the builder's chip script, on the tiny
    configuration: the one-token loop it times reads the same ids as the
    speculative loop served (float32 would be exact; bfloat16 may differ
    on a rounding and the script counts where), and the module's drafts
    are read against the reference's module logits."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "joyai_diag", os.path.join(ROOT, "tools", "joyai_diag.py"))
    diag = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(diag)
    monkeypatch.setattr(diag, "ROOT", str(tmp_path))
    for mode in ("steps", "module"):
        assert diag.main([mode, "--tiny", "--tasks", "1", "--seed",
                          "2147484101", "--out", "diag.jsonl"]) == 0
    with open(tmp_path / "chiprun_out" / "diag.jsonl") as f:
        steps, module = (json.loads(x) for x in f)
    assert steps["what"] == "steps" and steps["batch"] == 4
    assert 4 * 31 == 4 * steps["spec_steps"] - steps["idle_row_steps"] \
        + steps["accepted"]
    assert steps["served_ids_the_one_token_loop_reads_otherwise"] \
        <= steps["of"] // 10
    assert module["of"] == 31
    assert module["module_drafts"]["logit_gap"]["positions"] == 31
    assert module["module_drafts"]["logit_gap"]["value"] < 0.3
    assert module["drafts_equal_reference_first"] >= 20
