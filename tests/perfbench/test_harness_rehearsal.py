"""The harness end to end at the tiny presets on the CPU, as the driver
calls it: every configuration and traffic file of the tests' manifest (two
whose solutions are pictures, one whose solution is text), a well-formed last
line, no device metric off the chip, and a non-zero exit with no result
at full size without a TPU or without the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from pb_paths import ROOT, TINY_MANIFEST

ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}


def _run(args, cwd=ROOT, run_py=None):
    cmd = [sys.executable, run_py or os.path.join("perfbench", "run.py")]
    return subprocess.run(cmd + args, cwd=cwd, env=ENV, capture_output=True,
                          text=True, timeout=900)


@pytest.fixture(scope="module")
def lines():
    out = {}
    for cell, trace in (("tiny-k2-backlog", 0), ("tiny-mix-backlog", 1),
                        ("tiny-text-backlog", 1)):
        p = _run(["--manifest", TINY_MANIFEST, "--workload", cell, "--seed",
                  "2147483999", "--seconds", "1", "--trace", str(trace)])
        assert p.returncode == 0, p.stderr[-3000:]
        out[cell] = (json.loads(p.stdout.strip().splitlines()[-1]), p.stderr)
    return out


CELLS = {"tiny-k2-backlog": {"image_mad.kandinsky2"},
         "tiny-mix-backlog": {"image_mad.kandinsky2", "image_mad.anythingv3"},
         "tiny-text-backlog": {"logit_gap.textgen"}}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_last_line_is_well_formed(lines, cell):
    line, _ = lines[cell]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] == line["solved"] > 0 and line["failed"] == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_tiny_cells_come_out_correct(lines, cell):
    line, err = lines[cell]
    assert line["correct"] is True, err[-2000:]
    assert line["compared"]["chain_mismatch"] == {"value": 0, "limit": 0}
    # what each model's family compares, and nothing the harness adds
    assert set(line["compared"]) == CELLS[cell] | {"chain_mismatch"}
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    # every number compared is on the last lines of stderr beside its limit
    tail = err.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and "limit" in t for t in tail)


def test_no_device_metric_is_printed_off_the_chip(lines):
    with open(TINY_MANIFEST) as f:
        manifest = json.load(f)
    counts = {m["name"] for m in manifest["per_layer"]
              if m["source"] == "program_counter"}
    untraced = lines["tiny-k2-backlog"][0]                   # --trace 0
    assert untraced["metrics"] == {}
    # ... whose line says its ticks and, off the chip, the counts alone
    detail = untraced["window_detail"]
    assert len(detail["tick_s"]) == untraced["ticks"]
    assert set(detail) - {"tick_s"} <= counts and "padded_slot_pct" in detail
    for cell in ("tiny-mix-backlog", "tiny-text-backlog"):
        traced = lines[cell][0]
        assert set(traced["metrics"]) == counts
        assert "busy_s" not in traced["device"] and "breakdown" not in traced
        assert "window_detail" not in traced
    traced = lines["tiny-mix-backlog"][0]
    # the mix's under-filled buckets show in the one count there is
    assert traced["metrics"]["padded_slot_pct"]["value"] == 0.0 \
        or traced["metrics"]["padded_slot_pct"]["value"] > 0


def test_the_line_says_what_ran(lines):
    for cell, (line, _) in lines.items():
        assert line["workload"] == cell and line["seed"] == 2147483999
        assert line["window_s"] >= 1.0 and line["ticks"] >= 1
        assert set(line["timings"]) == {"param_init_s", "bucket_warm_s"}
        assert line["compile_cache"]["lookups_in_window"] == 0


@pytest.mark.parametrize("cell", ["k2-768-backlog", "mix-768-backlog"])
def test_full_size_without_a_tpu_exits_non_zero_and_prints_nothing(cell):
    p = _run(["--workload", cell, "--seed", "7", "--seconds", "1",
              "--trace", "0"])
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_alone_in_a_directory_it_exits_non_zero_and_prints_nothing(tmp_path):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in manifest["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "k2-768-backlog", "--seed", "7", "--seconds", "1",
              "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
