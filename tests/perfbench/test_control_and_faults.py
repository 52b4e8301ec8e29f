"""`correct` has to come out false when it should: the control (the
reference put in the served answer's place and computed in fp8) and the
faults a serving cell can have, planted under the harness at the tiny size, in a cell
whose solutions are pictures and in one whose solutions are text — an
answer altered where it is produced (every task gets another task's
answer; one slot of every bucket does), bytes that are no answer to the
task, and a revealed CID that is not the CID of the pinned bytes (the
program's `evilmode`). The harness's look for a chip is skipped by the
tiny configurations' `rehearsal` mark; the rest of the run is the real one.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
import pytest

from pb_paths import ROOT, TINY_MANIFEST


# cell -> (the number its family compares, the runner whose `finalize`
# hands the answers on, how to put a bucket's answers into another order)
CELLS = {
    "tiny-k2-backlog": (
        "image_mad.kandinsky2", "Kandinsky2Runner",
        lambda images, order: [images[i] for i in order]),
    "tiny-text-backlog": (
        "logit_gap.textgen", "TextGenRunner",
        lambda dev, order: (dev[0][np.asarray(order)], dev[1])),
}


def _args(manifest=TINY_MANIFEST, control=None, seed=2147484001,
          cell="tiny-k2-backlog"):
    return argparse.Namespace(workload=cell, seed=seed,
                              seconds=0.5, trace=0, manifest=manifest,
                              control=control)


def _reorder(monkeypatch, cell, order):
    """Every bucket's answers leave `finalize` in `order` of the slots."""
    from arbius_tpu.node import solver

    _, runner, reorder = CELLS[cell]
    cls = getattr(solver, runner)
    finalize = cls.finalize
    monkeypatch.setattr(
        cls, "finalize", lambda self, dev, n_real: finalize(
            self, reorder(dev, order), n_real))


def _run(args):
    from perfbench import harness

    code, line = harness.run_cell(args, time.perf_counter())
    assert code == 0
    return line


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_comes_out_not_correct(cell, compile_cache_restored):
    line = _run(_args(control="fp8", cell=cell))
    c = line["compared"][CELLS[cell][0]]
    assert c["value"] > c["limit"] and line["correct"] is False
    assert line["compared"]["chain_mismatch"]["value"] == 0
    assert line["control"] == "fp8"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_an_answer_altered_where_it_is_produced(cell, monkeypatch,
                                                compile_cache_restored):
    _reorder(monkeypatch, cell, (1, 0))
    line = _run(_args(cell=cell))
    c = line["compared"][CELLS[cell][0]]
    assert c["value"] > 3 * c["limit"] and line["correct"] is False
    # the bytes are still the bytes that were pinned and revealed
    assert line["compared"]["chain_mismatch"]["value"] == 0


@pytest.mark.parametrize("slot", [0, 1])
def test_one_slot_of_every_bucket_altered(slot, monkeypatch,
                                          compile_cache_restored):
    """The sample is whole buckets, so a fault in one slot of the batched
    program (here: that slot gets its neighbour's picture) is seen
    whichever slot it is."""
    _reorder(monkeypatch, "tiny-k2-backlog", (1 - slot,) * 2)
    line = _run(_args())
    c = line["compared"]["image_mad.kandinsky2"]
    assert c["value"] > 3 * c["limit"] and line["correct"] is False
    assert line["compared"]["chain_mismatch"]["value"] == 0


def test_bytes_that_are_no_answer_to_the_task(monkeypatch,
                                              compile_cache_restored):
    """What a family cannot decode is a chain_mismatch, whatever the CIDs
    say: here every text comes a byte short of the tokens asked for."""
    from arbius_tpu.node.solver import TextGenRunner

    finalize = TextGenRunner.finalize
    monkeypatch.setattr(
        TextGenRunner, "finalize", lambda self, dev, n_real: [
            {k: v[:-1] for k, v in files.items()}
            for files in finalize(self, dev, n_real)])
    line = _run(_args(cell="tiny-text-backlog"))
    assert line["compared"]["chain_mismatch"]["value"] == line["solved"] > 0
    assert line["correct"] is False
    # nothing decodable was compared, and the line says so
    assert line["not_compared"] == ["textgen"]
    assert "logit_gap.textgen" not in line["compared"]


def test_a_revealed_cid_that_is_not_the_bytes_cid(tmp_path,
                                                  compile_cache_restored):
    tiny = os.path.dirname(TINY_MANIFEST)
    with open(TINY_MANIFEST) as f:
        manifest = json.load(f)
    manifest["paths"] = [tiny, os.path.join(ROOT, "perfbench")]
    for c in manifest["configs"]:
        with open(os.path.join(tiny, c["file"])) as f:
            cfg = json.load(f)
        cfg["node"]["evilmode"] = True
        for m in cfg["models"]:
            m["template_file"] = os.path.join(tiny, "templates",
                                              m["template"] + ".json")
        c["file"] = str(tmp_path / (c["name"] + ".json"))
        with open(c["file"], "w") as f:
            json.dump(cfg, f)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    line = _run(_args(manifest=str(path)))
    assert line["compared"]["chain_mismatch"]["value"] == line["solved"] > 0
    assert line["correct"] is False
