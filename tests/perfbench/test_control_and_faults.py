"""`correct` has to come out false when it should: the control (the
reference put in the served image's place and computed in fp8) and the faults a
serving cell can have, planted under the harness at the tiny size — an
answer altered where it is produced (every task gets another task's
picture; one slot of every bucket does), and a revealed CID that is not the CID of the pinned bytes (the
program's `evilmode`). The harness's look for a chip is skipped by the
tiny configurations' `rehearsal` mark; the rest of the run is the real one.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import pytest

from pb_paths import ROOT, TINY_MANIFEST


def _args(manifest=TINY_MANIFEST, control=None, seed=2147484001):
    return argparse.Namespace(workload="tiny-k2-backlog", seed=seed,
                              seconds=0.5, trace=0, manifest=manifest,
                              control=control)


def _run(args):
    from perfbench import harness

    code, line = harness.run_cell(args, time.perf_counter())
    assert code == 0
    return line


def test_control_comes_out_not_correct(compile_cache_restored):
    line = _run(_args(control="fp8"))
    c = line["compared"]["image_mad.kandinsky2"]
    assert c["value"] > c["limit"] and line["correct"] is False
    assert line["compared"]["chain_mismatch"]["value"] == 0
    assert line["control"] == "fp8"


def test_an_answer_altered_where_it_is_produced(monkeypatch,
                                                compile_cache_restored):
    from arbius_tpu.node.solver import Kandinsky2Runner

    finalize = Kandinsky2Runner.finalize

    def swapped(self, images, n_real):
        return finalize(self, images[::-1], n_real)

    monkeypatch.setattr(Kandinsky2Runner, "finalize", swapped)
    line = _run(_args())
    c = line["compared"]["image_mad.kandinsky2"]
    assert c["value"] > 3 * c["limit"] and line["correct"] is False
    # the bytes are still the bytes that were pinned and revealed
    assert line["compared"]["chain_mismatch"]["value"] == 0


@pytest.mark.parametrize("slot", [0, 1])
def test_one_slot_of_every_bucket_altered(slot, monkeypatch,
                                          compile_cache_restored):
    """The sample is whole buckets, so a fault in one slot of the batched
    program (here: that slot gets its neighbour's picture) is seen
    whichever slot it is."""
    from arbius_tpu.node.solver import Kandinsky2Runner

    finalize = Kandinsky2Runner.finalize

    def one_slot(self, images, n_real):
        images = list(images)
        images[slot] = images[1 - slot]
        return finalize(self, images, n_real)

    monkeypatch.setattr(Kandinsky2Runner, "finalize", one_slot)
    line = _run(_args())
    c = line["compared"]["image_mad.kandinsky2"]
    assert c["value"] > 3 * c["limit"] and line["correct"] is False
    assert line["compared"]["chain_mismatch"]["value"] == 0


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
