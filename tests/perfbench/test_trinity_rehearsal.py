"""The cell `trinity-ep8-8k-backlog` rehearsed on the CPU through the
harness, from a manifest of its own beside the tiny one
(`tiny-trinity/manifest.json`: the family's tiny topology, half its
experts and part of its vocabulary held, prompts four times the tiny
window): the served run comes out correct with both new counts on its
traced line, the fp8 control and two slots' answers swapped do not; and
the reference's FLOP count at the REAL cell's shapes, from shapes alone:
the window's and the causal pair counts, and the routed experts at the
expected load."""
from __future__ import annotations

import argparse
import os
import time

import numpy as np
import pytest

from pb_paths import ROOT

MANIFEST = os.path.join(ROOT, "tests", "perfbench", "tiny-trinity",
                        "manifest.json")
CELL = "tiny-trinity-backlog"


def _run(control=None, trace=0, seed=2147484001):
    from perfbench import harness

    code, line = harness.run_cell(argparse.Namespace(
        workload=CELL, seed=seed, seconds=0.5, trace=trace,
        manifest=MANIFEST, control=control), time.perf_counter())
    assert code == 0
    return line


@pytest.mark.parametrize("case", ["served", "fp8", "swapped"])
def test_rehearsal_served_control_and_swapped_slots(case, monkeypatch,
                                                    compile_cache_restored):
    if case == "swapped":
        from arbius_tpu.node.solver import TextGenRunner

        finalize = TextGenRunner.finalize
        # every bucket's texts leave finalize in the other slot's place;
        # the routers' counts beside them are the bucket's, untouched
        monkeypatch.setattr(
            TextGenRunner, "finalize", lambda self, dev, n_real: finalize(
                self, ((dev[0][0][np.asarray((1, 0))], dev[0][1]), dev[1]),
                n_real))
    line = _run(control="fp8" if case == "fp8" else None,
                trace=int(case == "served"))
    c = line["compared"]["logit_gap.trinity"]
    assert line["compared"]["chain_mismatch"] == {"value": 0, "limit": 0}
    assert set(line["compared"]) == {"chain_mismatch", "logit_gap.trinity",
                                     "gap_rms.trinity"}
    r = line["compared"]["gap_rms.trinity"]
    assert line["attempted"] == line["solved"] > 0 and line["failed"] == 0
    assert line["compile_cache"]["lookups_in_window"] == 0
    if case == "served":
        assert line["correct"] is True and c["value"] <= c["limit"]
        assert r["value"] <= r["limit"]
        m = line["metrics"]
        # 4 rings of 8 rows and one full layer of 32 + 32, of 5 x 64
        assert m["kv_rows_held_pct"]["value"] == 100.0 * 96 / 320
        # 4 of 8 experts held: half of the assignments, within sampling
        assert 45.0 < m["expert_assign_held_pct"]["value"] < 55.0
        assert m["padded_slot_pct"]["value"] == 0.0
    else:
        assert line["correct"] is False and c["value"] > c["limit"]
        assert r["value"] > r["limit"]
        if case == "swapped":
            assert c["value"] > 3 * c["limit"]


@pytest.mark.parametrize("wrong,mean_says,rms_says", [
    (0, True, True), (1, True, True), (2, True, False), (4, True, False),
    (8, False, False)])
def test_gap_rms_moves_on_a_local_fault_that_the_mean_dilutes(
        wrong, mean_says, rms_says):
    """At the cell's 256 positions and limits: a task whose served ids
    are the reference's own but for `wrong` positions holding a random
    byte. The mean gives way at about six wrong tokens, the root mean
    square at two (one reads 0.17-0.19 on top of a sound task's 0.07:
    under the limit, which has to clear sound runs' 0.12)."""
    from perfbench import manifest

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    model = cell.config["models"][0]
    fam = cell.family(model["family"])
    rng = np.random.default_rng(28)
    ref = rng.standard_normal((256, 256)).astype(np.float32)
    ids = ref.argmax(axis=-1)
    at = rng.choice(256, size=wrong, replace=False)
    ids[at] = ref[at].argsort(axis=-1)[:, 128]      # a middling byte
    got = fam.gaps(ref, ids)
    assert set(got) == set(fam.COMPARED) == set(model["limits"])
    assert (got["logit_gap"]["value"] <= model["limits"]["logit_gap"]) \
        is mean_says
    assert (got["gap_rms"]["value"] <= model["limits"]["gap_rms"]) \
        is rms_says


def test_flop_count_at_the_cells_shapes_window_pairs_and_expert_load():
    """From shapes alone (`jax.eval_shape`), at the published widths and
    the cell's batch, prompt edge and decode edge."""
    from perfbench import flops, manifest

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    model = cell.config["models"][0]
    fam = cell.family(model["family"])
    arch = model["arch"]
    import jax

    pipe, _ = fam.build(arch, "bf16")
    shapes = jax.eval_shape(
        lambda: pipe.init_params(seed=0, dtype="bfloat16"))
    from perfbench import weights

    assert weights.count(shapes) == cell.config["parameters"]["total"] \
        == 4_321_903_872
    task = {**model["defaults"], "prompt": "x" * 7000}
    b, s, w = 16, 8192 + 256 - 1, 4096
    parts = flops.count_parts(fam.reference, arch, task, shapes, batch=b)
    fwd = parts["forward"]
    assert fwd["calls"] == 1 and fwd["attn_calls"] == []
    sliding = w * (w + 1) // 2 + (s - w) * w      # min(i + 1, w) summed
    full = s * (s + 1) // 2
    pairs: dict = {}
    for bb, h, sq, sk, d, n in fwd["masked_attn_calls"]:
        assert (bb, h, d) == (b, 48, 128)
        pairs[sk - sq] = pairs.get(sk - sq, 0) + n
    # 17 blocks a layer; 4 sliding layers and 1 full
    assert len(fwd["masked_attn_calls"]) == 5 * 17
    assert sum(pairs.values()) == 4 * sliding + full
    assert fwd["attn"] == 4.0 * b * 48 * 128 * (4 * sliding + full)
    # routed experts: tokens x 4 choices x 32/256 held, three products
    per_token = 3 * 2 * 3072 * 3072
    assert fwd["other"] == {
        "experts": 4 * (b * s * 4 * 32 / 256) * per_token}
    assert fam.kernel_calls(fwd["attn_calls"]) == []
    # one solution: the dense part (attention projections, dense mlp,
    # router, shared experts, head at 256 positions) dominates
    one = flops.total(flops.count_parts(fam.reference, arch, task, shapes))
    assert one * b == pytest.approx(flops.total(parts), rel=1e-12)
    assert 12e12 < one < 15e12
