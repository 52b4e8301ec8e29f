"""The cell `trinity-ep8-8k-backlog` rehearsed on the CPU through the
harness, from a manifest of its own beside the tiny one
(`tiny-trinity/manifest.json`: the family's tiny topology, half its
experts and part of its vocabulary held, prompts four times the tiny
window): the served run comes out correct with both new counts on its
traced line, the fp8 control and two slots' answers swapped do not; and
the reference's FLOP count at the REAL cell's shapes, from shapes alone:
the window's and the causal pair counts, and the routed experts at the
expected load. Since PR 33 also: a configuration's stated weight draw
(`weights.seed`), a traffic file's `min_ticks`, the untraced line's
`window_detail`, and the causal kernel's roofline reader on a hand-made
trace at the real cell's shapes."""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
import pytest

from pb_paths import ROOT

MANIFEST = os.path.join(ROOT, "tests", "perfbench", "tiny-trinity",
                        "manifest.json")
CELL = "tiny-trinity-backlog"


def _run(control=None, trace=0, seed=2147484001, cell=CELL, seconds=0.5):
    from perfbench import harness

    code, line = harness.run_cell(argparse.Namespace(
        workload=cell, seed=seed, seconds=seconds, trace=trace,
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
        assert "window_detail" not in line      # the traced line has them
    else:
        assert len(line["window_detail"]["tick_s"]) == line["ticks"]
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


@pytest.mark.parametrize("cell,ticks", [(CELL, 1),
                                        ("tiny-trinity-3ticks", 3)])
def test_min_ticks_closes_the_window_and_the_untraced_line_says_its_ticks(
        cell, ticks, compile_cache_restored):
    """At `--seconds 0` a window closes at its first tick's return, as
    before, unless the traffic file's `min_ticks` holds it open: then
    after exactly that many. The `--trace 0` line carries, under
    `window_detail`, the seconds of each tick and (off the chip) the
    counts the traced line would, over the whole window."""
    line = _run(cell=cell, seconds=0.0)
    assert line["correct"] is True and line["ticks"] == ticks
    assert line["attempted"] == line["solved"] == 4 * ticks
    detail = line["window_detail"]
    assert len(detail["tick_s"]) == ticks
    assert all(t > 0 for t in detail["tick_s"])
    assert sum(detail["tick_s"]) <= line["window_s"]
    assert list(line)[-1] == "compared" and line["metrics"] == {}
    assert set(detail) == {"tick_s", "padded_slot_pct", "kv_rows_held_pct",
                           "expert_assign_held_pct"}
    assert detail["kv_rows_held_pct"] == 100.0 * 96 / 320
    assert detail["padded_slot_pct"] == 0.0
    assert 40.0 < detail["expert_assign_held_pct"] < 60.0


def test_a_traced_window_is_not_held_open_for_min_ticks(
        compile_cache_restored):
    """The traced run's readers see the first whole tick alone, so a
    `--trace 1` run closes at `--seconds` as a traffic file without
    `min_ticks` does: no later check pays for ticks that nothing reads."""
    line = _run(cell="tiny-trinity-3ticks", seconds=0.0, trace=1)
    assert line["correct"] is True and line["ticks"] == 1
    assert line["attempted"] == line["solved"] == 4
    assert "window_detail" not in line
    assert line["metrics"]["kv_rows_held_pct"]["value"] == 100.0 * 96 / 320


@pytest.mark.parametrize("stated", [True, False])
def test_a_stated_weight_draw_holds_the_weights_and_not_the_traffic(stated):
    """With `weights.seed` in the configuration two `--seed`s build the
    same parameter tree — the draw `--seed` of that value gave before the
    key was there — and send other prompts; without it, other trees."""
    import jax

    from perfbench import manifest, system, traffic

    cell = manifest.Cell(MANIFEST, CELL)
    config = cell.config
    if stated:
        config = {**config, "weights": {**config["weights"], "seed": 11}}

    def built(seed):
        sysm = system.System(config, seed, config_dir=cell.config_dir,
                             family=cell.family)
        try:
            sysm.build()
            params = jax.tree_util.tree_map(np.asarray,
                                            sysm.models[0].params)
        finally:
            sysm.close()
        gen = traffic.Traffic(cell.traffic, seed)
        return params, [gen.task()[1]["prompt"] for _ in range(4)]

    (p11, t11), (p12, t12) = built(11), built(12)
    same = jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda a, b: np.array_equal(a, b), p11, p12))
    assert same is stated
    assert t11 != t12 and [p.split()[0] for p in t11] == ["t1", "t2", "t3",
                                                          "t4"]
    if stated:
        # the draw the records know as --seed 11
        plain = system.System(cell.config, 11, config_dir=cell.config_dir,
                              family=cell.family)
        try:
            plain.build()
            assert jax.tree_util.tree_all(jax.tree_util.tree_map(
                lambda a, b: np.array_equal(a, np.asarray(b)), p11,
                plain.models[0].params))
        finally:
            plain.close()


def test_the_accepted_text_cell_states_its_draw_and_its_window():
    from perfbench import manifest
    from perfbench.traffic import Traffic

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    seed = cell.config["weights"]["seed"]
    assert isinstance(seed, int) and 0 <= seed < 2**31 + 2**20
    assert Traffic(cell.traffic, 1).min_ticks == 4
    assert any("weights.seed" in a for a in cell.config["assumed"])
    # the image cells draw from --seed and close on their first tick
    for name in ("k2-768-backlog", "mix-768-backlog"):
        other = manifest.Cell(manifest.DEFAULT_MANIFEST, name)
        assert "seed" not in other.config["weights"]
        assert "min_ticks" not in other.traffic
        assert Traffic(other.traffic, 1).min_ticks == 1
    with pytest.raises(ValueError, match="min_ticks"):
        Traffic({**cell.traffic, "min_ticks": 0}, 1)


def test_causal_flash_roofline_reader_on_a_hand_made_trace():
    """The causal kernel's reader at the real cell's shapes: the least
    time is prefill's masked attention by the pairs the masks leave — 4
    window layers and a full one over the 8192 positions of the prompt
    bucket, reckoned here by hand — once a dispatched bucket; the time is
    every event of the causal kernel and no other's; no such event, no
    metric; and the unmasked kernel's reader does not take these events
    for its own."""
    import jax

    from perfbench import flops, harness, manifest, peaks, system

    cell = manifest.Cell(manifest.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    assert "causal_flash_roofline_pct" in {m["name"]
                                           for m in cell.per_layer()}
    for other in ("k2-768-backlog", "mix-768-backlog"):
        assert "causal_flash_roofline_pct" not in {
            m["name"] for m in manifest.Cell(manifest.DEFAULT_MANIFEST,
                                             other).per_layer()}
    with open(os.path.join(ROOT, "tests", "perfbench", "fixtures",
                           "causal_flash_trace.json")) as f:
        fixture = json.load(f)
    model = system.Model(cell.config["models"][0], cell.family)
    batch = cell.config["node"]["canonical_batch"]
    pipe, _ = model.family.build(model.arch, "bf16")
    shapes = jax.eval_shape(
        lambda: pipe.init_params(seed=0, dtype="bfloat16"))
    first = {**cell.traffic["tasks"]["trinity"]["input"],
             "prompt": "x" * 7000}
    run = harness.Run()
    run.cell, run.peaks = cell, peaks.peaks_for("TPU v5 lite")
    run.parts["trinity"] = {batch: flops.count_parts(
        model.family.reference, model.arch, model.hydrated(first), shapes,
        batch=batch)}
    run.system = type("S", (), {"canonical_batch": batch,
                                "models": [model]})
    run.first_task["trinity"] = model.hydrated(first)
    run.spans = fixture["spans"]
    run.trace = {"events": [tuple(e) for e in fixture["events"]]}

    s, w = 8192, 4096
    sliding = w * (w + 1) // 2 + (s - w) * w
    full = s * (s + 1) // 2
    assert (sliding, full) == (25_167_872, 33_558_528)
    calls = model.family.causal_kernel_calls(
        run.parts["trinity"][batch]["forward"]["masked_attn_calls"],
        model.arch, model.hydrated(first))
    assert calls == [(16, 48, s, s, 128, sliding)] * 4 \
        + [(16, 48, s, s, 128, full)]
    bucket_s = 4.0 * 16 * 48 * 128 * (4 * sliding + full) / 197e12
    assert bucket_s == pytest.approx(0.26793, rel=1e-4)
    value = cell.reader("causal_flash_roofline_pct")(run)
    # two buckets over 0.20 + 0.30 + 0.25 + 0.25 s of the kernel's events
    assert value == pytest.approx(100.0 * 2 * bucket_s / 1.0, rel=1e-12)
    assert 53.0 < value < 54.0
    # a prompt bucket under the kernel's first length: the walk serves it
    short = {**model.arch, "prompt_buckets": [1024]}
    assert model.family.causal_kernel_calls(
        [(16, 48, 512, 512, 128, 1)], short,
        model.hydrated({**first, "prompt": "x" * 100})) == []
    run.trace = {"events": [("fusion.12", 0.0, 2.0),
                            ("flash_attention.7", 2.0, 0.4)]}
    assert cell.reader("causal_flash_roofline_pct")(run) is None
    mix = manifest.Cell(manifest.DEFAULT_MANIFEST, "mix-768-backlog")
    run.trace = {"events": [("causal_flash_attention.3", 0.0, 1.0)]}
    assert mix.reader("flash_roofline_pct")(run) is None
