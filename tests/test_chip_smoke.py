"""chip_smoke.py on CPU: the body at the tiny preset through the explicit
argument, and the failure contract — the smoke must exit non-zero, with
no result on stdout, whenever the node did not mine (the tool it
replaces returned 0 with 0 tasks solved)."""
from __future__ import annotations

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke
from test_node import USER, build_world, fake_runner


def test_burst_measures_every_task_and_claims():
    eng, tok, chain, node, mid = build_world()
    notes = []
    burst = chip_smoke.run_burst(node, eng, USER, bytes.fromhex(mid[2:]), 5,
                                 {}, note=notes.append)
    assert burst["n_tasks"] == 5
    assert burst["solved"] == 5, (burst, notes)
    assert burst["claimed"] == 5
    assert burst["submit_to_last_solution_s"] > 0
    assert len(set(burst["cids"].values())) == 5   # distinct prompts
    # a second burst on the same node counts only its own claims
    again = chip_smoke.run_burst(node, eng, USER, bytes.fromhex(mid[2:]), 2,
                                 {}, note=notes.append)
    assert (again["solved"], again["claimed"]) == (2, 2)


def test_tiny_preset_drives_the_whole_body_on_cpu(capfd):
    assert chip_smoke.main(["--preset", "tiny"]) == 0
    out = capfd.readouterr().out.strip().splitlines()
    summary, last = json.loads(out[-2]), json.loads(out[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 8}}
    assert summary["device"] == last["device"]
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    assert summary["failures"] == []
    assert summary["burst"]["solved"] == summary["burst"]["claimed"] == 8
    assert summary["solutions_submitted_total"] == 8
    assert summary["failed_jobs"] == []
    assert summary["golden"]["after_burst"] == summary["golden"]["cid"]
    assert summary["mosaic_kernel_in_bucket"] is False   # einsum off-TPU
    assert summary["deflate_impl"] in ("native", "python")
    assert summary["versions"]["jax"] and "JAX_PLATFORMS" in summary
    assert summary["setup_s"] > 0


def test_no_argument_off_the_chip_exits_before_any_model_is_built(
        monkeypatch, capfd):
    def no_models(*a, **kw):
        raise AssertionError("a model was built off the chip")

    monkeypatch.setattr("arbius_tpu.node.factory.build_registry", no_models)
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code == chip_smoke.EXIT_NOT_TPU != 0
    assert capfd.readouterr().out == ""


def _registry_with(runner):
    """A build_registry stand-in serving `runner` under the config's id."""
    def build(cfg, **_kw):
        from arbius_tpu.node import ModelRegistry, RegisteredModel
        from arbius_tpu.templates.engine import load_template

        reg = ModelRegistry()
        reg.register(RegisteredModel(
            id=cfg.models[0].id, template=load_template("anythingv3"),
            runner=runner))
        return reg

    return build


def test_a_runner_that_raises_fails_the_smoke(monkeypatch, capfd):
    """The node quarantines a failed bucket solve and keeps ticking; the
    smoke must not read that as a pass."""
    def runner(hydrated, seed):
        if "smoke test" in hydrated["prompt"]:
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        return fake_runner(hydrated, seed)   # the golden input still solves

    monkeypatch.setattr("arbius_tpu.node.factory.build_registry",
                        _registry_with(runner))
    assert chip_smoke.main(["--preset", "tiny"]) == chip_smoke.EXIT_FAILED
    cap = capfd.readouterr()
    assert cap.out == ""                      # no result line
    assert "solved 0/8, claimed 0/8" in cap.err
    assert "quarantined jobs" in cap.err


def test_a_golden_that_moves_after_the_burst_fails_the_smoke(
        monkeypatch, capfd):
    golden_solves = []

    def runner(hydrated, seed):
        if hydrated["prompt"] == "arbius test cat":
            golden_solves.append(seed)
            # in-run record and boot agree; the post-burst solve drifts
            seed += len(golden_solves) > 2
        return fake_runner(hydrated, seed)

    monkeypatch.setattr("arbius_tpu.node.factory.build_registry",
                        _registry_with(runner))
    assert chip_smoke.main(["--preset", "tiny"]) == chip_smoke.EXIT_FAILED
    cap = capfd.readouterr()
    assert cap.out == ""
    assert "not deterministic in-run" in cap.err
    assert len(golden_solves) == 3
