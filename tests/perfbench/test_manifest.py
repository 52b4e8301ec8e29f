"""BENCHMARK.json against the contract's shape rules, and the file-only
addition of a cell, a configuration's stand-in and a per-layer metric."""
from __future__ import annotations

import json
import os
import re
import shutil

import pytest

from pb_paths import ROOT

from perfbench import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in bench["paths"])


def test_names_units_and_entry_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])


def test_every_cell_finds_its_files_by_name(bench):
    for w in bench["workloads"]:
        cell = mf.Cell(mf.DEFAULT_MANIFEST, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["loop"] == "closed"
        assert cell.config["reduced"] == cell.config_entry["reduced"]
        assert set(cell.config["reduced"]) <= set(cell.config)
        for m in cell.per_layer():
            assert callable(cell.reader(m["name"]))
        for m in cell.config["models"]:
            fam = mf.family(m["family"])
            assert fam.TEMPLATE == m["template"]
            assert m["limits"]["image_mad"] > 0


def test_a_cell_and_a_metric_are_added_as_files_only(tmp_path, bench):
    """A later PR adds entries to the manifest and files beside the ones
    that are there; no file of the harness changes."""
    extra = tmp_path / "extra"
    (extra / "traffic").mkdir(parents=True)
    (extra / "metrics").mkdir()
    src = os.path.join(ROOT, "perfbench", "traffic", "backlog8-768.json")
    with open(src) as f:
        spec = json.load(f)
    spec["outstanding"] = 1
    (extra / "traffic" / "lone-768.json").write_text(json.dumps(spec))
    (extra / "metrics" / "tasks_seen.py").write_text(
        "def read(run):\n    return float(len(run.tasks)) or None\n")
    added = json.loads(json.dumps(bench))
    added["paths"] = [os.path.join(ROOT, p) for p in bench["paths"]] \
        + [str(extra)]
    for c in added["configs"]:
        c["file"] = os.path.join(ROOT, c["file"])
    added["workloads"].append({
        "name": "k2-768-lone", "config": "kandinsky2", "traffic": "lone-768",
        "chips": 1, "why": "one task outstanding"})
    added["per_layer"].append({
        "name": "tasks_seen", "unit": "tasks", "better": "higher",
        "source": "program_counter", "layer": "node loop",
        "moves": "sol_per_hour", "workloads": ["k2-768-lone"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(added))
    cell = mf.Cell(str(path), "k2-768-lone")
    assert cell.traffic["outstanding"] == 1
    assert "tasks_seen" in {m["name"] for m in cell.per_layer()}

    class Run:
        tasks = [1, 2, 3]

    assert cell.reader("tasks_seen")(Run()) == 3.0
    # and the old cells do not see the new metric
    old = mf.Cell(str(path), "k2-768-backlog")
    assert "tasks_seen" not in {m["name"] for m in old.per_layer()}
    with pytest.raises(mf.ManifestError):
        mf.Cell(str(path), "no-such-cell")


def test_traffic_is_the_same_work_in_another_order():
    from perfbench.traffic import Traffic

    with open(os.path.join(ROOT, "perfbench", "traffic",
                           "backlog16-13to3-768.json")) as f:
        spec = json.load(f)
    blocks = []
    for seed in (1, 2**31 + 5):
        t = Traffic(spec, seed)
        blocks.append([t.task()[0] for _ in range(32)])
    for b in blocks:
        for i in (0, 16):
            assert sorted(b[i:i + 16]) == ["anythingv3"] * 13 \
                + ["kandinsky2"] * 3
    assert blocks[0] != blocks[1]
    a, b = Traffic(spec, 9), Traffic(spec, 9)
    assert [a.task() for _ in range(20)] == [b.task() for _ in range(20)]
    prompts = [Traffic(spec, 9).task()[1]["prompt"] for _ in range(3)]
    assert len(set(prompts)) == 1   # same seed, same first task
