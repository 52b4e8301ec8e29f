"""BENCHMARK.json against the contract's shape rules, the file-only
addition of a cell, a per-layer metric and a family whose solution is no
picture, and the traffic generator's lengths."""
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
            fam = cell.family(m["family"])
            assert fam.TEMPLATE == m["template"]
            # a limit for every number the family compares, and no other
            assert sorted(m["limits"]) == sorted(fam.COMPARED)
            assert all(v > 0 for v in m["limits"].values())
            for name in ("OUT_NAME", "build", "reference", "decode",
                         "compare", "kernel_calls"):
                assert hasattr(fam, name), (m["family"], name)
    # a metric that names its cells is read in those cells alone, and each
    # of them reports the end-to-end metric it moves
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
        assert os.path.exists(os.path.join(ROOT, "perfbench", "metrics",
                                           m["name"] + ".py"))
    causal = next(m for m in bench["per_layer"]
                  if m["name"] == "causal_flash_roofline_pct")
    assert causal["workloads"] == ["trinity-ep8-8k-backlog"]
    assert (causal["layer"], causal["moves"], causal["source"]) \
        == ("kernels", "sol_per_hour", "device_trace")
    text = mf.Cell(mf.DEFAULT_MANIFEST, "trinity-ep8-8k-backlog")
    assert callable(text.family("trinity").causal_kernel_calls)


@pytest.mark.parametrize("name", ["backlog8-768", "backlog16-13to3-768",
                                  "backlog32-8k-256"])
def test_accepted_traffic_files_build_and_state_their_window(name):
    from perfbench.traffic import Traffic

    with open(os.path.join(ROOT, "perfbench", "traffic",
                           name + ".json")) as f:
        spec = json.load(f)
    gen = Traffic(spec, 2**31 + 77)
    assert gen.min_ticks == spec.get("min_ticks", 1) >= 1
    assert set(spec) <= {"loop", "outstanding", "min_ticks", "cycle",
                         "tasks", "check"}
    assert sum(n for _, n in gen.cycle) % gen.outstanding == 0 \
        or gen.outstanding % sum(n for _, n in gen.cycle) == 0


def test_a_cell_and_a_metric_are_added_as_files_only(tmp_path, bench):
    """A later PR adds entries to the manifest and files beside the ones
    that are there; no file of the harness changes."""
    extra = tmp_path / "extra"
    (extra / "traffic").mkdir(parents=True)
    (extra / "metrics").mkdir()
    (extra / "families").mkdir()
    (extra / "configs").mkdir()
    src = os.path.join(ROOT, "perfbench", "traffic", "backlog8-768.json")
    with open(src) as f:
        spec = json.load(f)
    spec["outstanding"] = 1
    (extra / "traffic" / "lone-768.json").write_text(json.dumps(spec))
    (extra / "metrics" / "tasks_seen.py").write_text(
        "def read(run):\n    return float(len(run.tasks)) or None\n")
    # a family whose solution is text: what was served is the string, and
    # the number compared is how many letters differ from the prompt's
    (extra / "families" / "echo.py").write_text(ECHO_FAMILY)
    (extra / "configs" / "echo.json").write_text(json.dumps({
        "name": "echo", "reduced": [], "models": [{
            "template": "echo", "family": "echo", "arch": {},
            "defaults": {}, "limits": {"letters_off": 0}}]}))
    spec["cycle"] = [{"model": "echo", "count": 1}]
    spec["tasks"] = {"echo": {"input": {}, "prompt_bytes": [20, 30]}}
    (extra / "traffic" / "lone-echo.json").write_text(json.dumps(spec))
    added = json.loads(json.dumps(bench))
    added["paths"] = [os.path.join(ROOT, p) for p in bench["paths"]] \
        + [str(extra)]
    for c in added["configs"]:
        c["file"] = os.path.join(ROOT, c["file"])
    added["configs"].append({
        "name": "echo", "source": "none", "reduced": [], "why": "text",
        "file": str(extra / "configs" / "echo.json")})
    added["workloads"].append({
        "name": "echo-lone", "config": "echo", "traffic": "lone-echo",
        "chips": 1, "why": "one text task outstanding"})
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

    # the text cell: its family is found under the extra path, and what
    # decides `correct` goes through it by the harness's own calls
    from perfbench import correct, system
    from perfbench.reference import l0
    from perfbench.traffic import Traffic

    cell = mf.Cell(str(path), "echo-lone")
    model = system.Model(cell.config["models"][0], cell.family)
    assert model.family.OUT_NAME == "out-1.txt"
    assert cell.family("kandinsky2").TEMPLATE == "kandinsky2"
    gen = Traffic(cell.traffic, 3)
    miner = "0x" + "aa" * 20
    inputs = [gen.task()[1] for _ in range(3)]
    answers = [inputs[0]["prompt"].encode(),            # the answer
               b"\xff\xfe",                             # no text at all
               ("X" + inputs[2]["prompt"][1:]).encode()]  # a letter off
    recs = [{"taskid": bytes([i]) * 32, "model": "echo", "input": inp}
            for i, inp in enumerate(inputs)]
    files = {r["taskid"]: {"out-1.txt": a} for r, a in zip(recs, answers)}

    class FakeSystem:
        engine = type("E", (), {
            "solutions": {t: type("S", (), {"cid": l0.solution_cid(f)})
                          for t, f in files.items()},
            "commitments": {l0.commitment(miner, t, l0.solution_cid(f)): 1
                            for t, f in files.items()}})

        def solution_files(self, rec):
            return files[rec["taskid"]]

        def model(self, template):
            return model

    bad, served = correct.chain_checks(FakeSystem(), recs, miner)
    assert bad == 1 and sorted(served) == [recs[0]["taskid"],
                                           recs[2]["taskid"]]
    values = [model.family.compare(model, r, served[r["taskid"]])
              ["letters_off"]["value"] for r in (recs[0], recs[2])]
    assert values == [0, 1]


ECHO_FAMILY = '''
TEMPLATE = "echo"
OUT_NAME = "out-1.txt"
COMPARED = ("letters_off",)
reference = None


def build(arch, precision):
    raise NotImplementedError


def decode(data, hydrated):
    return data.decode("utf-8")      # raises on bytes that are no text


def compare(model, rec, served, control=None):
    want = model.hydrated(rec["input"])["prompt"]
    return {"letters_off": {"value": sum(a != b for a, b in
                                         zip(served, want))}}


def kernel_calls(attn_calls):
    return []
'''


@pytest.mark.parametrize("limits", [{}, {"image_mad": 6.0, "psnr": 30.0},
                                    {"psnr": 30.0}])
def test_limits_that_are_not_what_the_family_compares(limits, bench):
    """A model whose limits lack a name its family compares, or state one
    it does not produce, is refused at set-up: no run passes by comparing
    nothing."""
    from perfbench import system

    cell = mf.Cell(mf.DEFAULT_MANIFEST, bench["workloads"][0]["name"])
    entry = dict(cell.config["models"][0], limits=limits)
    with pytest.raises(mf.ManifestError, match="compares"):
        system.Model(entry, cell.family)
    with pytest.raises(mf.ManifestError, match="compares"):
        system.System(dict(cell.config, models=[entry]), 1,
                      family=cell.family)
    with pytest.raises(mf.ManifestError, match="no families file"):
        cell.family("no-such-family")


# the first three tasks of seed 9, as the parent of PR 27 gave them
PINNED = {
    "backlog8-768": [
        ("kandinsky2", "t1 orchid basalt chip tundra raven xenon beacon "
                       "ember tensor raven fjord raven"),
        ("kandinsky2", "t2 lantern ember beacon kelp prairie yarrow iris "
                       "willow orchid lattice"),
        ("kandinsky2", "t3 delta ember orchid orchid prairie violet raven "
                       "orchid")],
    "backlog16-13to3-768": [
        ("anythingv3", "t1 raven xenon beacon ember tensor raven fjord "
                       "raven quartz lantern ember beacon kelp prairie"),
        ("kandinsky2", "t2 iris willow orchid lattice jade delta ember "
                       "orchid orchid prairie violet raven"),
        ("kandinsky2", "t3 quartz jade prairie orchid cedar nebula chip "
                       "basalt glacier")],
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_accepted_traffic_gives_the_prompts_it_gave(name):
    from perfbench.traffic import Traffic

    with open(os.path.join(ROOT, "perfbench", "traffic",
                           name + ".json")) as f:
        spec = json.load(f)
    t = Traffic(spec, 9)
    got = [t.task() for _ in range(3)]
    assert [(m, i["prompt"]) for m, i in got] == PINNED[name]
    assert all({**i, "prompt": ""} == {**spec["tasks"][m]["input"],
                                       "prompt": ""} for m, i in got)


def test_prompt_bytes_hits_its_range_and_is_seeded():
    from perfbench.traffic import Traffic

    spec = {"loop": "closed", "outstanding": 4,
            "cycle": [{"model": "m", "count": 4}],
            "tasks": {"m": {"input": {"k": 1}, "prompt_bytes": [40, 200]}}}
    gen = Traffic(spec, 2**31 + 9)
    prompts = [gen.task()[1]["prompt"] for _ in range(300)]
    assert prompts[0] == Traffic(spec, 2**31 + 9).task()[1]["prompt"]
    sizes = [len(p.encode()) for p in prompts]
    assert min(sizes) == 40 and max(sizes) == 200
    assert len(set(sizes)) > 100                    # drawn, not fixed
    assert len(set(prompts)) == 300                 # distinct by the index
    assert all(p.startswith(f"t{i + 1} ") for i, p in enumerate(prompts))
    other = Traffic(spec, 5)
    assert [other.task()[1]["prompt"] for _ in range(3)] != prompts[:3]
    # one unit a task kind, and room for the index
    for tasks in ({"input": {}}, {"input": {}, "prompt_bytes": [40, 50],
                                  "prompt_words": [3, 6]},
                  {"input": {}, "prompt_bytes": [4, 50]}):
        with pytest.raises(ValueError, match="prompt_"):
            Traffic({**spec, "tasks": {"m": tasks}}, 1)


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
