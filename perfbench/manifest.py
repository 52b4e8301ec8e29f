"""Finding a cell's files by the names in the manifest (`BENCHMARK.json`).

Nothing here knows a cell, a configuration, a metric or a family by name:
a later PR adds entries to the manifest and files beside the ones that
are there (`configs/<config>.json`, `traffic/<traffic>.json`,
`metrics/<metric>.py`, `families/<family>.py`), each under any of the
manifest's `paths`, and edits no file.

What a family file defines (a configuration's model names its family):

    TEMPLATE      the program's template the family serves
    OUT_NAME      the solution's file name, as the template states it
    COMPARED      the names of the numbers `compare` gives, each the
                  worse the larger; a model's `limits` in the
                  configuration file holds exactly these
    build(arch, precision) -> (the program's pipeline, its runner class)
    reference     the plain reference: `parts(arch)` and
                  `forward_shapes(arch, task, batch)` for the FLOP count
                  (perfbench/flops.py), and whatever `compare` calls
    decode(data, hydrated) -> what was served, from the pinned bytes of a
                  task with these hydrated fields; raises where the bytes
                  are no such answer (perfbench/correct.py)
    compare(model, rec, served, control=None) ->
                  {name: {"value": x, ...}}: what was served for the
                  task `rec` against the reference on `model.params`;
                  with `control`, the reference in that lower precision
                  stands in the served answer's place
    kernel_calls(attn_calls) -> those of the reference's unmasked
                  attention calls that the program serves with its flash
                  kernel (`flash_roofline_pct`)
    causal_kernel_calls(masked_attn_calls, arch, task)   (optional) ->
                  the reference's masked attention that the program
                  serves with its causal kernel, a call a layer with the
                  pairs its mask leaves (`causal_flash_roofline_pct`)
"""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEFAULT_MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


class ManifestError(ValueError):
    pass


class Cell:
    """One entry of `workloads` with everything its run needs."""

    def __init__(self, manifest_path: str, name: str):
        self.manifest_path = os.path.abspath(manifest_path)
        self.base = os.path.dirname(self.manifest_path)
        with open(self.manifest_path) as f:
            self.manifest = json.load(f)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise ManifestError(
                f"no workload {name!r} in {manifest_path}; it has "
                f"{sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.entry["config"]]
        config_path = os.path.join(self.base, self.config_entry["file"])
        self.config_dir = os.path.dirname(config_path)
        with open(config_path) as f:
            self.config = json.load(f)
        # the traffic mix is a data file beside the manifest's first path
        self.traffic_path = self._find("traffic", self.entry["traffic"],
                                       ".json")
        with open(self.traffic_path) as f:
            self.traffic = json.load(f)

    def _dirs(self, kind: str) -> list[str]:
        return [os.path.join(self.base, p, kind)
                for p in self.manifest["paths"]]

    def _find(self, kind: str, name: str, ext: str) -> str:
        for d in self._dirs(kind):
            path = os.path.join(d, name + ext)
            if os.path.exists(path):
                return path
        raise ManifestError(
            f"no {kind} file {name + ext} under any of {self._dirs(kind)}")

    def _applies(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self) -> list[dict]:
        return [m for m in self.manifest["end_to_end"] if self._applies(m)]

    def per_layer(self) -> list[dict]:
        return [m for m in self.manifest["per_layer"] if self._applies(m)]

    def reader(self, metric_name: str):
        """The metric's reader: `metrics/<name>.py` with `read(run)`."""
        return load_py(self._find("metrics", metric_name, ".py")).read

    def family(self, name: str):
        """The model family, `families/<name>.py`, found as a metric is."""
        return load_py(self._find("families", name, ".py"))


@functools.lru_cache(maxsize=None)
def _load_real(path: str):
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        "perfbench_file_" + re.sub(r"\W", "_", stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_py(path: str):
    """The module in the file at `path`, once a process however the path
    is spelt (a family keeps its jitted reference)."""
    return _load_real(os.path.realpath(path))


def family(name: str):
    """A family of the benchmark's own directory, for callers with no
    cell at hand; a run goes through `Cell.family`."""
    return load_py(os.path.join(HERE, "families", name + ".py"))
