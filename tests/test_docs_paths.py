"""No document names a file that is not there: every back-ticked path
in the README, the goldens' READMEs and `docs/*.md` that points into
the tree exists. A deleted script or record leaves its mentions red
here until the sentence that leaned on it is gone too."""
from __future__ import annotations

import glob
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
DOCS = ["README.md", "goldens/README.md", "goldens/graph/README.md",
        *sorted(glob.glob("docs/*.md", root_dir=ROOT))]
DIRS = ("arbius_tpu", "tools", "tests", "perfbench", "bench_runs", "goldens",
        "examples")
# paths under those directories, this repo's flat `docs/` (a deeper
# `docs/src/...` is the reference's own tree), root-level scripts, and
# the names the benchmark records of before the chip carried
CHECKED = re.compile(rf"(?:(?:{'|'.join(DIRS)})/\S*|docs/[^/\s]+"
                     r"|[\w.-]+\.py|(?:BENCH|MULTICHIP)[\w.-]*\.\w+)")
# a bare `name.py` that some directory holds is a file name in prose,
# not a root-level script: only what is left is looked for at the root
BARE = {name for d in DIRS for _, _, names in os.walk(os.path.join(ROOT, d))
        for name in names if name.endswith(".py")}
BARE.add("modeling_afmoe.py")       # the published model's file, not ours


def named_paths(text: str) -> set[str]:
    """Back-ticked spans that are wholly a checked path (a command line
    or a path with a placeholder or wildcard in it is not), without a
    trailing `:line`, `:line-line` or `::test_name`."""
    found = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        path = re.sub(r"(?::\d+(?:-\d+)?|::\w+)$", "", span)
        if (CHECKED.fullmatch(path) and path not in BARE
                and not re.search(r"[<>*{}$…]", path)):
            found.add(path.rstrip("/"))
    return found


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as fh:
        missing = sorted(p for p in named_paths(fh.read())
                         if not os.path.exists(os.path.join(ROOT, p)))
    assert not missing, f"{doc} names files that are not there: {missing}"
