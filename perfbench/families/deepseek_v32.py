"""Family `deepseek_v32`: the program's DeepSeek-V3.2-Exp text pipeline
and runner at the configuration file's `arch` — one chip's share of the
model — whose solution is `out-1.txt`, and the plain reference beside it
(`perfbench/reference/deepseek_v32.py`).

What is compared is what `families/trinity.py` compares, by its `gaps`
(imported, not copied): over a greedy task's served ids, teacher-forced
against the float32 reference's logits on the same bfloat16 weights,
`logit_gap` (the mean of the positions' gaps: what the precision moves)
and `gap_rms` (their root mean square: what a local fault moves), each
the worst task's. The limits are this configuration's own, from its own
readings (the configuration file, `limit_readings`).
"""
from __future__ import annotations

import os

import numpy as np

from perfbench import manifest
from perfbench.reference import deepseek_v32 as reference

_trinity = manifest.load_py(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "trinity.py"))
gaps = _trinity.gaps
decode = _trinity.decode     # out-1.txt -> the served ids, a byte an id

TEMPLATE = "deepseek_v32"
OUT_NAME = "out-1.txt"
COMPARED = ("logit_gap", "gap_rms")


def build(arch: dict, precision: str):
    from arbius_tpu.models.deepseek_v32 import (
        DeepSeekV32Config,
        DeepSeekV32Pipeline,
    )
    from arbius_tpu.node.solver import TextGenRunner

    pipe = DeepSeekV32Pipeline(
        DeepSeekV32Config(**arch["model"]), precision=precision,
        prompt_buckets=tuple(arch["prompt_buckets"]),
        decode_buckets=tuple(arch["decode_buckets"]), top_k=arch["top_k"])
    return pipe, TextGenRunner


def compare(model, rec: dict, served: np.ndarray,
            control: str | None = None) -> dict:
    """With `control` ("fp8") the ids that the reference in that precision
    puts first, at each position of the same prompt and served ids, stand
    in the served ids' place."""
    task = model.hydrated(rec["input"])
    ref = reference.logits(model.params, model.arch, task, served)
    ids = served
    if control:
        ids = reference.logits(model.params, model.arch, task, served,
                               weights=control).argmax(axis=-1)
    return gaps(ref, ids)


def kernel_calls(attn_calls):
    """None: the program calls neither flash kernel. Its prefill
    attention is a walk in XLA over blocks of query and key rows under
    the selection's mask, its decode attention einsums over the latent
    cache; the reference counts the selected pairs and the index scores
    by name (`other`: "attention", "indexer") for the kernel that a
    later PR brings."""
    return []
