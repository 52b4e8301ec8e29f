"""Family `nemotron_h`: the program's Nemotron 3 Super text pipeline and
runner at the configuration file's `arch` — one chip's share of the
model: every Mamba-2 and attention layer whole, a quarter of each expert
layer's routed experts, the multi-token prediction module — whose
solution is `out-1.txt`, and the plain reference beside it
(`perfbench/reference/nemotron_h.py`).

What is compared is what `families/trinity.py` compares, by its `gaps`
(imported, not copied): over a greedy task's served ids, teacher-forced
against the float32 reference's MAIN-model logits on the same bfloat16
weights, `logit_gap` (the mean of the positions' gaps: what the
precision moves) and `gap_rms` (their root mean square: what a local
fault moves), each the worst task's. The program decodes speculatively
and keeps each row's recurrent state after the positions it takes; the
reference runs the recurrence one position at a time over prompt and
served ids and knows nothing of drafts, so a loop that kept the state
after a rejected draft reads as wrong tokens do. The limits are this
configuration's own, from its own readings (the configuration file,
`limit_readings`).
"""
from __future__ import annotations

import os

import numpy as np

from perfbench import manifest
from perfbench.reference import nemotron_h as reference

_trinity = manifest.load_py(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "trinity.py"))
gaps = _trinity.gaps
decode = _trinity.decode     # out-1.txt -> the served ids, a byte an id

TEMPLATE = "nemotron_h"
OUT_NAME = "out-1.txt"
COMPARED = ("logit_gap", "gap_rms")


def build(arch: dict, precision: str):
    from arbius_tpu.models.nemotron_h import (
        NemotronHConfig,
        NemotronHPipeline,
    )
    from arbius_tpu.node.solver import TextGenRunner

    pipe = NemotronHPipeline(
        NemotronHConfig(**arch["model"]), precision=precision,
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
    """None: the reference makes no unmasked attention call and the
    program never calls the unmasked flash kernel. Its attention layer's
    prefill is Trinity's causal kernel (`causal_flash_attention` on the
    TPU from 2,048 prompt positions), one layer in eleven; its decode
    attention einsums over the K/V rows."""
    return []
