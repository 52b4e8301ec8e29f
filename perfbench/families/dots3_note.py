"""Family `dots3_note`: the program's dots3-note-prev text pipeline and
runner at the configuration file's `arch` — one chip's share of the
model — whose solution is `out-1.txt`, and the plain reference beside it
(`perfbench/reference/dots3_note.py`).

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
from perfbench.reference import dots3_note as reference

_trinity = manifest.load_py(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "trinity.py"))
gaps = _trinity.gaps
decode = _trinity.decode     # out-1.txt -> the served ids, a byte an id

TEMPLATE = "dots3_note"
OUT_NAME = "out-1.txt"
COMPARED = ("logit_gap", "gap_rms")


def build(arch: dict, precision: str):
    from arbius_tpu.models.dots3 import Dots3NoteConfig, Dots3NotePipeline
    from arbius_tpu.node.solver import TextGenRunner

    pipe = Dots3NotePipeline(
        Dots3NoteConfig(**arch["model"]), precision=precision,
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
    program never calls the unmasked flash kernel. Its full layers'
    prefill attention is deepseek_v32's selected kernel, its sliding
    layers' the banded one (`window_kernel_floor_s`), its decode
    attention einsums over the latent caches and rings."""
    return []


def window_kernel_floor_s(arch: dict, task: dict, peaks: dict) -> float:
    """The least seconds the chip could take for the banded prefill
    attention the program serves with `window_flash_attention` for ONE
    sequence of `task`'s shape: over its sliding layers, the larger of
    the band's FLOPs at the bf16 peak and its bytes at the HBM bandwidth
    (`reference.window_work`); 0 where the prompt bucket takes the walk."""
    total = 0.0
    for call in reference.window_kernel_calls(arch, task):
        flops, nbytes = reference.window_work(*call)
        total += max(flops / peaks["bf16_flops"],
                     nbytes / peaks["hbm_bytes_per_s"])
    return total
