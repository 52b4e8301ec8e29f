"""Family `joyai_llm_flash`: the program's JoyAI-LLM-Flash text pipeline
and runner at the configuration file's `arch` — every routed expert and
the multi-token prediction module on the chip — whose solution is
`out-1.txt`, and the plain reference beside it
(`perfbench/reference/joyai_llm_flash.py`).

What is compared is what `families/trinity.py` compares, by its `gaps`
(imported, not copied): over a greedy task's served ids, teacher-forced
against the float32 reference's MAIN-model logits on the same bfloat16
weights, `logit_gap` and `gap_rms`, each the worst task's. The program
decodes speculatively and the reference does not know it: a token is
served only where it is the sampler's own choice at its index, so the
served ids are held to the one-token-a-step model, and a loop that takes
a token it should not have (from the second position after a rejected
draft) reads as wrong tokens do. The module's drafts never reach the
bytes, so this comparison cannot see them: `mtp_accept_pct` and the
builder's diagnostic (`tools/joyai_diag.py`, the reference's
`both_logits`) hold the module to its equations.
"""
from __future__ import annotations

import os

import numpy as np

from perfbench import manifest
from perfbench.reference import joyai_llm_flash as reference

_trinity = manifest.load_py(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "trinity.py"))
gaps = _trinity.gaps
decode = _trinity.decode     # out-1.txt -> the served ids, a byte an id

TEMPLATE = "joyai_llm_flash"
OUT_NAME = "out-1.txt"
COMPARED = ("logit_gap", "gap_rms")


def build(arch: dict, precision: str):
    from arbius_tpu.models.joyai_flash import (
        JoyAIFlashConfig,
        JoyAIFlashPipeline,
    )
    from arbius_tpu.node.solver import TextGenRunner

    pipe = JoyAIFlashPipeline(
        JoyAIFlashConfig(**arch["model"]), precision=precision,
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
    program never calls the unmasked flash kernel. Its prefill attention
    is deepseek_v32's masked path (`selected_flash_attention` on the TPU
    from 2,048 prompt positions) under an all-ones selection, a tenth of
    a bucket; its decode attention einsums over the latent caches."""
    return []
