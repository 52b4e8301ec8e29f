"""Family `textgen`, for the tests' rehearsal only: the program's
decoder-only text pipeline and runner at the configuration file's `arch`,
whose solution is `out-1.txt` and no picture, and the plain reference
beside it (`../reference/textgen.py`).

  logit_gap   over every served id of a task: how far the reference's
              logit for the served id lies under the reference's largest
              at that position (prompt and the served ids before it
              teacher-forced), in units of that row's spread (its
              standard deviation over the byte ids); the widest of them.
              Greedy tasks only. 0 where the served id is the reference's
              own first choice.
"""
from __future__ import annotations

import os

import numpy as np

from perfbench import manifest

reference = manifest.load_py(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "reference",
    "textgen.py"))

TEMPLATE = "textgen"
OUT_NAME = "out-1.txt"
COMPARED = ("logit_gap",)


def build(arch: dict, precision: str):
    from arbius_tpu.models.textgen import TextGenConfig, TextGenPipeline
    from arbius_tpu.node.solver import TextGenRunner

    pipe = TextGenPipeline(
        TextGenConfig(**arch["model"]), precision=precision,
        prompt_buckets=tuple(arch["prompt_buckets"]),
        decode_buckets=tuple(arch["decode_buckets"]), top_k=arch["top_k"])
    return pipe, TextGenRunner


def decode(data: bytes, hydrated: dict) -> np.ndarray:
    """out-1.txt -> the served ids. A byte is the id of the same value;
    the configuration's weights keep every first choice a byte id, so the
    text holds one byte for each token asked for, or it is no answer."""
    ids = np.frombuffer(data, np.uint8).astype(np.int32)
    if len(ids) != int(hydrated["max_new_tokens"]):
        raise ValueError(f"{len(ids)} bytes for "
                         f"{hydrated['max_new_tokens']} tokens")
    return ids


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
    gap = ref.max(axis=-1) - ref[np.arange(len(ids)), ids]
    spread = ref.std(axis=-1)
    return {"logit_gap": {
        "value": float((gap / spread).max()), "positions": len(ids),
        "not_first": int((gap > 0).sum()),
        "spread": float(spread.mean())}}


def kernel_calls(attn_calls):
    """The program's text model calls no kernel: its attention is einsum."""
    return []
