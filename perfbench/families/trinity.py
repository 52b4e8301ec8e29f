"""Family `trinity`: the program's Trinity (`afmoe`) text pipeline and
runner at the configuration file's `arch` — one chip's share of the
model — whose solution is `out-1.txt`, and the plain reference beside it
(`perfbench/reference/trinity.py`).

Over the served ids of a task, teacher-forced (prompt and the served ids
before a position given), the gap of a position is how far the
reference's logit for the served id lies under the reference's largest
there, in units of that row's spread (its standard deviation over the
byte ids): 0 where the served id is the reference's own first choice.
Greedy tasks only. Two numbers are compared, each the worst task's:

  logit_gap   the MEAN of a task's gaps: what the precision moves. An
              expert layer routes by the top-k of scores a rounding
              apart, so in any precision some positions send a token to
              another expert than the reference does and read a wide
              gap; the widest of 256 positions is drawn from that tail
              in bfloat16 and under the fp8 control alike (it is
              printed as `widest`, beside `not_first`), while the mean
              differs five times.
  gap_rms     the ROOT of the MEAN SQUARE of them: what a local fault
              moves. A wrong token reads a gap of about 2.8 (a random id
              against the largest of 256): one in 256 positions adds
              0.011 to the mean and is lost in it, but lifts the root
              mean square from 0.07 to 0.19, and two lift it to 0.26.
"""
from __future__ import annotations

import numpy as np

from perfbench.reference import trinity as reference

TEMPLATE = "trinity"
OUT_NAME = "out-1.txt"
COMPARED = ("logit_gap", "gap_rms")


def build(arch: dict, precision: str):
    from arbius_tpu.models.trinity import TrinityConfig, TrinityPipeline
    from arbius_tpu.node.solver import TextGenRunner

    pipe = TrinityPipeline(
        TrinityConfig(**arch["model"]), precision=precision,
        prompt_buckets=tuple(arch["prompt_buckets"]),
        decode_buckets=tuple(arch["decode_buckets"]), top_k=arch["top_k"])
    return pipe, TextGenRunner


def decode(data: bytes, hydrated: dict) -> np.ndarray:
    """out-1.txt -> the served ids. A byte is the id of the same value;
    the program samples over the byte ids alone, so the text holds one
    byte for each token asked for, or it is no answer."""
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
    return gaps(ref, ids)


def gaps(ref: np.ndarray, ids: np.ndarray) -> dict:
    """What `ids` [T] read against the reference's logits `ref` [T, BYTES]."""
    spread = ref.std(axis=-1)
    gap = (ref.max(axis=-1) - ref[np.arange(len(ids)), ids]) / spread
    return {"logit_gap": {
        "value": float(gap.mean()), "widest": float(gap.max()),
        "positions": len(ids), "not_first": int((gap > 0).sum()),
        "spread": float(spread.mean())},
        "gap_rms": {"value": float(np.sqrt((gap * gap).mean())),
                    "far": int((gap > 1.5).sum())}}


def kernel_calls(attn_calls):
    """The reference makes no unmasked attention call, and the program
    never calls the unmasked flash kernel (`ops/flash.py`): its prefill
    attention is the causal kernel below, its decode attention an einsum
    over the caches."""
    return []


# query positions from which the program's prefill takes its causal /
# sliding-window kernel on the TPU (`ops/causal_flash.py`, PR 31)
CAUSAL_KERNEL_MIN_ROWS = 2048


def causal_kernel_calls(masked_attn_calls, arch: dict, task: dict):
    """The reference's masked attention that the program serves with its
    causal kernel (`causal_flash_attention`): prefill's — the query rows
    of the prompt bucket, where that has CAUSAL_KERNEL_MIN_ROWS positions
    or more — as one call a layer, (b, h, s, s, d, pairs), `pairs` being
    the (query, key) pairs the layer's mask leaves there. The reference
    walks a layer's positions, prompt bucket and decode bucket in one
    pass, in blocks of query rows from the first; a block that ends
    inside the prompt bucket is prefill's, the rows after it are decode's
    (an einsum over the caches in the program, no kernel). The work is
    the algorithm's: neither the blocks the kernel visits, which would
    read a tile choice as work, nor s x s, which counts what the mask
    takes away."""
    edge = reference.prompt_bucket(arch, task.get("prompt", ""))
    if edge < CAUSAL_KERNEL_MIN_ROWS:
        return []
    total = edge + reference.decode_bucket(
        arch, int(task["max_new_tokens"])) - 1
    out, row, pairs = [], 0, 0
    for b, h, sq, _sk, d, n in masked_attn_calls:
        row += sq
        if row <= edge:
            pairs += n
        if row == total:        # the layer's last block
            out.append((b, h, edge, edge, d, pairs))
            row = pairs = 0
    if row:
        raise ValueError("the masked calls do not add up to whole layers "
                         f"of {total} positions")
    return out
