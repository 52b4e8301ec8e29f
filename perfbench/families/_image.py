"""What the families whose solution is one PNG share (no family itself:
no configuration names it): how the pinned bytes become what was served,
and the number compared with the plain reference.

  image_mad   the mean absolute difference, in 8-bit levels, between the
              served image and the image the float32 reference computes
              for the same (input, task seed) from the same weights.
"""
from __future__ import annotations

import io

import numpy as np

from perfbench.reference import l0

COMPARED = ("image_mad",)


def decode(data: bytes, hydrated: dict) -> np.ndarray:
    """PNG bytes -> uint8 RGB [H,W,3] of the task's size, or a raise."""
    from PIL import Image

    with Image.open(io.BytesIO(data)) as im:
        img = np.asarray(im.convert("RGB"))
    want = (hydrated["height"], hydrated["width"], 3)
    if img.shape != want:
        raise ValueError(f"an image of {img.shape}, the task's is {want}")
    return img


def compare(reference, model, rec: dict, served: np.ndarray,
            control: str | None = None) -> dict:
    """`value` is the number compared; the others are printed beside it so
    that a reading far off can be looked into. With `control` ("fp8"), the
    reference computed in that precision is put in the served image's
    place."""
    args = (model.params, model.arch, model.hydrated(rec["input"]),
            l0.task_seed(rec["taskid"]))
    ref = reference.image(*args)
    if control:
        served = reference.image(*args, weights=control)
    diff = np.abs(served.astype(np.int32) - ref.astype(np.int32))
    return {"image_mad": {
        "value": float(diff.mean()), "median": float(np.median(diff)),
        "p90": float(np.percentile(diff, 90)),
        "over16": float((diff > 16).mean()),
        "ref_std": float(ref.std()),
        "saturated": float(((ref == 0) | (ref == 255)).mean())}}
