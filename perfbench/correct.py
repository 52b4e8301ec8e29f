"""What decides `correct`: the timed path's own products against the plain
reference, once the window has closed.

Two kinds of number, each printed beside its limit:

  chain_mismatch   exact, limit 0, over EVERY task the window finished:
                   the PNG bytes the node pinned -> the benchmark's own
                   CID (perfbench/reference/l0.py) must be the CID the
                   engine holds as revealed; the commitment
                   keccak(abi(miner, taskid, cid)) must be on the engine;
                   the bytes must decode (PIL) to an image of the task's
                   size. Tasks submitted into the window and dispatched but
                   never solved, and quarantined jobs, count here too.
  image_mad.<model> for whole buckets, drawn from the seed, of the tasks
                   the window finished (of every model the traffic holds,
                   every slot of a bucket): the mean absolute difference,
                   in 8-bit levels, between the served image and the image
                   the float32 reference computes for the same (input,
                   task seed) from the same weights; the worst of the
                   sample. The limit is the model's, from the
                   configuration file.
"""
from __future__ import annotations

import io
import random

import numpy as np

from perfbench.reference import l0


def decode_png(data: bytes) -> np.ndarray:
    from PIL import Image

    with Image.open(io.BytesIO(data)) as im:
        return np.asarray(im.convert("RGB"))


def chain_checks(system, tasks: list[dict], miner: str) -> tuple[int, dict]:
    """(mismatches, {taskid: decoded image}) over the window's tasks."""
    bad = 0
    images = {}
    for rec in tasks:
        sol = system.engine.solutions.get(rec["taskid"])
        files = system.solution_files(rec)
        if sol is None or files is None:
            bad += 1
            continue
        cid = l0.solution_cid(files)
        ok = cid == bytes(sol.cid)
        ok &= system.engine.commitments.get(
            l0.commitment(miner, rec["taskid"], cid), 0) != 0
        try:
            img = decode_png(next(iter(files.values())))
            h = system.model(rec["model"]).hydrated(rec["input"])
            ok &= img.shape == (h["height"], h["width"], 3)
            images[rec["taskid"]] = img
        except Exception:  # noqa: BLE001 — undecodable bytes are a mismatch
            ok = False
        bad += 0 if ok else 1
    return bad, images


def sample(tasks: list[dict], per_model: dict, canonical_batch: int,
           seed: int) -> list[dict]:
    """Whole buckets of the window's tasks, drawn from the seed: of every
    model, `per_model[model]` of them, every slot of each. The node fills
    a bucket with `canonical_batch` consecutive tasks of one model and
    shape, in the order a tick took them in (`solver.chunk_items`), so a
    fault in one slot of the batched program cannot hide behind another
    slot's picture. Full buckets first; a padded one only where a model
    has no other."""
    rng = random.Random(f"perfbench-sample-{seed}")
    out = []
    for model, n in sorted(per_model.items()):
        by_tick: dict = {}
        for t in tasks:
            if t["model"] == model:
                by_tick.setdefault(t["tick"], []).append(t)
        buckets = [ts[i:i + canonical_batch] for ts in by_tick.values()
                   for i in range(0, len(ts), canonical_batch)]
        full = [b for b in buckets if len(b) == canonical_batch] or buckets
        for b in rng.sample(full, min(n, len(full))):
            out.extend(t for t in b if t["solved"] is not None)
    return out


def image_stats(system, rec: dict, served: np.ndarray,
                control: str | None = None) -> dict:
    """The served image against the reference's for the same (input, task
    seed): `mean` is the number compared (`image_mad`); the others are
    printed beside it so that a reading far off can be looked into. With
    `control` ("fp8"), the reference computed in that precision is put in
    the served image's place."""
    m = system.model(rec["model"])
    args = (m.params, m.arch, m.hydrated(rec["input"]),
            l0.task_seed(rec["taskid"]))
    ref = m.family.reference.image(*args)
    if control:
        served = m.family.reference.image(*args, weights=control)
    diff = np.abs(served.astype(np.int32) - ref.astype(np.int32))
    return {"mean": float(diff.mean()), "median": float(np.median(diff)),
            "p90": float(np.percentile(diff, 90)),
            "over16": float((diff > 16).mean()),
            "ref_std": float(ref.std()),
            "saturated": float(((ref == 0) | (ref == 255)).mean())}
