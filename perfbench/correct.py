"""What decides `correct`: the timed path's own products against the plain
reference, once the window has closed.

Two kinds of number, each printed beside its limit:

  chain_mismatch   exact, limit 0, over EVERY task the window finished,
                   and the protocol's: the bytes the node pinned -> the
                   benchmark's own CID (perfbench/reference/l0.py) must be
                   the CID the engine holds as revealed; the commitment
                   keccak(abi(miner, taskid, cid)) must be on the engine;
                   the bytes must be an answer to the task, which the
                   model's family says: `family.decode(data, hydrated)`
                   returns what was served or raises. Tasks submitted into
                   the window and dispatched but never solved, and
                   quarantined jobs, count here too.
  <name>.<model>   the family's: for whole buckets, drawn from the seed, of
                   the tasks the window finished (of every model the
                   traffic holds, every slot of a bucket),
                   `family.compare(model, rec, served, control)` holds what
                   was served against what the float32 reference computes
                   for the same (input, task seed) from the same weights,
                   and gives {name: {"value": x, ...}} for every name in
                   the family's `COMPARED`; the worst of the sample stands
                   beside the model's limit of that name, from the
                   configuration file (`limits`).

A family file (`families/<name>.py` under any of the manifest's `paths`)
defines `TEMPLATE`, `OUT_NAME`, `COMPARED`, `build`, `reference`, `decode`,
`compare` and `kernel_calls`; perfbench/manifest.py says what each is. This
module and the harness know no family, no output type and no compared
number by name.
"""
from __future__ import annotations

import random

from perfbench.reference import l0


def chain_checks(system, tasks: list[dict], miner: str) -> tuple[int, dict]:
    """(mismatches, {taskid: what was served}) over the window's tasks."""
    bad = 0
    served = {}
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
        m = system.model(rec["model"])
        try:
            served[rec["taskid"]] = m.family.decode(
                next(iter(files.values())), m.hydrated(rec["input"]))
        except Exception:  # noqa: BLE001 — undecodable bytes are a mismatch
            ok = False
        bad += 0 if ok else 1
    return bad, served


def sample(tasks: list[dict], per_model: dict, canonical_batch: int,
           seed: int) -> list[dict]:
    """Whole buckets of the window's tasks, drawn from the seed: of every
    model, `per_model[model]` of them, every slot of each. The node fills
    a bucket with `canonical_batch` consecutive tasks of one model and
    shape, in the order a tick took them in (`solver.chunk_items`), so a
    fault in one slot of the batched program cannot hide behind another
    slot's answer. Full buckets first; a padded one only where a model
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
