"""solvepipe — the staged solve executor (docs/pipeline.md).

The synchronous solve path runs the whole post-infer tail — encode,
CID, the pin round-trip, commit and reveal — on the tick thread while
the chip idles. This module decouples the three cost domains of that
hot loop into stages with bounded hand-off buffers:

  device   canonical_batch chunks dispatched up to `depth` ahead (XLA
           async dispatch: the call queues the program on the chip and
           returns immediately; generalizes solver.py's old one-deep
           overlap to a configurable prefetch window)
  encode   wait for the chunk's device result, then transfer + codec
           + CID per chunk, on a pool of `encode_workers` threads (0 =
           inline on the tick thread); per-chunk work is a pure
           function of the device result, so worker count and
           completion order can never change bytes
  network  pin → commit → reveal per task, on the tick thread, drained
           while later chunks are already on the chip; the backlog is
           bounded by `max_inflight_pins`

Determinism: chunking is `solver.chunk_items` (shared with the serial
path), encode is per-chunk pure, and the network stage consumes results
strictly in task order — the chain-write sequence is identical to the
synchronous path; only the schedule changes. Every stage completion is
journaled (`pipeline_stage` events; simnet SIM109 audits per-task
monotonicity) and persisted to the sqlite checkpoint (`pipeline_state`
rows, written only AFTER the stage's side effect landed), so a
crash-restart resumes mid-pipeline: a re-solved task whose recorded CID
matches skips the pin/commit work that already happened.

The path times itself (docs/observability.md): each chunk journals
`solve.dispatch` on the tick thread and, as its children on whichever
thread finalizes, `solve.device_wait` → `solve.encode` → `solve.cid`
(the workers run under the node's obs). The moment a chunk's result is
ready rides back with its encode result; from the chunks' [dispatch
start, ready] intervals the tick thread reckons when nothing was on the
chip, journals those stretches as `solve.idle` and adds them to
`arbius_chip_idle_seconds_total`. A ready stamp is taken when a thread
gets to wait on the result, so with fewer free workers than chunks in
flight it can be late: idle is then under-counted, never over-counted.

Every stage buffer is bounded — CONC302 is enforced for this file: an
unbounded queue would hide a slow consumer instead of exerting
backpressure on the dispatcher.

Mesh transparency (docs/multichip.md): the executor never looks inside
a device payload, so sharded solves ride the same stages unchanged —
`runner.dispatch` places the batch with its NamedShardings and queues
the GSPMD program (still async, so depth-k prefetch overlaps exactly as
on one chip), and `runner.finalize` performs the fully-replicated
gather in canonical order before encoding. mesh=None and any mesh
layout therefore share this schedule byte-for-byte.
"""
# detlint: enforce[CONC302]
from __future__ import annotations

import logging
import queue
import threading
import time
from dataclasses import dataclass

from arbius_tpu.node.solver import (
    chunk_items,
    device_wait,
    encode_chunk,
    program_attrs,
)
from arbius_tpu.obs import span, use_obs

log = logging.getLogger("arbius.pipeline")

# per-task lifecycle order; SIM109 audits that a task's journaled ranks
# never regress inside one node life
STAGE_RANK = {"solve": 0, "encode": 1, "pin": 2, "commit": 3, "reveal": 4}


@dataclass
class _Chunk:
    idx: int
    bucket: int             # index of the bucket this chunk came from
    model: object
    entries: list           # [(Job, hydrated)] — real tasks only
    items: list             # [(hydrated, seed)] padded to canonical_batch
    real: int
    t_start: int = 0        # chain time at dispatch (latency metric)
    t_dispatch: float = 0.0        # perf_counter at dispatch: busy from
    t_ready: float | None = None   # ... until the result was ready
    payload: tuple | None = None   # inline mode: _finish_chunk's arguments


def _finish_chunk(model, payload, real: int, chunk: list,
                  parent: int | None, t_ready: float | None) -> tuple:
    """Encode-stage body, on a worker or inline: wait for the device
    result, then `solver.encode_chunk`. Returns (encode seconds from
    the ready stamp on, ready stamp, [(cid_hex, files)] or the
    Exception the stage raised). `t_ready` comes in set for a payload
    that needs no wait (the device stage computed it)."""
    try:
        if payload[0] == "dev":
            t_ready = device_wait(payload[1], chunk=chunk, parent=parent)
        out = encode_chunk(model, payload, real, chunk=chunk, parent=parent)
    except Exception as e:  # noqa: BLE001 — reported per chunk
        out = e
    # detlint: allow[DET101] obs stage timing; never reaches solve bytes
    t_end = time.perf_counter()
    if t_ready is None:
        # the wait itself failed: the chip counts as busy until it did
        t_ready = t_end
    return t_end - t_ready, t_ready, out


class SolvePipeline:
    """One node's staged executor. Driven from the tick thread
    (`run()`); only the encode pool runs on worker threads, and those
    touch nothing but their bounded input queue and the condition-
    guarded results map — chain, db, and journal writes all stay on the
    tick thread, in task order."""

    def __init__(self, node, cfg):
        self.node = node
        self.cfg = cfg
        reg = node.obs.registry
        self._c_stalls = reg.counter(
            "arbius_pipeline_stalls_total",
            "Times a pipeline stage blocked its producer, by stage",
            labelnames=("stage",))
        self._h_stage = reg.histogram(
            "arbius_pipeline_stage_seconds",
            "Wall seconds per pipeline stage unit (device=dispatch call "
            "per chunk, encode=transfer+codec+CID per chunk from the "
            "moment its device result is ready, network="
            "pin+commit+reveal per task)", labelnames=("stage",))
        self._g_depth = reg.gauge(
            "arbius_pipeline_queue_depth",
            "Items currently inside each pipeline stage buffer",
            labelnames=("stage",))
        self._infer_left: dict = {}
        self._infer_start: dict = {}
        self._infer_ok: set = set()
        self._commit_left: dict = {}
        self._commit_acc: dict = {}
        self._bucket_keys: dict = {}
        self._bucket_h0: dict = {}
        self._bucket_n: dict = {}
        self._cv = threading.Condition()
        # (generation, chunk idx) -> (encode seconds, ready stamp,
        # result); guarded by self._cv. The generation token fences off
        # results a worker finishes AFTER a crash aborted its run —
        # without it, the next run's chunk 0 could consume the dead
        # run's bytes.
        self._results: dict[tuple, object] = {}
        self._gen = 0
        # index of the chunk an operator's device profile is open over
        # (node._profile_due): started at its dispatch, stopped when its
        # result is consumed, so the trace holds the bucket program
        self._profiled: int | None = None
        # device→encode hand-off, bounded at depth: a stalled encode
        # pool must block the dispatcher, not buffer device results
        self._encode_q: queue.Queue = queue.Queue(maxsize=max(1, cfg.depth))
        self._workers = [
            threading.Thread(target=self._encode_worker, daemon=True,
                             name=f"solvepipe-encode-{i}")
            for i in range(cfg.encode_workers)]
        for t in self._workers:
            t.start()

    def shutdown(self) -> None:
        """Stop the encode pool (sentinel per worker). Idempotent; the
        node's close() calls this."""
        for _ in self._workers:
            self._encode_q.put(None)
        for t in self._workers:
            t.join(timeout=5.0)
        self._workers = []

    # -- encode pool (worker threads) -------------------------------------
    def _encode_worker(self) -> None:
        with use_obs(self.node.obs):
            while True:
                item = self._encode_q.get()
                if item is None:
                    return
                key, args = item
                try:
                    res = _finish_chunk(*args)
                except BaseException as e:  # noqa: BLE001 — a worker
                    # that dies WITHOUT posting a result would wedge the
                    # tick thread in _consume's cv.wait forever; every
                    # death, kill-class included, must surface as a
                    # chunk failure
                    # detlint: allow[DET101] obs stage timing; never reaches solve bytes
                    res = (0.0, time.perf_counter(), RuntimeError(
                        f"encode worker died: {type(e).__name__}: {e}"))
                with self._cv:
                    self._results[key] = res
                    self._cv.notify_all()

    # -- the driver (tick thread) -----------------------------------------
    def run(self, buckets: list) -> int:
        """Drive one tick's solve buckets through the staged schedule.
        `buckets` is [(model, [(Job, hydrated), ...], bucket_key)] in
        PACK order — the scheduler's output (node/sched.py) feeds the
        device stage in the order it chose; returns the number of jobs
        completed."""
        chunks = self._plan(buckets)
        self._gen += 1
        # detlint: allow[DET101] obs idle accounting only
        t_open = time.perf_counter()
        with self._cv:
            # purge anything a dead run's workers finished late
            self._results.clear()
        # arbius_stage_seconds{infer} is observed once per BUCKET as a
        # WALL window from the bucket's first dispatch to its last
        # chunk leaving encode — the serial path's granularity and
        # meaning (_solve_bucket times one bucket dispatch as one
        # sample), so the profitability gate's p50 cost estimate reads
        # the same signal whichever schedule runs. (Summing per-chunk
        # spans instead would double-count device wait that concurrent
        # encode workers block on together.)
        self._infer_left = {}      # bucket -> chunks not yet consumed
        self._infer_start = {}     # bucket -> wall stamp of 1st dispatch
        self._infer_ok = set()     # buckets with >= 1 successful chunk
        # stage=commit mirrors the serial path too: one sample per
        # bucket (the summed network tail of its tasks), not per task —
        # NodeMetrics' p50/p95 must not shift with the schedule
        self._commit_left = {}     # bucket -> tasks not yet drained
        self._commit_acc = {}      # bucket -> summed network seconds
        for ch in chunks:
            self._infer_left[ch.bucket] = \
                self._infer_left.get(ch.bucket, 0) + 1
            self._commit_left[ch.bucket] = \
                self._commit_left.get(ch.bucket, 0) + ch.real
        # bucket -> real task count, frozen before the drains decrement
        # (the cost tag needs it when the last chunk leaves encode)
        self._bucket_n = dict(self._commit_left)
        done = 0
        backlog: list = []    # network-stage items, strict task order
        inflight: list = []   # dispatched chunks not yet consumed
        i = 0
        try:
            while i < len(chunks) or inflight or backlog:
                # 1. fill the device window
                while i < len(chunks) and len(inflight) < self.cfg.depth:
                    ch = chunks[i]
                    i += 1
                    if self._device_stage(ch):
                        inflight.append(ch)
                    else:
                        self._bucket_chunk_done(ch.bucket)
                self._set_depths(len(inflight), len(backlog))
                # 2. consume the oldest chunk's encode result
                if inflight:
                    ch = inflight.pop(0)
                    res = self._consume(ch)
                    if isinstance(res, Exception):
                        self._fail_chunk(ch, res)
                        continue
                    for (job, _), (cid, files) in zip(ch.entries, res):
                        taskid = job.data["taskid"]
                        self._stage_event(taskid, "encode", job.id,
                                          cid=cid)
                        backlog.append((job, taskid, cid, files,
                                        ch.t_start, ch.bucket))
                    # 3. backpressure: drain the backlog down to its
                    #    bound now, while the chip still holds the
                    #    window's remaining chunks — after the append,
                    #    so the bound is a true ceiling on held bytes
                    while len(backlog) > self.cfg.max_inflight_pins:
                        self._c_stalls.inc(stage="network")
                        done += self._network_stage(backlog.pop(0))
                elif backlog:
                    # nothing on the chip and nothing left to dispatch
                    while backlog:
                        done += self._network_stage(backlog.pop(0))
        finally:
            self._set_depths(0, 0)
            self._stop_profile()
        # detlint: allow[DET101] obs idle accounting only
        self.node._account_idle(t_open, time.perf_counter(), [
            (ch.t_dispatch, ch.t_ready, ch.idx) for ch in chunks
            if ch.t_ready is not None])
        return done

    def _plan(self, buckets: list) -> list[_Chunk]:
        b = max(1, self.node.config.canonical_batch)
        chunks: list[_Chunk] = []
        self._bucket_keys: dict[int, tuple] = {}
        # one hydrated input per bucket — the perfscope card bind's
        # cache_tag join key (node._observe_infer), same element
        # bucket_disk_warm uses
        self._bucket_h0: dict[int, dict] = {}
        for bi, (model, entries, key) in enumerate(buckets):
            self._bucket_keys[bi] = key
            if entries:
                self._bucket_h0[bi] = entries[0][1]
            items = [(h, h["seed"]) for _, h in entries]
            for ci, (padded, real) in enumerate(chunk_items(items, b)):
                chunks.append(_Chunk(
                    idx=len(chunks), bucket=bi, model=model,
                    entries=entries[ci * b:ci * b + real],
                    items=padded, real=real))
        return chunks

    def _device_stage(self, ch: _Chunk) -> bool:
        """Dispatch one chunk. Pipelined runners (dispatch/finalize)
        queue the XLA program and return; plain runners compute here.
        Returns False when the chunk failed (its jobs quarantined)."""
        ch.t_start = self.node.chain.now
        runner = ch.model.runner
        taskids = [job.data["taskid"] for job, _ in ch.entries]
        if self._profiled is None and self.node._profile_due():
            import jax

            jax.profiler.start_trace(self.node.config.profile_dir)
            self._profiled = ch.idx
        # detlint: allow[DET101] obs stage timing; never reaches solve bytes
        ch.t_dispatch = time.perf_counter()
        self._infer_start.setdefault(ch.bucket, ch.t_dispatch)
        try:
            with self.node.obs.span(
                    "solve.dispatch", n=ch.real, batch=len(ch.items),
                    chunk=[self._gen, ch.idx], model=ch.model.id,
                    taskids=taskids,
                    **program_attrs(runner, ch.items)) as dsp:
                dispatch = getattr(runner, "dispatch", None)
                finalize = getattr(runner, "finalize", None)
                if dispatch is not None and finalize is not None:
                    payload = ("dev", dispatch(ch.items))
                else:
                    run_batch = getattr(runner, "run_batch", None)
                    if run_batch is not None and len(ch.items) > 1:
                        payload = ("files", run_batch(ch.items))
                    else:
                        payload = ("files", [runner(h, s)
                                             for h, s in ch.items[:ch.real]])
        except Exception as e:  # noqa: BLE001 — chunk-level quarantine
            log.warning("pipeline device stage failed: %r", e)
            if self._profiled == ch.idx:
                self._stop_profile()
            self._fail_chunk(ch, e)
            return False
        # detlint: allow[DET101] obs stage timing; never reaches solve bytes
        t_done = time.perf_counter()
        self._h_stage.observe(t_done - ch.t_dispatch, stage="device")
        for taskid in taskids:
            self.node._record_queue_wait(taskid, ch.t_dispatch)
        # dispatch succeeded ⇒ the bucket's executable is compiled —
        # feed the packer's warm-preference set (docs/scheduler.md);
        # state lock: a /debug snapshot may iterate the warm set
        with self.node.state_lock:
            self.node._sched.mark_warm(self._bucket_keys[ch.bucket])
        for job, _ in ch.entries:
            self._stage_event(job.data["taskid"], "solve", job.id)
        # a plain runner computed in the call: ready when it returned
        args = (ch.model, payload, ch.real, [self._gen, ch.idx],
                dsp.span_id, None if payload[0] == "dev" else t_done)
        if self._workers:
            self._encode_q.put(((self._gen, ch.idx), args))
        else:
            ch.payload = args
        return True

    def _consume(self, ch: _Chunk):
        """Block until chunk `ch`'s encode result is ready; returns the
        [(cid, files)] list or the exception the stage raised. Also
        feeds `arbius_stage_seconds{infer}` so the profitability gate
        and NodeMetrics see the same cost signal as the serial path."""
        if not self._workers:
            elapsed, ch.t_ready, out = _finish_chunk(*ch.payload)
            ch.payload = None
        else:
            key = (self._gen, ch.idx)
            with self._cv:
                if key not in self._results:
                    self._c_stalls.inc(stage="encode")
                while key not in self._results:
                    self._cv.wait()
                elapsed, ch.t_ready, out = self._results.pop(key)
        if self._profiled == ch.idx:
            self._stop_profile()
        self._h_stage.observe(elapsed, stage="encode")
        self._bucket_chunk_done(ch.bucket, ok=not isinstance(out, Exception))
        return out

    def _stop_profile(self) -> None:
        if self._profiled is not None:
            import jax

            self._profiled = None
            jax.profiler.stop_trace()

    def _network_stage(self, item: tuple) -> int:
        """Pin → commit → reveal one task on the tick thread, resuming
        past stages a previous life already landed (same CID only)."""
        job, taskid, cid, files, t_start, bucket = item
        node = self.node
        # detlint: allow[DET101] obs stage timing; never reaches solve bytes
        t0 = time.perf_counter()
        state = node.db.get_pipeline_stage(taskid)
        resumed = STAGE_RANK.get(state[0], -1) \
            if state is not None and state[1] == cid else -1
        try:
            with span("solve.task", taskid=taskid, cid=cid):
                if resumed >= STAGE_RANK["pin"]:
                    # the bytes were pinned before the crash; re-pinning
                    # would only re-run the 60 s-timeout network call
                    self._stage_event(taskid, "pin", job.id, cid=cid,
                                      resumed=True)
                else:
                    node._store_solution(taskid, cid, files)
                    node.db.set_pipeline_stage(taskid, "pin", cid)
                    self._stage_event(taskid, "pin", job.id, cid=cid)
                node._commit_reveal(
                    taskid, cid, t_start,
                    skip_commit=resumed >= STAGE_RANK["commit"],
                    progress=lambda stage, resumed=False:
                        self._progress(job.id, taskid, cid, stage, resumed))
            node.db.clear_pipeline_state(taskid)
            node.db.delete_job(job.id)
            done = 1
        except Exception as e:  # noqa: BLE001 — per-task quarantine
            log.warning("pipeline network stage failed for %s: %r",
                        taskid, e)
            node._fail_job(job, e)
            done = 0
        # detlint: allow[DET101] obs stage timing; never reaches solve bytes
        elapsed = time.perf_counter() - t0
        self._h_stage.observe(elapsed, stage="network")
        self._commit_acc[bucket] = \
            self._commit_acc.get(bucket, 0.0) + elapsed
        self._commit_left[bucket] -= 1
        if self._commit_left[bucket] == 0:
            node._h_stage.observe(self._commit_acc[bucket], stage="commit")
        return done

    def _progress(self, jobid: int, taskid: str, cid: str, stage: str,
                  resumed: bool) -> None:
        """_commit_reveal's checkpoint hook: the chain accepted the
        stage's write (or a previous life had), so record it."""
        node = self.node
        if not resumed:
            node.db.set_pipeline_stage(taskid, stage, cid)
        self._stage_event(taskid, stage, jobid, cid=cid,
                          **({"resumed": True} if resumed else {}))

    def _bucket_chunk_done(self, bucket: int, ok: bool = False) -> None:
        """One bucket ⇒ one infer sample: the wall window from the
        bucket's first dispatch to its last chunk leaving encode,
        emitted only if at least one chunk succeeded (an all-failed
        bucket emits nothing, like the serial path)."""
        self._infer_left[bucket] -= 1
        if ok:
            self._infer_ok.add(bucket)
        if self._infer_left[bucket] == 0 and bucket in self._infer_ok:
            self._infer_ok.discard(bucket)
            # cost-tagged (and perfscope-bound) exactly like the serial
            # path, so the learned model and the card read one signal
            # whichever schedule ran
            self.node._observe_infer(
                self._bucket_keys[bucket], self._bucket_n[bucket],
                # detlint: allow[DET101] obs stage timing; never reaches solve bytes
                time.perf_counter() - self._infer_start[bucket],
                hydrated=self._bucket_h0.get(bucket))

    # -- bookkeeping -------------------------------------------------------
    def _stage_event(self, taskid: str, stage: str, jobid: int,
                      **fields) -> None:
        """Journal one stage completion. `jobid` identifies the solve
        ATTEMPT: replayed chain events legitimately queue duplicate
        solve jobs for an already-solved task, and each attempt walks
        the stages from the top — SIM109's monotonicity is per
        (task, attempt), reset by a crash boundary."""
        self.node.obs.event("pipeline_stage", taskid=taskid, stage=stage,
                            jobid=jobid, rank=STAGE_RANK[stage], **fields)

    def _fail_chunk(self, ch: _Chunk, e: Exception) -> None:
        for job, _ in ch.entries:
            self.node._fail_job(job, e)
        # its tasks never reach the network stage — keep the per-bucket
        # commit-sample accounting converging
        self._commit_left[ch.bucket] -= ch.real
        if self._commit_left[ch.bucket] == 0 and \
                self._commit_acc.get(ch.bucket, 0.0) > 0.0:
            self.node._h_stage.observe(self._commit_acc[ch.bucket],
                                       stage="commit")

    def _set_depths(self, device: int, network: int) -> None:
        self._g_depth.set(device, stage="device")
        self._g_depth.set(self._encode_q.qsize(), stage="encode")
        self._g_depth.set(network, stage="network")
