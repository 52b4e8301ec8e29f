"""The solve path times itself (docs/observability.md): one span chain
per dispatched chunk on every thread of both schedules, a ready stamp
per chunk, chip idle reckoned from them, queue wait per task, and an
operator's profile that covers the bucket it names."""
from __future__ import annotations

import time

import pytest

from arbius_tpu.node.config import PipelineConfig
from arbius_tpu.obs import span
from tests.test_node import drain, submit
from tests.test_pipeline import PIPE_ON, _SD15FakeRunner, _world

INLINE = PipelineConfig(enabled=True, depth=2, encode_workers=0,
                        max_inflight_pins=2)
SCHEDULES = {"staged-workers": PIPE_ON, "staged-inline": INLINE,
             "serial": None}
DEVICE_S, ENCODE_S = 0.30, 0.06


class _Dev:
    """A device result that is ready at a set moment."""

    def __init__(self, ready_at: float, data: list):
        self.ready_at, self.data = ready_at, data

    def block_until_ready(self):
        left = self.ready_at - time.perf_counter()
        if left > 0:
            time.sleep(left)
        return self


class _ChipFakeRunner(_SD15FakeRunner):
    """SD15Runner-shaped over a one-program-at-a-time fake chip: a
    chunk's result is ready `device_s` after the chip got to it, and
    finalize opens `solve.encode` as the real runners do."""

    def __init__(self, device_s: float = 0.0, encode_s: float = 0.0):
        super().__init__()
        self.device_s, self.encode_s = device_s, encode_s
        self._chip_free = 0.0

    def dispatch(self, items):
        self._chip_free = max(self._chip_free, time.perf_counter()) \
            + self.device_s
        return _Dev(self._chip_free, super().dispatch(items))

    def finalize(self, dev, n_real):
        with span("solve.encode", n=n_real, codec="png"):
            time.sleep(self.encode_s)
            return super().finalize(dev.data, n_real)


def _spans(node, name=None):
    return [e for e in node.obs.journal.events(kind="span")
            if name is None or e["name"] == name]


def _end(e):
    return e["mono_start"] + e["wall_s"]


def _solve(runner, pipeline, n_tasks, batch):
    eng, node, mid, pinner = _world(runner, pipeline=pipeline,
                                    canonical_batch=batch)
    tids = [submit(eng, mid, prompt=f"t{i}") for i in range(n_tasks)]
    drain(node)
    cids = {t: eng.solutions[bytes.fromhex(t[2:])].cid for t in tids}
    return node, tids, cids


# -- (a) one chain per chunk, on every thread -------------------------------

@pytest.mark.parametrize("schedule", SCHEDULES)
def test_chunk_chain_parents_chunks_and_taskids(schedule):
    node, tids, _ = _solve(_ChipFakeRunner(), SCHEDULES[schedule], 5, 2)
    dispatches = _spans(node, "solve.dispatch")
    assert [d["attrs"]["chunk"][1] for d in dispatches] == [0, 1, 2]
    assert len({d["attrs"]["chunk"][0] for d in dispatches}) == 1
    assert [d["taskids"] for d in dispatches] == [tids[0:2], tids[2:4],
                                                  tids[4:5]]
    assert [(d["attrs"]["n"], d["attrs"]["batch"]) for d in dispatches] \
        == [(2, 2), (2, 2), (1, 2)]
    for d in dispatches:
        assert d["attrs"]["model"] == dispatches[0]["attrs"]["model"]
        kids = [e for e in _spans(node) if e["parent_id"] == d["span_id"]]
        kids.sort(key=lambda e: e["mono_start"])
        assert [k["name"] for k in kids] == ["solve.device_wait",
                                             "solve.encode", "solve.cid"]
        wait, encode, cid = kids
        assert wait["attrs"]["chunk"] == cid["attrs"]["chunk"] \
            == d["attrs"]["chunk"]
        assert cid["attrs"]["n"] == encode["attrs"]["n"] == d["attrs"]["n"]
        # a chain: each starts where the one before it ended, or later
        assert d["mono_start"] <= wait["mono_start"]
        assert _end(wait) <= encode["mono_start"] + 1e-5
        assert _end(encode) <= cid["mono_start"] + 1e-5
    # and /debug/trace shows the chunk under the task it solved
    roots = node.obs.task_trace(tids[4])

    def find(nodes, name):
        for n in nodes:
            if n["name"] == name:
                yield n
            yield from find(n["children"], name)

    mine = [d for d in find(roots, "solve.dispatch")
            if tids[4] in d["taskids"]]
    assert len(mine) == 1
    assert [c["name"] for c in mine[0]["children"]] == [
        "solve.device_wait", "solve.encode", "solve.cid"]
    node.close()


# -- (b) idle is what no bucket hides ---------------------------------------

@pytest.mark.parametrize("schedule", ["staged-workers", "serial"])
def test_idle_is_the_last_chunks_tail_and_the_counter_is_its_sum(schedule):
    """k2-768-backlog-shaped: 8 tasks, 2 chunks of 4. The first chunk's
    encode runs while the second is on the chip; the second's is idle,
    and so is the network tail that comes after it (the serial path
    commits the whole bucket then; the staged path has done at least
    the first chunk's tasks beyond max_inflight_pins by then)."""
    runner = _ChipFakeRunner(DEVICE_S, ENCODE_S)
    eng, node, mid, _ = _world(runner, pipeline=SCHEDULES[schedule],
                               canonical_batch=4)
    c_idle = node.obs.registry.counter("arbius_chip_idle_seconds_total")
    before = c_idle.value()
    for i in range(8):
        submit(eng, mid, prompt=f"t{i}")
    drain(node)
    idle = _spans(node, "solve.idle")
    total = sum(e["wall_s"] for e in idle)
    assert c_idle.value() - before == pytest.approx(total, abs=1e-4)
    waits = {tuple(e["attrs"]["chunk"])[1]: e
             for e in _spans(node, "solve.device_wait")}
    encodes = sorted(_spans(node, "solve.encode"),
                     key=lambda e: e["mono_start"])
    root = _spans(node, "solve.pipeline" if schedule != "serial"
                  else "solve.batch")[-1]
    last = max(idle, key=lambda e: e["wall_s"])
    assert last["attrs"]["after_chunk"] == 1
    assert last["parent_id"] == root["span_id"]
    # from the moment the last chunk was ready to the end of the pass:
    # its encode, its CIDs and the pin/commit/reveal still to do
    assert last["mono_start"] == pytest.approx(_end(waits[1]), abs=2e-3)
    assert _end(last) == pytest.approx(_end(root), abs=5e-3)
    assert last["mono_start"] <= encodes[1]["mono_start"]
    assert _end(encodes[1]) <= _end(last)
    late = sum(last["mono_start"] <= r["mono_start"]
               for r in _spans(node, "solve.reveal"))
    assert late == 8 if schedule == "serial" else 4 <= late <= 6
    # ... and not the first chunk's, which the second hides
    assert _end(encodes[0]) <= last["mono_start"]
    assert ENCODE_S <= total < DEVICE_S
    if schedule != "serial":
        # the worker waited out its chunk's program (up to two of them)
        # before encoding; the stage's seconds start at the ready stamp
        h = node.obs.registry.histogram("arbius_pipeline_stage_seconds",
                                        labelnames=("stage",))
        samples = h.values(stage="encode")[-2:]
        assert all(ENCODE_S <= s < DEVICE_S for s in samples), samples
    node.close()


# -- (c) queue wait ---------------------------------------------------------

@pytest.mark.parametrize("schedule", ["staged-workers", "serial"])
def test_queue_wait_runs_from_the_task_event_to_its_dispatch(schedule):
    node, tids, _ = _solve(_ChipFakeRunner(), SCHEDULES[schedule], 3, 2)
    events = {e["taskid"]: e for e in _spans(node, "task.event")}
    waits = {e["taskid"]: e for e in _spans(node, "task.queue_wait")}
    assert set(waits) == set(tids)
    for d in _spans(node, "solve.dispatch"):
        for tid in d["taskids"]:
            w = waits[tid]
            assert w["parent_id"] == events[tid]["span_id"]
            assert w["mono_start"] == pytest.approx(_end(events[tid]),
                                                    abs=1e-5)
            assert _end(w) == pytest.approx(d["mono_start"], abs=1e-4)
    assert [n["name"] for n in node.obs.task_trace(tids[0])[0]["children"]] \
        == ["task.queue_wait"]
    # a task whose event an earlier life took in has no stamp: no span
    assert node._event_done == {}
    node._record_queue_wait("0xdead", 1.0)
    assert len(_spans(node, "task.queue_wait")) == 3
    node.close()


# -- (d) tracing off, and a worker that dies --------------------------------

@pytest.mark.parametrize("schedule", SCHEDULES)
def test_obs_off_journals_nothing_solves_the_same_and_still_counts(schedule):
    on, _, cids_on = _solve(_ChipFakeRunner(0.0, 0.02), SCHEDULES[schedule],
                            3, 2)
    on.close()
    eng, node, mid, _ = _world(_ChipFakeRunner(0.0, 0.02),
                               pipeline=SCHEDULES[schedule],
                               canonical_batch=2)
    node.obs.enabled = node.obs.tracer.enabled = False
    tids = [submit(eng, mid, prompt=f"t{i}") for i in range(3)]
    before = len(node.obs.journal)
    drain(node)
    assert [e for e in node.obs.journal.events()[before:]
            if e["kind"] == "span"] == []
    assert {t: eng.solutions[bytes.fromhex(t[2:])].cid for t in tids} \
        == cids_on
    # the registry stays truthful with tracing off
    assert node.obs.registry.counter(
        "arbius_chip_idle_seconds_total").value() >= 0.02
    node.close()


def test_worker_death_posts_its_result_with_spans_closed_as_errors():
    class Dying(_ChipFakeRunner):
        def finalize(self, dev, n_real):
            with span("solve.encode", n=n_real, codec="png"):
                raise KeyboardInterrupt("worker killed")

    eng, node, mid, _ = _world(Dying(), pipeline=PIPE_ON, canonical_batch=2)
    tids = [submit(eng, mid, prompt=f"t{i}") for i in range(2)]
    drain(node)   # must return, not hang
    assert {d.get("taskid") for m, d in node.db.failed_jobs()
            if m == "solve"} == set(tids)
    (dispatch,) = _spans(node, "solve.dispatch")
    (encode,) = _spans(node, "solve.encode")
    assert encode["status"] == "error"
    assert "KeyboardInterrupt" in encode["error"]
    assert encode["parent_id"] == dispatch["span_id"]
    (wait,) = _spans(node, "solve.device_wait")
    assert wait["status"] == "ok"
    node.close()


# -- the operator's profile -------------------------------------------------

def test_profile_covers_the_bucket_and_holds_the_programs_spans(tmp_path):
    """profile_dir on the staged path: the trace opens at the chunk's
    dispatch and closes when its result is consumed, and the program's
    spans are in its host plane."""
    import dataclasses
    import glob

    from jax.profiler import ProfileData

    eng, node, mid, _ = _world(_ChipFakeRunner(0.05, 0.01),
                               pipeline=PIPE_ON, canonical_batch=2)
    node.config = dataclasses.replace(
        node.config, profile_dir=str(tmp_path), profile_every=1)
    for i in range(4):
        submit(eng, mid, prompt=f"t{i}")
    drain(node)
    assert node._pipeline._profiled is None
    files = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                                 "*.xplane.pb")))
    # depth 2: the second chunk is dispatched inside the first's trace
    # (one session at a time), so two chunks make one profile
    assert len(files) == 1
    data = ProfileData.from_file(files[0])
    host = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("solve."):
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns, ev.duration_ns))
    assert {"solve.dispatch", "solve.device_wait", "solve.encode",
            "solve.cid"} <= set(host)
    # the first chunk's whole wait for the device is inside the trace
    assert max(d for _, d in host["solve.device_wait"]) >= 0.04e9
    node.close()
