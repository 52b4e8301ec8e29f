"""Chunk-pipelined solve path: solve_cid_batch must overlap host
encode with the next dispatch WITHOUT changing output order or bytes."""
from __future__ import annotations

from arbius_tpu.node.solver import RegisteredModel, solve_cid_batch


class _Template:
    outputs = [type("O", (), {"filename": "out-1.png", "type": "image"})()]


class _PipelinedRunner:
    """Fake runner recording the dispatch/finalize schedule."""

    def __init__(self, log):
        self.log = log

    def __call__(self, hydrated, seed):
        return self.run_batch([(hydrated, seed)])[0]

    def run_batch(self, items):
        return self.finalize(self.dispatch(items), len(items))

    def dispatch(self, items):
        self.log.append(("dispatch", tuple(s for _, s in items)))
        return [f"img{s}".encode() for _, s in items]

    def finalize(self, dev, n_real):
        self.log.append(("finalize", tuple(dev[:n_real])))
        return [{"out-1.png": dev[i]} for i in range(n_real)]


def _model(log):
    return RegisteredModel(id="0x00", template=_Template(),
                           runner=_PipelinedRunner(log))


def test_pipeline_overlaps_and_preserves_order():
    log = []
    items = [({"prompt": f"p{i}"}, i) for i in range(7)]
    out = solve_cid_batch(_model(log), items, canonical_batch=2)
    # bytes + order identical to the serial path
    assert [f["out-1.png"] for _, f in out] == [f"img{i}".encode()
                                            for i in range(7)]
    # schedule actually overlaps: chunk 2's dispatch precedes chunk 1's
    # finalize (one-deep pipeline), incl. the padded last chunk
    kinds = [k for k, _ in log]
    assert kinds == ["dispatch", "dispatch", "finalize", "dispatch",
                     "finalize", "dispatch", "finalize", "finalize"]
    # padding repeats the last item but only the real result surfaces
    assert log[-1] == ("finalize", (b"img6",))


def test_single_chunk_stays_serial():
    log = []
    items = [({"prompt": "p"}, 1), ({"prompt": "q"}, 2)]
    out = solve_cid_batch(_model(log), items, canonical_batch=2)
    assert [f["out-1.png"] for _, f in out] == [b"img1", b"img2"]
    assert [k for k, _ in log] == ["dispatch", "finalize"]
