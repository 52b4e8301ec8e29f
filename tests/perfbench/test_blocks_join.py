"""The join of the program's named blocks with the profiler's operations
(perfbench/blocks.py), on hand-built span and event lists in the forms
`harness.Run.spans` and `run.trace["events"]` have: which chunks are
whole, the two clocks, two programs whose instruction names collide,
and the six readers on a program that names its blocks and on one that
does not."""
from __future__ import annotations

import pytest

from pb_paths import ROOT  # noqa: F401 — puts the repo root on the path

from perfbench import blocks as pb
from perfbench import manifest as mf

SHIFT = 100.0      # the profiler's clock runs 100 s ahead of the spans'


def _span(name, t0, t1, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs}


def _chunk(i, t0, ready, program="text.a", n=4):
    return [_span("solve.dispatch", t0, t0 + 0.01, n=n, batch=4,
                  chunk=[7, i], program=program),
            _span("solve.device_wait", ready - 0.5, ready, chunk=[7, i])]


MAP_A = {"fusion.1": ("prefill",), "fusion.2": ("prefill", "attention"),
         "fusion.3": ("decode",), "copy.4": ()}
# the other model's program: the same instruction names, other blocks
MAP_B = {"fusion.1": ("unet",), "fusion.2": ("vae",), "fusion.3": ("unet",)}


def _ops(t0):
    """One bucket's operations from t0 on (profiler clock)."""
    return [("fusion.1", t0 + 0.0, 1.0), ("fusion.2", t0 + 1.0, 0.5),
            ("fusion.3", t0 + 1.5, 2.0), ("copy.4", t0 + 3.5, 0.25),
            ("fusion.7", t0 + 3.75, 0.25)]


def _maps(tag):
    return {"text.a": MAP_A, "image.b": MAP_B}.get(tag)


def test_chunks_start_where_the_one_before_was_ready():
    spans = _chunk(0, 1.0, 5.0) + _chunk(1, 1.5, 9.0) \
        + [_span("solve.dispatch", 2.0, 2.1, n=4, chunk=[7, 2])]
    got = pb.chunks(spans)
    # the chunk whose dispatch names no program is not read
    assert [(c["t0"], c["t1"]) for c in got] == [(1.0, 5.0), (5.0, 9.0)]
    assert {c["program"] for c in got} == {"text.a"}


def test_the_shift_puts_each_operation_in_its_chunk():
    spans = _chunk(0, 1.0, 5.0) + _chunk(1, 1.5, 9.0)
    events = _ops(1.0 + SHIFT) + _ops(5.0 + SHIFT)
    whole = pb.join(pb.chunks(spans), events, SHIFT, 0.5, 10.0, _maps)
    assert len(whole) == 2
    for ch in whole:
        assert ch["ops_s"] == pytest.approx(4.0)
        assert ch["blocks"] == {"prefill": pytest.approx(1.5),
                                "attention": pytest.approx(0.5),
                                "decode": pytest.approx(2.0)}
        assert ch["unblocked_s"] == pytest.approx(0.5)
        assert ch["unmapped_s"] == pytest.approx(0.25)
    # read on the wrong clock, nothing lands in the chunks
    wrong = pb.join(pb.chunks(spans), events, 0.0, 0.5, 10.0, _maps)
    assert [c["ops_s"] for c in wrong] == [0.0, 0.0]


def test_a_trace_cut_inside_the_second_chunk_keeps_the_first_alone():
    """The profiler kept the first chunk and half the second (its buffer
    filled): the second is read nowhere, the first whole."""
    spans = _chunk(0, 1.0, 5.0) + _chunk(1, 1.5, 9.0)
    events = _ops(1.0 + SHIFT) + _ops(5.0 + SHIFT)[:2]
    whole = pb.join(pb.chunks(spans), events, SHIFT, 0.5, 10.0, _maps)
    assert [c["t1"] for c in whole] == [5.0]
    assert whole[0]["ops_s"] == pytest.approx(4.0)
    # a trace whose last operation ends a few milliseconds before the
    # host saw the last chunk ready keeps it
    early = _ops(1.0 + SHIFT) + _ops(5.0 + SHIFT)
    early[-1] = ("fusion.7", 8.75 + SHIFT, 0.245)
    assert len(pb.join(pb.chunks(spans), early, SHIFT, 0.5, 10.0,
                       _maps)) == 2
    early[-1] = ("fusion.7", 8.75 + SHIFT, 0.2)
    assert len(pb.join(pb.chunks(spans), early, SHIFT, 0.5, 10.0,
                       _maps)) == 1
    # a chunk that runs past the traced window is not whole either
    assert [c["t1"] for c in pb.join(pb.chunks(spans), early, SHIFT, 0.5,
                                      9.0, _maps)] == [5.0]


def test_two_programs_with_colliding_names_are_told_apart_by_time():
    """A mix: one model's bucket, then the other's; the same instruction
    names mean other blocks in each."""
    spans = _chunk(0, 1.0, 5.0, "text.a") + _chunk(1, 1.5, 9.0, "image.b")
    events = _ops(1.0 + SHIFT) + _ops(5.0 + SHIFT)
    a, b = pb.join(pb.chunks(spans), events, SHIFT, 0.5, 10.0, _maps)
    assert set(a["blocks"]) == {"prefill", "attention", "decode"}
    assert b["blocks"] == {"unet": pytest.approx(3.0),
                           "vae": pytest.approx(0.5)}
    # copy.4 and fusion.7 are in no map of the image program
    assert b["unmapped_s"] == b["unblocked_s"] == pytest.approx(0.5)


def test_blocks_and_the_unblocked_share_add_up_to_each_chunks_seconds():
    spans = _chunk(0, 1.0, 5.0, "text.a") + _chunk(1, 1.5, 9.0, "image.b")
    events = _ops(1.0 + SHIFT) + _ops(5.0 + SHIFT) \
        + [("fusion.1", 9.5 + SHIFT, 0.25)]        # after the last chunk
    for ch in pb.join(pb.chunks(spans), events, SHIFT, 0.5, 10.0, _maps):
        outer = {"text.a": ("prefill", "decode"),
                 "image.b": ("unet", "vae")}[ch["program"]]
        assert sum(ch["blocks"][b] for b in outer) + ch["unblocked_s"] \
            == pytest.approx(ch["ops_s"])


class _Obs:
    def blocks(self, tag):
        return _maps(tag)


class _Run:
    def __init__(self, spans, events, obs=None, cell_seconds=10.0):
        self.spans = spans
        self.trace = None if events is None else {"events": events,
                                                  "shift": SHIFT}
        self.window = {"t0": 0.5}
        self.seconds = cell_seconds - 0.5
        node = type("Node", (), {})()
        if obs is not None:
            node.obs = obs
        self.system = type("System", (), {"node": node})()


NEW = ("prefill_s_per_sol", "decode_s_per_sol", "routed_experts_s_per_sol",
       "indexer_s_per_sol", "unet_s_per_sol", "unblocked_device_pct")


@pytest.fixture(scope="module")
def readers():
    cells = {"trinity-ep8-8k-backlog", "dsv32-ep16-16k-backlog",
             "joyai-ep1-2k-512-backlog", "k2-768-backlog", "mix-768-backlog"}
    out = {}
    for name in cells:
        cell = mf.Cell(mf.DEFAULT_MANIFEST, name)
        for m in cell.per_layer():
            if m["name"] in NEW:
                assert (m["source"], m["moves"]) == ("device_trace",
                                                     "sol_per_hour")
                out[m["name"]] = cell.reader(m["name"])
    assert set(out) == set(NEW)
    return out


def test_the_readers_on_a_program_that_names_its_blocks(readers):
    spans = _chunk(0, 1.0, 5.0, "text.a") + _chunk(1, 1.5, 9.0, "image.b")
    events = _ops(1.0 + SHIFT) + _ops(5.0 + SHIFT)
    run = _Run(spans, events, _Obs())
    assert readers["prefill_s_per_sol"](run) == pytest.approx(1.5 / 8)
    assert readers["decode_s_per_sol"](run) == pytest.approx(2.0 / 8)
    assert readers["unet_s_per_sol"](run) == pytest.approx(3.0 / 8)
    assert readers["unblocked_device_pct"](run) \
        == pytest.approx(100 * 1.0 / 8.0)
    # no operation of these blocks was read
    assert readers["routed_experts_s_per_sol"](run) is None
    assert readers["indexer_s_per_sol"](run) is None


@pytest.mark.parametrize("case", ["no trace", "no map", "no program",
                                  "cut before the first ready"])
def test_the_readers_read_nothing_where_nothing_is_whole(readers, case):
    """The parent of this change: its spans name no program and its obs
    builds no map; and a trace that kept no chunk whole."""
    spans = _chunk(0, 1.0, 5.0)
    events = _ops(1.0 + SHIFT)
    run = {"no trace": lambda: _Run(spans, None, _Obs()),
           "no map": lambda: _Run(spans, events, None),
           "no program": lambda: _Run(
               [{**s, "attrs": {k: v for k, v in s["attrs"].items()
                                if k != "program"}} for s in spans],
               events, _Obs()),
           "cut before the first ready": lambda: _Run(
               spans, events[:2], _Obs())}[case]()
    for name in NEW:
        assert readers[name](run) is None, name
