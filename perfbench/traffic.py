"""The one traffic generator: a seeded task sequence from a data file.

A traffic file states (all keys required unless marked):

    loop         "closed": keep `outstanding` tasks on the chain, topped up
                 each time solutions land (the only kind there is so far)
    outstanding  tasks kept outstanding
    cycle        [{"model": <template>, "count": n}, ...]: every run of
                 sum(count) consecutive tasks holds exactly these, in an
                 order shuffled by the seed — so every seed does the same
                 set of work in another order
    tasks        {<template>: {"input": {field: value}, "prompt_words":
                 [lo, hi]}}: the fields a task of that model submits, and
                 how many words its distinct, seeded prompt has. In place
                 of `prompt_words` (exactly one of the two), `prompt_bytes`
                 [lo, hi] states the length in the unit a byte-tokenised
                 model works in: the same word stream, cut to a length in
                 bytes drawn uniformly from the range (lo at least
                 MIN_PROMPT_BYTES, so the task's index is never cut)
    min_ticks    (optional, 1 where absent) the least number of ticks —
                 of times the backlog is solved whole — the measured
                 window holds: it closes at the first `tick()` return at
                 or after `--seconds` and after this many ticks, so a cell
                 whose tick is about as long as `--seconds` measures the
                 same number of ticks in every run, and one tick that
                 loses seconds cannot halve its window. A `--trace 1`
                 run, whose readers see its first tick alone, closes at
                 `--seconds` whatever this says
    check        {"buckets": {<template>: n}}: how many whole buckets of
                 each model's finished tasks, every slot of each, are
                 compared with the plain reference (perfbench/correct.py)

Nothing in here names a cell. Prompts are distinct within a run (the
task's index is in them), so no two tasks share a task id or an answer.
"""
from __future__ import annotations

import json
import random

WORDS = ("amber basalt cedar delta ember fjord glacier harbor iris jade "
         "kelp lantern meadow nebula orchid prairie quartz raven saffron "
         "tundra umbra violet willow xenon yarrow zephyr miner chip tensor "
         "lattice beacon orbit").split()
MIN_PROMPT_BYTES = 16


class Traffic:
    def __init__(self, spec: dict, seed: int):
        if spec.get("loop") != "closed":
            raise ValueError(f"traffic loop {spec.get('loop')!r}: only "
                             "'closed' is implemented")
        self.spec = spec
        self.outstanding = int(spec["outstanding"])
        self.min_ticks = int(spec.get("min_ticks", 1))
        if self.min_ticks < 1:
            raise ValueError(f"traffic min_ticks {spec['min_ticks']!r}: "
                             "a window holds at least one tick")
        self.cycle = [(c["model"], int(c["count"])) for c in spec["cycle"]]
        for model, t in spec["tasks"].items():
            if ("prompt_words" in t) == ("prompt_bytes" in t):
                raise ValueError(f"traffic tasks.{model}: exactly one of "
                                 "prompt_words and prompt_bytes")
            if "prompt_bytes" in t \
                    and t["prompt_bytes"][0] < MIN_PROMPT_BYTES:
                raise ValueError(f"traffic tasks.{model}: prompt_bytes "
                                 f"starts under {MIN_PROMPT_BYTES}")
        self._rng = random.Random(f"perfbench-traffic-{seed}")
        self._index = 0
        self._queue: list[str] = []

    def models(self) -> list[str]:
        return [m for m, _ in self.cycle]

    def _next_model(self) -> str:
        if not self._queue:
            block = [m for m, n in self.cycle for _ in range(n)]
            self._rng.shuffle(block)
            self._queue = block
        return self._queue.pop(0)

    def task(self, model: str | None = None, tag: str = "t") -> tuple[str, dict]:
        """(template, input dict) of the next task; `model` forces one (the
        warm-up) without consuming the cycle."""
        if model is None:
            model = self._next_model()
        t = self.spec["tasks"][model]
        if "prompt_words" in t:
            n = self._rng.randint(*t["prompt_words"])
            words = " ".join(self._rng.choice(WORDS) for _ in range(n))
            self._index += 1
            prompt = f"{tag}{self._index} {words}"
        else:
            n = self._rng.randint(*t["prompt_bytes"])
            self._index += 1
            prompt = f"{tag}{self._index}"
            while len(prompt) < n:      # WORDS are ASCII: a byte a letter
                prompt += " " + self._rng.choice(WORDS)
            prompt = prompt[:n]
        return model, {**t["input"], "prompt": prompt}


def encode(task_input: dict) -> bytes:
    return json.dumps(task_input, sort_keys=True).encode()
