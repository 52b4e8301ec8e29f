"""Phase heartbeat shared by bench.py and tools/.

A long stage reports its current phase to stderr every few seconds, so
the tail of a run that was cut off says where it was.
"""
from __future__ import annotations

import threading


class Heartbeat:
    """Background thread reporting the current phase every `interval` s
    through `note` (a callable taking one string)."""

    def __init__(self, stage: str, note, interval: float = 15.0):
        self.stage = stage
        self.phase = "start"
        self._note = note
        self._interval = interval
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._run, daemon=True)
        self._t.start()

    def set(self, phase: str) -> None:
        # detlint: allow[CONC301,CONC401] single-writer cosmetic label:
        # the str publish is GIL-atomic and the reader tolerates
        # staleness
        self.phase = phase
        self._note(f"[{self.stage}] phase: {phase}")

    def _run(self) -> None:
        while not self._stop.wait(self._interval):
            self._note(f"[{self.stage}] heartbeat: phase={self.phase}")

    def stop(self) -> None:
        self._stop.set()
