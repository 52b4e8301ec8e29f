"""Paths the benchmark's tests share."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TINY_MANIFEST = os.path.join(ROOT, "tests", "perfbench", "tiny",
                             "manifest.json")
