"""Bucket programs: device seconds of the `prefill` block (the prompt
pass up to the last position's logits), per real solution of the
traced window's whole chunks — the program's named blocks joined to the
profiler's operations (perfbench/blocks.py). Source: the device trace."""
from perfbench.blocks import block_s_per_sol


def read(run):
    return block_s_per_sol(run, "prefill")
