"""Bucket programs: device seconds of the `decode` block (the whole
decode loop, its sampler and a speculative loop's drafts included), per
real solution of the traced window's whole chunks (perfbench/blocks.py).
Source: the device trace."""
from perfbench.blocks import block_s_per_sol


def read(run):
    return block_s_per_sol(run, "decode")
