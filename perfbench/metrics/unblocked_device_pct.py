"""Device: percent of the operation seconds of the traced window's whole
chunks spent on operations that no named block of their program holds
(work outside the blocks, or an instruction the compiler made and left
no name on) — what the block metrics cannot see (perfbench/blocks.py).
Source: the device trace."""
from perfbench.blocks import unblocked_pct


def read(run):
    return unblocked_pct(run)
