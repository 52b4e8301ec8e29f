"""Sparse attention: device seconds of the `indexer` block (the index
scores of every query against the keys up to it and the top-k selection
over them), in prefill and decode alike, per real solution of the traced
window's whole chunks (perfbench/blocks.py). Source: the device trace."""
from perfbench.blocks import block_s_per_sol


def read(run):
    return block_s_per_sol(run, "indexer")
