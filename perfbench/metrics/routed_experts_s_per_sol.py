"""Expert layer: device seconds of the `routed_experts` block (the router
and the held experts' sort, tile loop and combine; not the shared
expert), in prefill and decode alike, per real solution of the traced
window's whole chunks (perfbench/blocks.py). Source: the device trace."""
from perfbench.blocks import block_s_per_sol


def read(run):
    return block_s_per_sol(run, "routed_experts")
