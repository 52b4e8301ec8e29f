"""Window attention: device seconds of the `window_attention` block (a
sliding layer's latent attention over its band — prefill's banded
kernel or walk, and decode's latent form over the ring, with their
projections and gates), in prefill and decode alike, per real solution
of the traced window's whole chunks (perfbench/blocks.py). Source: the
device trace. A program whose blocks name no such scope: nothing."""
from perfbench.blocks import block_s_per_sol


def read(run):
    return block_s_per_sol(run, "window_attention")
