"""Expert layer: device seconds of the `latent_moe` block (the router,
the latent down-projection, the held experts' sort, products and combine
in the latent space, the up-projection; not the shared expert), in
prefill and decode alike, per real solution of the traced window's whole
chunks (perfbench/blocks.py). Source: the device trace. A program whose
blocks name no such scope: nothing."""
from perfbench.blocks import block_s_per_sol


def read(run):
    return block_s_per_sol(run, "latent_moe")
