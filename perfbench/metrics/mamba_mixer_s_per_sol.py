"""State-space layer: device seconds of the `mamba_mixer` block (a Mamba-2
layer from in_proj to out_proj — the conv, the chunked scan in prefill,
the recurrence of a step's positions in decode, the gated norm), in
prefill and decode alike, per real solution of the traced window's whole
chunks (perfbench/blocks.py). Source: the device trace. A program whose
blocks name no such scope: nothing."""
from perfbench.blocks import block_s_per_sol


def read(run):
    return block_s_per_sol(run, "mamba_mixer")
