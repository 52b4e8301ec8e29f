"""Bucket programs: device seconds of the `unet` block (the whole
denoising loop of an image model, every model of a mix), per real
solution of the traced window's whole chunks (perfbench/blocks.py).
Source: the device trace."""
from perfbench.blocks import block_s_per_sol


def read(run):
    return block_s_per_sol(run, "unet")
