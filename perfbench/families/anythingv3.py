"""Family `anythingv3` (Stable Diffusion 1.5 topology): the program's
pipeline and runner built from the configuration file's `arch`, and the
plain reference beside it."""
from __future__ import annotations

import functools

from perfbench.families import _image
from perfbench.reference import sd15 as reference

TEMPLATE = "anythingv3"
OUT_NAME = "out-1.png"
COMPARED = _image.COMPARED
decode = _image.decode
compare = functools.partial(_image.compare, reference)


def build(arch: dict, precision: str):
    from arbius_tpu.models.sd15 import SD15Config, SD15Pipeline
    from arbius_tpu.models.sd15.text_encoder import TextEncoderConfig
    from arbius_tpu.models.sd15.tokenizer import ByteTokenizer
    from arbius_tpu.models.sd15.unet import UNetConfig
    from arbius_tpu.models.sd15.vae import VAEConfig
    from arbius_tpu.node.solver import SD15Runner

    def tup(d):
        return {k: tuple(v) if isinstance(v, list) else v
                for k, v in d.items()}

    cfg = SD15Config(unet=UNetConfig(**tup(arch["unet"])),
                     vae=VAEConfig(**tup(arch["vae"])),
                     text=TextEncoderConfig(**arch["text"]))
    tk = arch["tokenizer"]
    tok = ByteTokenizer(max_length=arch["text"]["max_length"],
                        bos_id=tk["bos_id"], eos_id=tk["eos_id"])
    return SD15Pipeline(cfg, tokenizer=tok, precision=precision), SD15Runner


def kernel_calls(attn_calls):
    """The reference's attention calls that the program serves with its
    flash kernel: every unmasked attention at 1024 query rows or more
    (UNet self- and cross-attention at the two upper levels, the VAE's
    mid-block); the text tower's causal attention is 77 rows."""
    return [c for c in attn_calls if c[2] >= 1024]
