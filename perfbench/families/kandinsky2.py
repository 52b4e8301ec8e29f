"""Family `kandinsky2`: the program's pipeline and runner built from the
configuration file's `arch`, and the plain reference beside it."""
from __future__ import annotations

import functools

from perfbench.families import _image
from perfbench.reference import kandinsky2 as reference

TEMPLATE = "kandinsky2"
OUT_NAME = "out-1.png"
COMPARED = _image.COMPARED
decode = _image.decode
compare = functools.partial(_image.compare, reference)


def build(arch: dict, precision: str):
    """-> (pipeline, runner class) of the program, at the file's sizes."""
    from arbius_tpu.models.kandinsky2 import (
        Kandinsky2Config,
        Kandinsky2Pipeline,
    )
    from arbius_tpu.models.kandinsky2.decoder import DecoderConfig
    from arbius_tpu.models.kandinsky2.movq import MOVQConfig
    from arbius_tpu.models.kandinsky2.prior import PriorConfig
    from arbius_tpu.models.sd15.text_encoder import TextEncoderConfig
    from arbius_tpu.models.sd15.tokenizer import ByteTokenizer
    from arbius_tpu.models.sd15.unet import UNetConfig
    from arbius_tpu.node.solver import Kandinsky2Runner

    dec = dict(arch["decoder"])
    unet = {k: tuple(v) if isinstance(v, list) else v
            for k, v in dec.pop("unet").items()}
    movq = {k: tuple(v) if isinstance(v, list) else v
            for k, v in arch["movq"].items()}
    cfg = Kandinsky2Config(
        prior=PriorConfig(**arch["prior"]),
        decoder=DecoderConfig(unet=UNetConfig(**unet), **dec),
        movq=MOVQConfig(**movq), text=TextEncoderConfig(**arch["text"]),
        prior_steps=arch["prior_steps"])
    tk = arch["tokenizer"]
    tok = ByteTokenizer(max_length=arch["text"]["max_length"],
                        bos_id=tk["bos_id"], eos_id=tk["eos_id"])
    return (Kandinsky2Pipeline(cfg, tokenizer=tok, precision=precision),
            Kandinsky2Runner)


def kernel_calls(attn_calls):
    """The reference's attention calls that the program serves with its
    flash kernel: every unmasked call of 1024 query rows or more, the
    rule `ops.flash.attention` itself applies to `AttnAddedKV` and to
    `models.common.Attention` alike — the decoder's added-KV attention
    at 2304 queries x 2314 keys (10 context tokens + the spatial tokens)
    and MOVQ's mid-block self-attention. The decoder's lower levels (576
    and 144 rows) and the prior (81 tokens, masked) are einsum in the
    program."""
    return [c for c in attn_calls if c[2] >= 1024]
