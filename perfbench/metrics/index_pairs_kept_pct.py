"""Sparse attention: of the (query, key) pairs the causal mask leaves to
the bucket programs' attention — prompt rows and decode steps, every
layer — the share the indexer's selection keeps for the softmax.
`text.bucket` spans carry `attn_pairs` and `attn_pairs_causal` for one
sequence, and `batch`. Source: the program's obs journal; a count. Reads
100 if a change drops the selection. A program without such spans
returns nothing."""
from perfbench.spans import named


def read(run):
    kept = causal = 0
    for s in named(run.spans, "text.bucket"):
        a = s["attrs"]
        if "attn_pairs" not in a or "attn_pairs_causal" not in a:
            continue
        kept += a["attn_pairs"] * a.get("batch", 1)
        causal += a["attn_pairs_causal"] * a.get("batch", 1)
    if not causal:
        return None
    return 100.0 * kept / causal
