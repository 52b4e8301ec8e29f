"""Cache: the bytes the bucket programs' carries hold — a latent and an
indexer key a position a layer — over what per-head K and V rows of
every published head would take. `text.bucket` spans (the text runner's
dispatch) carry `cache_bytes` and `cache_bytes_per_head` for one
sequence, and `batch`. Source: the program's obs journal; a count. Reads
100 if a change expands the cache per head. A program without such
spans returns nothing."""
from perfbench.spans import named


def read(run):
    held = full = 0
    for s in named(run.spans, "text.bucket"):
        a = s["attrs"]
        if "cache_bytes" not in a or "cache_bytes_per_head" not in a:
            continue
        held += a["cache_bytes"] * a.get("batch", 1)
        full += a["cache_bytes_per_head"] * a.get("batch", 1)
    if not full:
        return None
    return 100.0 * held / full
