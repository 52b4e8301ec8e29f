"""Cache: the bytes the bucket programs' carries hold — the sliding
layers' rings of latent rows and the full layers' latent and indexer
caches — over what they would hold with the sliding layers' latent rows
at full length. `text.bucket` spans (the text runner's dispatch) carry
`cache_bytes_window`, `cache_bytes_full` and `cache_bytes_window_full`
for one sequence, and `batch`. Source: the program's obs journal; a
count. Reads 100 if a change drops the ring. A program without such
spans returns nothing."""
from perfbench.spans import named

_KEYS = ("cache_bytes_window", "cache_bytes_full", "cache_bytes_window_full")


def read(run):
    held = whole = 0
    for s in named(run.spans, "text.bucket"):
        a = s["attrs"]
        if not all(k in a for k in _KEYS):
            continue
        b = a.get("batch", 1)
        held += (a["cache_bytes_window"] + a["cache_bytes_full"]) * b
        whole += (a["cache_bytes_window_full"] + a["cache_bytes_full"]) * b
    if not whole:
        return None
    return 100.0 * held / whole
