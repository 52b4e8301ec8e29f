"""Cache: the KV rows the bucket programs' carries hold, over what they
would hold with every layer at full length (prompt edge + decode edge).
`text.bucket` spans (the text runner's dispatch) carry `kv_rows` and
`kv_rows_full` for one sequence, and `batch`. Source: the program's obs
journal; a count. A program without such spans returns nothing."""
from perfbench.spans import named


def read(run):
    held = full = 0
    for s in named(run.spans, "text.bucket"):
        a = s["attrs"]
        if "kv_rows" not in a or "kv_rows_full" not in a:
            continue
        held += a["kv_rows"] * a.get("batch", 1)
        full += a["kv_rows_full"] * a.get("batch", 1)
    if not full:
        return None
    return 100.0 * held / full
