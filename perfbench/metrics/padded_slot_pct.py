"""Node loop: batch slots dispatched that held a repeated task, over all
slots dispatched. `solve.dispatch` spans (staged path) carry n real of
batch slots; `solve.infer` spans (serial path) carry n items chunked at
batch. Source: the program's obs journal; a count."""
from perfbench.spans import named


def read(run):
    slots = real = 0
    for s in named(run.spans, "solve.dispatch"):
        slots += s["attrs"]["batch"]
        real += s["attrs"]["n"]
    for s in named(run.spans, "solve.infer"):
        n, b = s["attrs"]["n"], max(1, s["attrs"]["batch"])
        slots += -(-n // b) * b
        real += n
    if not slots:
        return None
    return 100.0 * (slots - real) / slots
