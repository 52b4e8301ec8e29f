"""Kernels: over every flash-attention call in the traced window, the
least time the chip could take for the call's shapes (the larger of
FLOPs / bf16 peak and bytes / HBM bandwidth, for exact attention on the
unpadded [B,H,S,D]) over the call's device time in the trace.

Which attention calls the program serves with its kernel is the family's
`kernel_calls` (perfbench/families); how many bucket programs ran is the
count of `bench.dispatch` spans; the kernel's events are found by
KERNEL_PATTERN. A trace without such events returns nothing."""
from perfbench import flops
from perfbench.spans import named
from perfbench.trace_reduce import kernel_events

# the Mosaic call carries the name of the jitted function round it
# (`ops.flash.flash_attention`): in the trace it is that name and an
# instance number, which `kernel_events` drops as the breakdown does
KERNEL_PATTERN = r"^flash_attention$"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    events = kernel_events(run.trace["events"], KERNEL_PATTERN)
    if not events:
        return None
    spent = sum(d for _, _, d in events)
    batch = run.system.canonical_batch
    per_model = {}
    for m in run.system.models:
        parts = run.parts.get(m.template, {}).get(batch)
        if parts is None:
            continue
        floor = 0.0
        for part in parts.values():
            for call in m.family.kernel_calls(part["attn_calls"]):
                floor += part["calls"] * flops.attention_floor_seconds(
                    *call, run.peaks)[0]
        per_model[m.template] = floor
    floor = sum(per_model.get(s["attrs"].get("model"), 0.0)
                for s in named(run.spans, "bench.dispatch"))
    if not floor or not spent:
        return None
    return 100.0 * floor / spent
