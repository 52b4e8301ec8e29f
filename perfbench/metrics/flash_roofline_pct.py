"""Kernels: over every flash-attention call in the traced window, the
least time the chip could take for the call's shapes (the larger of
FLOPs / bf16 peak and bytes / HBM bandwidth, for exact attention on the
unpadded [B,H,S,D]) over the call's device time in the trace.

Which attention calls the program serves with its kernel is the family's
`kernel_calls` (perfbench/families); how many bucket programs ran is the
count of `bench.dispatch` spans; the kernel's events are found by
KERNEL_PATTERN. A trace without such events returns nothing."""
from perfbench import flops
from perfbench.trace_reduce import kernel_roofline_pct

# the Mosaic call carries the name of the jitted function round it
# (`ops.flash.flash_attention`): in the trace it is that name and an
# instance number, which `kernel_events` drops as the breakdown does
KERNEL_PATTERN = r"^flash_attention$"


def read(run):
    def bucket_floor_s(m, parts):
        return sum(
            part["calls"] * flops.attention_floor_seconds(*call,
                                                          run.peaks)[0]
            for part in parts.values()
            for call in m.family.kernel_calls(part["attn_calls"]))

    return kernel_roofline_pct(run, KERNEL_PATTERN, bucket_floor_s)
