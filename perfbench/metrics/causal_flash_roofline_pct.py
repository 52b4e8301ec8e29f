"""Kernels: over the causal / sliding-window flash kernel's calls in the
traced window, the least time the chip could take for the masked
attention they compute (the larger of FLOPs / bf16 peak and bytes / HBM
bandwidth, the FLOPs over the (query, key) pairs the mask leaves) over
the calls' device time in the trace.

Which of the reference's masked calls the program serves with the kernel
is the family's `causal_kernel_calls` (perfbench/families): prefill's,
one a layer at the canonical batch; how many bucket programs ran is the
count of `bench.dispatch` spans; the kernel's events are found by
KERNEL_PATTERN. FLOPs bind from the kernel's first length on (2,048
positions: 4.2 ms against 2.0 ms of bytes a bucket's layer; at 8,192,
50-67 ms against 7.9), though the bytes are counted with the keys and
values of every query head, as the reference repeats them, which is more
than a kernel for grouped heads has to read. A family without the
function, a trace without such events: nothing."""
from perfbench import flops
from perfbench.trace_reduce import kernel_roofline_pct

# the Mosaic call's own name (`ops.causal_flash`); in the trace that name
# and an instance number, which `kernel_events` drops
KERNEL_PATTERN = r"^causal_flash_attention$"


def read(run):
    def bucket_floor_s(m, parts):
        served = getattr(m.family, "causal_kernel_calls", None)
        task = run.first_task.get(m.template)
        if served is None or task is None:
            return 0.0
        return sum(
            part["calls"] * flops.attention_floor_seconds(
                *call[:5], run.peaks, pairs=call[5])[0]
            for part in parts.values()
            for call in served(part["masked_attn_calls"], m.arch, task))

    return kernel_roofline_pct(run, KERNEL_PATTERN, bucket_floor_s)
