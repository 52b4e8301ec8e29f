"""Kernels: over the banded flash kernel's calls in the traced window
(the sliding layers' prefill attention, `window_flash_attention` of
`ops/selected_flash.py`), the least time the chip could take for the
banded attention they compute — for each sliding layer of each sequence
the larger of the band's FLOPs at the bf16 peak and its bytes (query,
expanded K-nope | V, rotary key and output once each) at the HBM
bandwidth, the family's `window_kernel_floor_s` (the work is the
reference's `window_work`) — over the calls' device time in the trace.
One call a sliding layer a sequence, times the canonical batch, times
the `bench.dispatch` spans of the model. A family without the function,
a trace without such events: nothing."""
from perfbench.trace_reduce import kernel_roofline_pct

# the Mosaic call's own name; in the trace that name and an instance
# number, which `kernel_events` drops
KERNEL_PATTERN = r"^window_flash_attention$"


def read(run):
    batch = run.system.canonical_batch

    def bucket_floor_s(m, parts):
        floor = getattr(m.family, "window_kernel_floor_s", None)
        task = run.first_task.get(m.template)
        if floor is None or task is None:
            return 0.0
        return batch * floor(m.arch, task, run.peaks)

    return kernel_roofline_pct(run, KERNEL_PATTERN, bucket_floor_s)
