"""Model step: the matmul FLOPs the plain reference needs for the real
(unpadded) solutions finished in the traced window, over window seconds x
the chip's bf16 peak. FLOPs from perfbench/flops.py, peak from
perfbench/peaks.py. It bounds every kernel's share from above: padding,
idle time and recomputation all lower it."""


def read(run):
    if run.peaks is None or not run.tasks or run.trace is None:
        return None
    need = sum(run.flops_per_solution[t["model"]] for t in run.tasks)
    return 100.0 * need / (run.trace["window_s"] * run.peaks["bf16_flops"])
