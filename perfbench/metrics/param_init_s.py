"""Set-up: seconds until every model's parameters are on the device
(layout by eval_shape, then the benchmark's one jitted weight program per
model)."""


def read(run):
    return run.timings.get("param_init_s")
