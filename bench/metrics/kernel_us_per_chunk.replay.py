"""Device time of the mapping program per chunk: the union of the device's
operation intervals in the traced window (the uid-resolve fusions and the
``densify_map`` kernel of ``ops.dmm_apply_columnar``; replay runs nothing
else on the device) over the number of ``densify_map`` launches, one per
chunk."""


def read(ctx):
    red = ctx.reduction
    if red is None or not red.kernel_events:
        return None
    return red.busy_ns / red.kernel_events / 1e3
