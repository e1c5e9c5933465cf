"""Share of its roofline the ``densify_map`` kernel reaches: the bytes the
mapping of the window's chunks needs (``bench/lib/kernel_bytes.py``) over
the chip's HBM bandwidth, over the kernel's device time.  The mapping does
no arithmetic, so bandwidth bounds it.  Nothing is returned where the trace
holds no kernel event."""

from bench.lib.peaks import peaks


def read(ctx):
    red = ctx.reduction
    if red is None or not red.kernel_ns or not ctx.kernel_bytes:
        return None
    least_s = ctx.kernel_bytes / peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return least_s / (red.kernel_ns / 1e9) * 100.0
