"""Kernels: the verify kernel's share of its roofline, in %.

The least time the chip could take for the signatures the traced launches
really carried (useful signatures x operations per signature over the peak
that bounds it, served_bench/peaks.py) over the kernel's device time in the
trace.  Useful signatures per launch are the window's own mean (the counters
cannot be read at the trace's edges); the launches are the trace's."""

from served_bench import peaks
from served_bench.readers import delta


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("kernel_s") or not trace.get("kernel_launches"):
        return None
    signatures, launches = (delta(ctx, "device_signatures"),
                            delta(ctx, "launches_after_ready"))
    if not signatures or not launches:
        return None
    useful = trace["kernel_launches"] * signatures / launches
    least, _bound = peaks.least_seconds(useful, ctx["device_kind"])
    return 100.0 * least / trace["kernel_s"]
