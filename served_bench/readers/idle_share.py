"""Device: share of the timed window in which no operation ran, in %.

The trace gives the device's busy seconds per launch cycle, over whole cycles
(served_bench/tracing.py); the sidecar's launch counter, read at the window's
first and last instant, gives the launch cycles per second of the WHOLE timed
window.  Their product is the window's busy share.  The trace holds only a
handful of launches, and whether it caught a decision's two launches or the
pause between decisions moves its own share by some ten points; the launch
count over the window does not move.  (``device.busy_s`` / ``window_s`` on the
result line stay the trace's own, over its whole cycles.)  Where the counters
or whole cycles are missing, the trace's own share."""

from served_bench.readers import delta


def read(ctx):
    trace = ctx["trace"]
    if not trace or not trace.get("busy_s") or not trace.get("window_s"):
        return None
    launches, seconds = delta(ctx, "launches_after_ready"), ctx.get("counted_s")
    if trace.get("cycles") and launches and seconds:
        busy_share = trace["busy_s"] / trace["cycles"] * launches / seconds
    else:
        busy_share = trace["busy_s"] / trace["window_s"]
    return 100.0 * (1.0 - busy_share)
