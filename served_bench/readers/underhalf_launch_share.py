"""Wave former: share of flushes that filled at most half of the launch
shape, in %: launches two of which would fit one."""

from served_bench.readers import flusher


def read(ctx):
    underhalf = flusher.delta(ctx, "fill_le_25", "fill_le_50")
    flushes = flusher.delta(ctx, "flushes")
    if underhalf is None or not flushes:
        return None
    return 100.0 * underhalf / flushes
