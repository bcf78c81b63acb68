"""Wave former: share of the timed window that the flusher thread spent with
nothing pending (``wave.wait_work``), in %."""

from served_bench.readers import flusher


def read(ctx):
    waited, seconds = flusher.delta(ctx, "wave.wait_work"), ctx.get("counted_s")
    if waited is None or not seconds:
        return None
    return 100.0 * waited / (seconds * 1e9)
