"""Host prep: share of ``verify.prepare`` in which the flusher thread held no
core (the GIL taken by the request threads' decoding, the scheduler), in %."""

from served_bench.readers import flusher


def read(ctx):
    wall = flusher.delta(ctx, "verify.prepare")
    cpu = flusher.delta(ctx, "verify.prepare_cpu")
    if not wall or cpu is None:
        return None
    return 100.0 * (1.0 - cpu / wall)
