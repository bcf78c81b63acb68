"""Wave former: mean time a submission waited in the coalescer's queue, from
``_enqueue`` to the ``_take_batch`` that took it, in ms."""

from served_bench.readers import flusher


def read(ctx):
    waited = flusher.delta(ctx, "queue_wait_ns")
    submissions = flusher.delta(ctx, "submissions")
    if waited is None or not submissions:
        return None
    return waited / submissions / 1e6
