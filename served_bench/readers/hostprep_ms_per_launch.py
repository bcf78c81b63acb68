"""Host prep: time the flusher thread spent preparing one launch
(``verify.prepare`` + ``verify.layout``), in ms."""

from served_bench.readers import flusher


def read(ctx):
    prep = flusher.delta(ctx, "verify.prepare", "verify.layout")
    flushes = flusher.delta(ctx, "flushes")
    if prep is None or not flushes:
        return None
    return prep / flushes / 1e6
