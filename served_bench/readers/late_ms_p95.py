"""Load generator: 95th percentile of how late a request was sent, in ms."""

from served_bench.poll import percentile


def read(ctx):
    if not ctx["late_s"]:
        return None
    return 1000.0 * max(0.0, percentile(ctx["late_s"], 95))
