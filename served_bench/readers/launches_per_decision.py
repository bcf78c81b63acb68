"""Wave former: device launches per decision inside the window."""

from served_bench.readers import delta


def read(ctx):
    launches = delta(ctx, "launches_after_ready")
    if launches is None or not ctx["decisions"]:
        return None
    return launches / ctx["decisions"]
