"""Ordering: committed requests per decision inside the window."""


def read(ctx):
    if not ctx["decisions"]:
        return None
    return ctx["requests"] / ctx["decisions"]
