"""Wave former: share of the window's device signatures that rode a launch at
least half the full width wide (the ladder's upper rungs), in %."""

from served_bench.readers import delta


def read(ctx):
    first = ctx["first"].get("signatures_by_lanes")
    last = ctx["last"].get("signatures_by_lanes")
    signatures, lanes = delta(ctx, "device_signatures"), ctx.get("lanes")
    if first is None or last is None or not signatures or not lanes:
        return None
    upper = sum(n - first.get(width, 0) for width, n in last.items()
                if int(width) >= lanes // 2)
    return 100.0 * upper / signatures
