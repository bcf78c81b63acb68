"""Wave former: share of launched lanes that carried no signature, in %."""

from served_bench.readers import delta


def read(ctx):
    signatures, lanes = delta(ctx, "device_signatures"), delta(ctx, "device_lanes")
    if not lanes or signatures is None:
        return None
    return 100.0 * (1.0 - signatures / lanes)
