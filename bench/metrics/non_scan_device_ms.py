"""Device busy milliseconds per step outside the configuration's remote
scan kernel and the catalog's relayout copies (`catalog_relayout_ms`):
candidate merge, gain and subgradient, OMA with the projection,
rounding."""
from bench.metricslib import catalog_copy_ns, kernel_ns


def read(ctx):
    scan = kernel_ns(ctx, ctx["config"]["scan_kernel"])
    if scan is None:
        return None
    copies = catalog_copy_ns(ctx) or (0.0, 0)
    busy = ctx["trace"]["busy_ns"] - scan[0] - copies[0]
    return busy / 1e6 / ctx["batches"]
