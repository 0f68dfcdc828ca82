"""Device milliseconds per step of the copies XLA adds to change the
layout of the whole catalog, each as large as the catalog: on the TPU a
float32 [1000000, 960] catalog is kept column-major, and the step copies
it row-major for the scan kernel and for the gather of cached rows."""
from bench.metricslib import catalog_copy_ns


def read(ctx):
    got = catalog_copy_ns(ctx)
    return None if got is None else got[0] / 1e6 / ctx["batches"]
