"""95th percentile over the window's batches of how late the load
generator dispatched a batch against the time its last request fell due."""
from bench.metricslib import percentile


def read(ctx):
    return percentile(ctx["host"].get("gen_lag_ms", []), 95)
