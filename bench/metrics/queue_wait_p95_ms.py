"""95th percentile over the window's requests of the time from a request's
due time to the dispatch of its batch: the wait for the batch to fill."""
from bench.metricslib import percentile


def read(ctx):
    return percentile(ctx["host"].get("queue_wait_ms", []), 95)
