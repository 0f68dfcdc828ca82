"""Median host milliseconds of one call of the serving entry point
`AcaiCache.serve_update_batch`.  The call returns when the host has
read back the batch's check for finite queries, so while the device is
busy with the step before it, the call lasts about one step."""
from bench.metricslib import percentile


def read(ctx):
    return percentile(ctx["host"].get("dispatch_ms", []), 50)
