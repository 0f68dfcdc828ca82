"""Device milliseconds of the ivf_scan kernel per step."""
from bench.metricslib import kernel_ms


def read(ctx):
    return kernel_ms(ctx, "ivf_scan")
