"""Device milliseconds of the l2_topk kernel per step."""
from bench.metricslib import kernel_ms


def read(ctx):
    return kernel_ms(ctx, "l2_topk")
