"""Percent of its roofline one call of the l2_topk kernel reaches."""
from bench.metricslib import roofline


def read(ctx):
    return roofline(ctx, "l2_topk")
