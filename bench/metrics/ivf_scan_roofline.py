"""Percent of its roofline one call of the ivf_scan kernel reaches."""
from bench.metricslib import roofline


def read(ctx):
    return roofline(ctx, "ivf_scan")
