"""Helpers the per-layer metric readers share.  A reader gets the run's
context dict: `trace` (the reduced trace of `bench/tracereduce.py`, or
None), `host` (the window's host timings), `shapes` (the sizes the kernel
counts read), `peaks` (the device's row of `bench/peaks.json`),
`batches` (steps in the window), `config`, `traffic`, `registry`.  A
reader returns None where it finds nothing to read."""

from __future__ import annotations

import sys

import numpy as np

from bench import tracereduce


def kernel_ns(ctx: dict, kernel: str):
    """(device ns, calls) of `kernel` in the traced window, or None."""
    if ctx["trace"] is None:
        return None
    mod = ctx["registry"].module("kernels", kernel)
    ns, calls = tracereduce.kernel_time(ctx["trace"], mod.MATCH)
    return (ns, calls) if calls > 0 else None


DTYPES = {"float32": "f32", "bfloat16": "bf16", "float16": "f16"}


def catalog_copy_ns(ctx: dict):
    """(device ns, calls) of the copies XLA adds to the step to change the
    layout of the whole catalog (an HLO `copy` whose result is the
    configuration's [rows, dim] array), or None."""
    if ctx["trace"] is None:
        return None
    cfg = ctx["config"]
    shape = rf"{DTYPES[cfg['dtype']]}\[{cfg['rows']},{cfg['dim']}\]"
    ns, calls = tracereduce.kernel_time(
        ctx["trace"], [rf"^%copy[\w.-]* = {shape}\S* copy\("])
    return (ns, calls) if calls > 0 else None


def kernel_ms(ctx: dict, kernel: str):
    """Device milliseconds of `kernel` per step of the window."""
    got = kernel_ns(ctx, kernel)
    return None if got is None else got[0] / 1e6 / ctx["batches"]


def roofline(ctx: dict, kernel: str):
    """Percent of its roofline a call of `kernel` reaches: the least time
    the chip needs for its operations (at the bf16 peak) or its bytes (at
    the HBM peak), whichever is larger, over its measured device time."""
    got = kernel_ns(ctx, kernel)
    if got is None or not ctx["shapes"]:
        return None
    ns, calls = got
    c = ctx["registry"].module("kernels", kernel).counts(ctx["shapes"])
    peak = ctx["peaks"]
    t_ops = c["flops"] / peak["bf16_flops_per_s"]
    t_bytes = c["bytes"] / peak["hbm_bytes_per_s"]
    t_call = ns / 1e9 / calls
    bound = "bytes" if t_bytes >= t_ops else "operations"
    print(f"{kernel} roofline: bound by {bound} (least {max(t_ops, t_bytes)*1e3:.4f} ms: "
          f"{c['flops']:.4g} ops, {c['bytes']:.4g} bytes; measured "
          f"{t_call * 1e3:.4f} ms per call over {calls:g} calls)",
          file=sys.stderr)
    return 100.0 * max(t_ops, t_bytes) / t_call


def percentile(values, q: float):
    values = np.asarray(values, np.float64)
    return None if values.size == 0 else float(np.percentile(values, q))
