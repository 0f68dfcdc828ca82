"""Operations and bytes of one call of the fused exact scan (squared L2 +
top-k over the whole catalog) for the B requests of a batch, as the
algorithm needs them, whatever implements it: 2 B N d for the distance
products of the B real requests (not of a padded tile), the catalog read
once, the queries read and k (distance, id) pairs written per request."""

# the kernel's device op in a profiler trace: the custom call named after
# its jitted wrapper, `repro.kernels.ops.topk_l2`
MATCH = (r"^%topk_l2(\.\d+)? = .*tpu_custom_call",)


def counts(s: dict) -> dict:
    n, d, b, k, item = s["rows"], s["dim"], s["batch"], s["k"], s["itemsize"]
    return {"flops": 2 * b * n * d,
            "bytes": n * d * item + b * d * item + b * k * 8}
