"""Operations and bytes of one call of the IVF list scan (gather + squared
L2 + top-k over the rows of the probed lists), as the algorithm needs
them for the B requests of a batch, whatever implements it: each probed
row is read once with its id, each element costs a difference, a square
and an add; the queries are read and k (distance, id) pairs written per
request."""

# the kernel's device op in a profiler trace: the custom call named after
# its jitted wrapper, `repro.kernels.ops.ivf_scan_topk`
MATCH = (r"^%ivf_scan_topk(\.\d+)? = .*tpu_custom_call",)


def counts(s: dict) -> dict:
    rows, d, b, k, item = (s["probed_rows"], s["dim"], s["batch"], s["k"],
                           s["itemsize"])
    return {"flops": 3 * rows * d,
            "bytes": rows * d * item + rows * 4 + b * d * item + b * k * 8}
