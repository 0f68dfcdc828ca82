"""The reduction from a profiler trace to the per-layer metrics, on short
excerpts of traces recorded on a TPU v5e (`bench/testdata/`), and the
kernels' operation and byte counts against hand-computed shapes."""

import gzip
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import tracereduce
from bench.registry import Registry

DATA = pathlib.Path(__file__).resolve().parents[1] / "testdata"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def excerpt(cell):
    with gzip.open(DATA / f"{cell}.trace.json.gz", "rt") as f:
        return json.load(f)


def ctx(reduced, config, shapes, batches, host=None):
    return {"trace": reduced, "config": config, "shapes": shapes,
            "batches": batches, "peaks": V5E, "registry": Registry(),
            "host": host or {}}


def test_reduce_ivf_excerpt():
    r = tracereduce.reduce(excerpt("sift1m-ivf.backlog"))
    assert r["window_ns"] == 600_000_000 and r["device_count"] == 1
    assert r["busy_ns"] == pytest.approx(583_388_203)
    ivf = Registry().module("kernels", "ivf_scan").MATCH
    flat = Registry().module("kernels", "l2_topk").MATCH
    assert tracereduce.kernel_time(r, ivf) == (579_664_554, 6)
    assert tracereduce.kernel_time(r, flat) == (0, 0)
    # the device waits only while the host is inside serve_update_batch
    assert [g[0] for g in r["idle_gaps"]] == ["bench.dispatch"]
    assert r["idle_gaps"][0][1] == pytest.approx(0.016611797)
    assert r["device_ops"][0][0].startswith("%ivf_scan_topk")
    assert len(r["device_ops"]) <= 10


def test_reduce_flat_excerpt():
    r = tracereduce.reduce(excerpt("gist1m-flat.backlog"))
    flat = Registry().module("kernels", "l2_topk").MATCH
    assert tracereduce.kernel_time(r, flat) == (314_444_910, 7)
    assert r["busy_ns"] == pytest.approx(576_983_657)


def test_catalog_relayout_readers_on_the_flat_excerpt():
    """The two copies of the 1M x 960 catalog into row-major layout are
    read apart from the scan kernel and from the rest of the step."""
    reg = Registry()
    r = tracereduce.reduce(excerpt("gist1m-flat.backlog"))
    cfg = {"scan_kernel": "l2_topk", "dtype": "float32", "rows": 1_000_000,
           "dim": 960}
    c = ctx(r, cfg, {}, batches=7)
    read = lambda name: reg.module("metrics", name).read(c)
    copies = 97_572_262 + 85_389_715
    assert read("catalog_relayout_ms") == pytest.approx(copies / 1e6 / 7)
    assert read("non_scan_device_ms") == pytest.approx(
        (576_983_657 - 314_444_910 - copies) / 1e6 / 7)
    # a catalog of another shape has no such copy here
    c["config"] = {**cfg, "dim": 128}
    assert read("catalog_relayout_ms") is None


def test_metric_readers_on_the_excerpt():
    reg = Registry()
    r = tracereduce.reduce(excerpt("sift1m-ivf.backlog"))
    shapes = {"batch": 64, "rows": 1_000_000, "dim": 128, "k": 64,
              "itemsize": 4, "probed_rows": 1_000_000}
    c = ctx(r, {"scan_kernel": "ivf_scan", "dtype": "float32",
                "rows": 1_000_000, "dim": 128}, shapes, batches=6)
    read = lambda name: reg.module("metrics", name).read(c)
    assert read("ivf_scan.ms") == pytest.approx(579.664554 / 6)
    # bytes bound: (1e6 rows x 128 x 4 + 1e6 ids x 4 + 64 x 128 x 4
    # + 64 x 64 x 8) / 819e9 over the measured time per call
    least = (512e6 + 4e6 + 32768 + 32768) / 819e9
    assert read("ivf_scan_roofline") == pytest.approx(
        100 * least / (0.579664554 / 6))
    assert read("non_scan_device_ms") == pytest.approx(
        (583.388203 - 579.664554) / 6)
    assert read("device_idle_share.backlog") == pytest.approx(
        100 * (1 - 583.388203 / 600))
    assert read("l2_topk.ms") is None and read("l2_topk_roofline") is None


def test_host_clock_readers():
    reg = Registry()
    host = {"dispatch_ms": [1.0, 2.0, 9.0], "queue_wait_ms": np.arange(101.0),
            "gen_lag_ms": np.arange(21.0)}
    c = ctx(None, {}, {}, 3, host)
    read = lambda name: reg.module("metrics", name).read(c)
    assert read("host_dispatch_ms.backlog") == 2.0
    assert read("queue_wait_p95_ms") == pytest.approx(95.0)
    assert read("gen_lag_p95_ms") == pytest.approx(19.0)
    assert read("ivf_scan.ms") is None
    assert read("device_idle_share.backlog") is None


def test_l2_topk_counts():
    c = Registry().module("kernels", "l2_topk").counts(
        {"rows": 1_000_000, "dim": 960, "batch": 64, "k": 64, "itemsize": 4})
    assert c["flops"] == 2 * 64 * 1_000_000 * 960 == 122_880_000_000
    assert c["bytes"] == 3_840_000_000 + 245_760 + 32_768


def test_ivf_scan_counts():
    c = Registry().module("kernels", "ivf_scan").counts(
        {"probed_rows": 1_100_000, "dim": 128, "batch": 64, "k": 64,
         "itemsize": 4})
    assert c["flops"] == 3 * 1_100_000 * 128
    assert c["bytes"] == 1_100_000 * 516 + 64 * 512 + 64 * 64 * 8


def test_load_reads_host_spans(tmp_path):
    """A trace recorded here (no accelerator): the benchmark's host spans
    are found, there is no device plane, and the reduction gives nothing
    rather than a zero."""
    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    ev = tracereduce.load(path)
    names = [h[0] for h in ev["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 3
    assert ev["device"] == {}
    assert tracereduce.reduce(ev) is None
