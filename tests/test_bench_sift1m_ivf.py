"""`sift1m-ivf.backlog` as BENCHMARK.json itself defines it, run whole on
the CPU at the tiny size of `bench/tests/test_bench_run.py`: the IVF lists
pass the check (`ivf_lists_bad` 0) with the k-means assignment spread over
several chunks, and the run reports exactly the cell's end-to-end
metrics."""

import importlib
import time

import jax

from bench import harness, registry
from bench.tests.test_bench_run import TRAFFIC, tiny

CELL = "sift1m-ivf.backlog"


def test_sift1m_ivf_backlog_runs_correct(monkeypatch):
    kmeans = importlib.import_module("repro.index.kmeans")
    monkeypatch.setattr(kmeans, "CHUNK", 1000)   # 4096 rows: five chunks
    jax.clear_caches()
    bench = registry.load_benchmark()
    assert registry.find_cell(bench, CELL)["config"] == "sift1m-ivf"
    try:
        out = harness.run(bench, CELL, 2**31 + 17, 0.5, False,
                          time.perf_counter(), cfg_override=tiny(CELL),
                          traffic_override=TRAFFIC)
    finally:
        jax.clear_caches()
    assert out["correct"], out["checks"]
    assert out["checks"]["ivf_lists_bad"] == {"value": 0, "limit": 0}
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = {m["name"] for m in bench["end_to_end"]
           if registry.applies(m, CELL)}
    assert e2e == {"served_rps", "setup_s"}
    assert set(out["metrics"]) == e2e
