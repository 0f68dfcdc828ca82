"""Whole runs of the harness on the CPU at a tiny size: each kind of cell
comes out correct, and the command itself refuses to run off a TPU.
`tiny` and `run` are shared with test_bench_control.py."""

import os
import pathlib
import subprocess
import sys
import time

import pytest

from bench import harness, registry

REPO = pathlib.Path(__file__).resolve().parents[2]
TRAFFIC = {"pool_requests": 512, "warmup_seconds": 0.2, "check_batches": 4}


def tiny(cell: str) -> dict:
    cfg = {"rows": 4096, "dim": 16, "c_f_sample": 64, "h": 64,
           "knee_rps": 3000}
    if "ivf" in cell:
        cfg["index"] = {"backend": "ivf", "nlist": 16, "nprobe": 4}
    return cfg


def bench() -> dict:
    """BENCHMARK.json, plus the cells kept out of it for now (PERF.md, Open
    questions): IVF cells over bench/configs/sift1m-ivf.json reporting
    what the flat backlog cell reports, and an open-loop cell reporting
    the latency tails."""
    b = registry.load_benchmark()
    extra = [{"name": f"sift1m-ivf.{t}", "config": "sift1m-ivf",
              "traffic": t, "chips": 1, "why": "IVF path"}
             for t in ("backlog", "poisson80")]
    b["workloads"] += extra
    for m in b["end_to_end"] + b["per_layer"]:
        if "gist1m-flat.backlog" in m.get("workloads", ()):
            m["workloads"].append("sift1m-ivf.backlog")
    b["end_to_end"] += [{"name": n, "unit": "ms", "better": "lower",
                         "bound": 0.25, "source": "host_clock",
                         "workloads": ["sift1m-ivf.poisson80"]}
                        for n in ("latency_p50_ms", "latency_p95_ms")]
    return b


def run(cell: str, **kw) -> dict:
    return harness.run(bench(), cell, 2**31 + 99, 0.5, False,
                       time.perf_counter(), cfg_override=tiny(cell),
                       traffic_override=TRAFFIC, **kw)


@pytest.mark.parametrize("cell", ["sift1m-ivf.backlog", "gist1m-flat.backlog",
                                  "sift1m-ivf.poisson80"])
def test_cell_runs_correct(cell):
    out = run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    e2e = {m["name"] for m in bench()["end_to_end"]
           if registry.applies(m, cell)}
    assert set(out["metrics"]) == e2e
    assert list(out)[-1] == "checks"
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def test_no_tpu_exits_before_work():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gist1m-flat.backlog",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_traced_run_leaves_no_profile(tmp_path, monkeypatch):
    """A `--trace 1` run reads its profile from a temporary directory and
    removes it: every traced run of every check would otherwise leave one
    on the machine's disk."""
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    # peaks.json knows only the chips; the CPU's profile names no kernel
    monkeypatch.setattr(harness, "_peaks", lambda kind: {})
    cell = "gist1m-flat.backlog"
    out = harness.run(bench(), cell, 2**31 + 5, 0.3, True,
                      time.perf_counter(), cfg_override=tiny(cell),
                      traffic_override=TRAFFIC)
    assert out["correct"], out["checks"]
    assert "host_dispatch_ms.backlog" in out["metrics"]
    assert not list(tmp_path.glob("bench_trace_*"))
