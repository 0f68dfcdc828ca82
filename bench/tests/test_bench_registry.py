"""BENCHMARK.json against the benchmark's contract, and parts found by
name: a new configuration, traffic mix or metric reader is picked up from
its own file with no edit to any file that is there."""

import json
import pathlib
import re

import pytest

from bench import harness, registry

REPO = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return registry.load_benchmark()


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_names_units_and_keys(bench):
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert (REPO / c["file"]).is_file()
        names.add(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25


def test_every_cell_reports_what_it_must(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell = w["name"]
        mine = {n for n, m in e2e.items() if registry.applies(m, cell)}
        assert "setup_s" in mine and len(mine) >= 2, cell
        layer = [m for m in bench["per_layer"] if registry.applies(m, cell)]
        assert layer, cell
        for m in layer:
            assert m["moves"] in mine, (cell, m["name"])


def test_every_part_has_its_file(bench):
    reg = registry.Registry()
    for c in bench["configs"]:
        cfg = reg.json("configs", c["name"])
        reg.module("remote", cfg["index"]["backend"])
        reg.module("kernels", cfg["scan_kernel"])
        assert set(cfg["limits"]) >= set(harness.CHECKED)
    for m in bench["per_layer"]:
        assert callable(reg.module("metrics", m["name"]).read)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            reg.module("kernels", m["name"][:-len("_roofline")])


def test_new_parts_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a metric reader laid in their own
    files beside the benchmark's are found by their names."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    base = registry.Registry().json("configs", "sift1m-ivf")
    (tmp_path / "configs" / "deep1m-ivf.json").write_text(
        json.dumps({**base, "name": "deep1m-ivf", "dim": 96}))
    (tmp_path / "traffic" / "poisson50.json").write_text(json.dumps(
        {**registry.Registry().json("traffic", "poisson80"),
         "name": "poisson50", "rate": {"share_of_knee": 0.5}}))
    (tmp_path / "metrics" / "answer_hit_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    reg = registry.Registry(roots=[tmp_path])
    cfg = reg.json("configs", "deep1m-ivf")
    traffic = reg.json("traffic", "poisson50")
    assert cfg["dim"] == 96
    assert harness.traffic_rate(traffic, {**cfg, "knee_rps": 600}) == 300
    assert reg.module("metrics", "answer_hit_share").read({}) == 42.0
    # the benchmark's own parts are still found through the same registry
    assert reg.json("configs", "gist1m-flat")["dim"] == 960
    with pytest.raises(FileNotFoundError):
        reg.json("configs", "no-such-config")
