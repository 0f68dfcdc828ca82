"""Finds the benchmark's parts by name: configurations and traffic mixes
are JSON files, remote-scan references, per-layer metric readers and
kernel counts are Python files, each in a directory of its own:

    bench/configs/<config>.json       one deployment
    bench/traffic/<mix>.json          one traffic mix
    bench/remote/<backend>.py         the reference scan of an index backend
    bench/metrics/<metric>.py         the reader of one per-layer metric
    bench/kernels/<kernel>.py         operations and bytes of one kernel

A part is added by adding its file; no other file names it.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent
KINDS = ("configs", "traffic", "remote", "metrics", "kernels")


class Registry:
    """Looks a part up in each of `roots` in turn (the benchmark's own
    directory last), so that a caller can lay extra parts beside it."""

    def __init__(self, roots=()):
        self.roots = [pathlib.Path(r) for r in roots] + [BENCH]
        self._modules: dict = {}

    def path(self, kind: str, name: str, suffix: str) -> pathlib.Path:
        assert kind in KINDS, kind
        for root in self.roots:
            p = root / kind / f"{name}{suffix}"
            if p.is_file():
                return p
        raise FileNotFoundError(f"no {kind} file named {name}{suffix}")

    def json(self, kind: str, name: str) -> dict:
        return json.loads(self.path(kind, name, ".json").read_text())

    def module(self, kind: str, name: str):
        path = self.path(kind, name, ".py")
        mod = self._modules.get(path)
        if mod is None:
            tag = "".join(c if c.isalnum() else "_" for c in name)
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{tag}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return mod


def load_benchmark(path=REPO / "BENCHMARK.json") -> dict:
    return json.loads(pathlib.Path(path).read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    """Whether a metric of BENCHMARK.json is reported in `cell`."""
    return "workloads" not in metric or cell in metric["workloads"]
