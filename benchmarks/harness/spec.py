"""BENCHMARK.json and the files it names: cells, configurations, traffic mixes,
drivers and per-layer readers, all found by name.  Nothing here knows the name
of any cell, so a later PR adds a cell by adding files and entries only."""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """benchmarks/<kind>/<name>.py as a module (kind: drivers, layer_metrics)."""
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    def __init__(self, root: str = ROOT, bench_dir: str = BENCH_DIR):
        self.root = root
        self.bench_dir = bench_dir
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        for c in self.doc["workloads"]:
            if c["name"] == name:
                return c
        known = ", ".join(c["name"] for c in self.doc["workloads"])
        raise SpecError(f"no workload {name!r} in BENCHMARK.json (have: {known})")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise SpecError(f"no config {name!r} in BENCHMARK.json")

    def mix(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench_dir, "traffic", f"{name}.json"))

    def metrics(self, kind: str, cell_name: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports."""
        return [m for m in self.doc[kind]
                if "workloads" not in m or cell_name in m["workloads"]]
