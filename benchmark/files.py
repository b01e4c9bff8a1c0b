"""Where the benchmark's files are, and how one is found by its name."""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.abspath(__file__))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """A traffic kind or a metric's reader: a Python file loaded from
    where it lies, whatever its name."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find(root: str, *parts: str) -> str:
    """A file of the benchmark by its place and name. `root` is searched
    first: a rehearsal or a test keeps its small deployments, their traffic
    and its own BENCHMARK.json in a directory of its own and shares the
    rest."""
    path = os.path.join(root, *parts)
    return path if os.path.exists(path) else os.path.join(ROOT, *parts)


def load_benchmark(root: str = ROOT) -> dict:
    """BENCHMARK.json, which lies beside the benchmark's directory. Another
    `root` may hold one of its own, whose lists (cells, configurations,
    metrics) are appended."""
    benchmark = load_json(os.path.join(os.path.dirname(ROOT), "BENCHMARK.json"))
    extra = os.path.join(root, "BENCHMARK.json")
    if root != ROOT and os.path.exists(extra):
        for key, entries in load_json(extra).items():
            if isinstance(entries, list):
                benchmark[key] = benchmark[key] + entries
    return benchmark
