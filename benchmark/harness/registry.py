"""Everything of one configuration, traffic mix, generator or
per-layer metric lives in a file of its own, found by its name."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _path(*parts: str) -> str:
    path = os.path.join(BENCH, *parts)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark file not found: {path}")
    return path


def load_json(*parts: str) -> dict:
    with open(_path(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(name: str) -> dict:
    return load_json("configs", f"{name}.json")


def traffic(name: str) -> dict:
    return load_json("traffic", f"{name}.json")


def peaks() -> dict:
    return load_json("peaks.json")


def _module(kind: str, name: str):
    path = _path(kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generator(name: str):
    """`benchmark/gen/<name>.py`, exporting make_yaml()."""
    return _module("gen", name)


def reference(name: str):
    """`benchmark/reference/<name>.py`, exporting compare()."""
    return _module("reference", name)


def metric_reader(name: str):
    """`benchmark/metrics/<name>.py`'s read(ctx) -> float | None."""
    return _module("metrics", name).read
