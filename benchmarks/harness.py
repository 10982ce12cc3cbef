"""What ``run.py`` and the drivers share: finding the benchmark's files
by the names that ``BENCHMARK.json`` and the workload files give."""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(kind: str, name: str):
    """``<kind>/<name>.json`` of the benchmark, else of its selftest.
    Returns (data, whether it is a selftest file)."""
    for base, selftest in ((HERE, False),
                           (os.path.join(HERE, "selftest"), True)):
        path = os.path.join(base, kind, name + ".json")
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f), selftest
    raise SystemExit(f"no {kind}/{name}.json under benchmarks/")


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` as a module of its own."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"no {kind}/{name}.py under benchmarks/")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read_text(kind: str, name: str) -> str:
    with open(os.path.join(HERE, kind, name)) as f:
        return f.read()
