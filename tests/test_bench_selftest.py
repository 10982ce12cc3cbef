"""The benchmark's own checks that need no chip, under tier-1: the
selftest's ``flops`` check (every work-count file against its class's
``_flops_per_step``) and ``benchmarks/selftest/test_compare.py``, whose
cases hold ``compare.py``, the file every cell's ``correct`` rests on.
Each case counts as a test of its own here; ``run_selftest.py`` still
runs them all (``PERF.md`` §7 (14))."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
SELFTEST = os.path.join(BENCH, "selftest")


def _load(path, name):
    """A file of the benchmark as a module, with the paths
    ``run_selftest.py`` gives it while it loads and runs."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    keep = list(sys.path)
    try:
        spec.loader.exec_module(module)
    finally:
        sys.path[:] = keep
    return module


@pytest.fixture()
def bench_path(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)


compare_cases = _load(os.path.join(SELFTEST, "test_compare.py"),
                      "bench_selftest_test_compare")


def test_the_selftests_flops_check(bench_path):
    """flops.py, flops_moe.py and flops_lfm2.py against the three LM
    classes' own arithmetic, at every configuration that names each."""
    selftest = _load(os.path.join(SELFTEST, "run_selftest.py"),
                     "bench_selftest_run")
    selftest.check_flops()
    flops_lfm2 = _load(os.path.join(BENCH, "flops_lfm2.py"),
                       "bench_selftest_flops_lfm2")
    assert flops_lfm2._self_check() == 0


@pytest.mark.parametrize("case", compare_cases.CASES,
                         ids=lambda c: c.__name__[5:])
def test_compare_py_on_made_trees(case):
    case()


def test_a_precision_step_fails_by_the_update(bench_path):
    compare_cases.test_a_precision_step_fails_by_the_update()
