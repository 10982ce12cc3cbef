#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data. ``--workload`` names a file under
``workloads/``; it names its configuration (``configs/``), its driver
(``drivers/``) and the job a user submits. The metrics a run reports are
the entries of ``BENCHMARK.json`` that list the workload: end-to-end
metrics with ``--trace 0``, per-layer metrics with ``--trace 1``, each
read by ``metrics/<reader>.py:read(run)`` from what the driver recorded
on the host's clock and from the reduced profiler trace. The reader is
the last dot-separated part of the metric's name: one quantity that
cells of different kinds report under different end-to-end metrics
(``trials_per_hour``, ``search.trials_per_hour``) is split by a prefix
and read by one file. This file and the driver name no cell, no
configuration and no metric.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``compared``: every number the comparison
with the plain reference looked at, beside its limit. The same numbers
are the last lines of standard error.

A run that finds no TPU, an unknown device kind or fewer chips than the
cell asks for exits non-zero and prints no result. Only workloads kept
under ``selftest/`` may run on the CPU, and they report no device
metric.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))  # yardstick

from harness import ROOT, load_json, load_module  # noqa: E402

sys.path.insert(1, ROOT)  # the system under test


def metric_entries(workload: str, traced: bool, selftest: bool):
    """The metrics this run owes, from BENCHMARK.json: an entry without
    a ``workloads`` key is owed by every cell that reports what it
    moves. A selftest workload is no cell: it owes the end-to-end
    metrics of every cell, so that the whole command is exercised."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m["name"] for m in bench["end_to_end"]
            if selftest or workload in m.get("workloads", [workload])]
    if not traced:
        return [m for m in bench["end_to_end"] if m["name"] in mine]
    return [m for m in bench["per_layer"] if m["moves"] in mine
            and (selftest or workload in m.get("workloads", [workload]))]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    workload, selftest = load_json("workloads", args.workload)
    config, _ = load_json("configs", workload["config"])
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        # The compile cache sits inside this checkout, whatever the
        # machine's environment says: two checkouts share no program.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
            ROOT, ".jax_cache")
    elif not selftest:
        raise SystemExit("a measurement run needs the TPU: only "
                         "benchmarks/selftest/ workloads run on the CPU")
    driver = load_module("drivers", workload["driver"])

    run = driver.run(args=args, workload=workload, config=config,
                     t_start=T_START, selftest=selftest)

    metrics = {}
    if not (args.trace and run["device"]["platform"] == "cpu"):
        for entry in metric_entries(args.workload, bool(args.trace),
                                    selftest):
            reader = entry["name"].rsplit(".", 1)[-1]
            value = load_module("metrics", reader).read(run)
            if value is not None:  # a reader with nothing to read
                metrics[entry["name"]] = {"value": value,
                                          "unit": entry["unit"]}

    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "metrics": metrics,
              "device": run["device"]}
    if args.trace and run.get("breakdown"):
        result["breakdown"] = run["breakdown"]
    result["compared"] = run["compared"]
    sys.stdout.flush()
    for name, pair in run["compared"].items():
        print(f"compared {name}: {pair['value']!r} limit {pair['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
