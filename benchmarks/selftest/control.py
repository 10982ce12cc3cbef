#!/usr/bin/env python3
"""The control and the planted faults, read by the numbers ``compare.py``
compares: the plain reference put in the program's place, once in the
next precision below the one the configuration states (float8 operands)
and once with a fault planted (half of the batch left out and the mean
taken over the rest), against the reference itself.

    python3 benchmarks/selftest/control.py --workload lm14-final \\
        --seeds 101 102 103 [--lr 2e-4]

On the chip at the cell's own size this gives the upper readings the
limits in the workload files were set from (PERF.md lists them); the
benchmark's own runs never run it. Each variant is held to the
workload's limits by ``compare.judge``, as ``drivers/train_job.py``
holds the program: its line says ``correct``, and this command exits 1
if any variant reads ``correct`` true (a control or a fault that the
cell's limits let through). ``run_selftest.py control`` runs the same
function at the toy size on the CPU. One JSON line per variant.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

VARIANTS = (("fp8", ""), ("f32", "half_batch"))


def readings(config: dict, job: dict, seed: int, learning_rate: float,
             variants=VARIANTS):
    """{variant: ``compare.trial_numbers``} of one trial of ``job``."""
    import numpy as np

    import compare
    from harness import load_module

    reference = load_module("reference", config["reference"])
    data = load_module("data", config["data"]["generator"])
    dims = reference.dims_of(config)
    knobs = dict(config["knobs"], **job["fixed"])
    per_dispatch = int(knobs["steps_per_dispatch"])
    ids, _ = data.streams(seed, vocab_size=dims["v"],
                          n_train=int(config["data"]["n_train"]), n_val=8,
                          branching=int(config["data"].get("branching", 4)))

    def trial(mode, fault):
        return reference.train(
            ids, seed, dims, config["recipe"],
            steps=int(knobs["train_steps"]), batch=int(knobs["batch_size"]),
            per_dispatch=per_dispatch, learning_rate=learning_rate,
            mode=mode, fault=fault, host_dtype=np.float32)

    first, final, losses = trial("f32", "")
    out = {}
    for mode, fault in variants:
        _, theirs, their_losses = trial(mode, fault)
        out[fault or mode] = compare.trial_numbers(
            compare.chunk_means(their_losses, per_dispatch), losses,
            per_dispatch, theirs, final, first, dims["layers"],
            **compare.kinds_of(reference))
    return out


def main(argv=None) -> int:
    import compare
    from harness import load_json

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--lr", type=float, nargs="*", default=[],
                        help="one per seed, where the job searches it")
    args = parser.parse_args(argv)
    workload, _ = load_json("workloads", args.workload)
    config, _ = load_json("configs", workload["config"])
    let_through = 0
    for i, seed in enumerate(args.seeds):
        lr = args.lr[i] if i < len(args.lr) \
            else float(workload["job"]["fixed"]["learning_rate"])
        for variant, numbers in readings(config, workload["job"],
                                         seed % 2147483647, lr).items():
            compared, correct = compare.judge(
                numbers, compare.limits_of(workload))
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "learning_rate": lr, "variant": variant,
                              "correct": correct, "compared": compared}),
                  flush=True)
            let_through += correct
    return 1 if let_through else 0


if __name__ == "__main__":
    sys.exit(main())
