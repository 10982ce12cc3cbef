#!/usr/bin/env python3
"""The control and the planted fault for the sparse-expert reference
(``reference/joyai_flash.py``), read by the numbers ``compare.py``
compares: the plain reference put in the program's place, once in the
next precision below the one the configuration states (float8 operands;
the router stays float32, as in the program) and once with a fault
planted (the second half of every window's positions left out of both
losses: the micro-batch is one row, so ``control.py``'s half batch would
be empty), against the reference itself.

    python3 benchmarks/selftest/control_joyai.py \\
        --workload joyai-flash-final --seeds 101 102

On the chip at the cell's own size this gives the upper readings the
limits in the workload file were set from (PERF.md lists them). Each
variant is held to the workload's own limits by ``compare.judge``, as
``drivers/train_job.py`` holds the program: its line says
``correct``, and this command exits 1 if any variant reads ``correct``
true (a control or a fault that the cell's limits let through). It does
what ``control.py`` does, one trial a PROCESS: at 491 M parameters a
second trial of the reference in the same process met the machine's 40
GiB (chip runs, PR 29). So this process, which never touches jax,
starts one child per trial; the reference's own trial leaves its
parameters and losses under ``.bench_work/`` and each variant's child
reads them back for ``compare.py``. One JSON line per variant; each
child logs its host peak. The children keep their compiled steps in the
checkout's ``.jax_cache``, as ``run.py`` does: the reference's step
compiles once for all seeds.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

VARIANTS = (("fp8", ""), ("f32", "half_batch"))


def stage(config: dict, workload: dict, seed: int, mode: str, fault: str,
          workdir: str):
    """One trial in this process. The reference's own (``f32``, no
    fault) is saved under ``workdir``; any other is compared with it
    and held to the workload's limits: returns every number's value
    under its name, ``dparam_gap``'s ``leaf``, the other numbers'
    ``notes`` and ``correct``, else None."""
    import numpy as np

    import compare
    from harness import load_module

    reference = load_module("reference", config["reference"])
    data = load_module("data", config["data"]["generator"])
    dims = reference.dims_of(config)
    knobs = dict(config["knobs"], **workload["job"]["fixed"])
    per_dispatch = int(knobs["steps_per_dispatch"])
    ids, _ = data.streams(seed, vocab_size=dims["v"],
                          n_train=int(config["data"]["n_train"]), n_val=8,
                          branching=int(config["data"].get("branching", 4)))
    first, final, losses = reference.train(
        ids, seed, dims, config["recipe"],
        steps=int(knobs["train_steps"]), batch=int(knobs["batch_size"]),
        per_dispatch=per_dispatch,
        learning_rate=float(knobs["learning_rate"]), mode=mode, fault=fault,
        host_dtype=np.float32)
    own = not fault and mode == "f32"
    if own:
        for name, tree in (("first", first), ("final", final)):
            np.savez(os.path.join(workdir, name + ".npz"), **tree)
        np.save(os.path.join(workdir, "losses.npy"), losses)
        out = None
    else:
        del first
        numbers = compare.trial_numbers(
            compare.chunk_means(losses, per_dispatch),
            np.load(os.path.join(workdir, "losses.npy")), per_dispatch,
            final, dict(np.load(os.path.join(workdir, "final.npz"))),
            dict(np.load(os.path.join(workdir, "first.npz"))),
            dims["layers"], **compare.kinds_of(reference))
        out = {name: number["value"] for name, number in numbers.items()}
        out["correct"] = compare.judge(numbers,
                                       compare.limits_of(workload))[1]
        out["leaf"] = numbers["dparam_gap"]["leaf"]
        out["notes"] = {
            name: {k: v for k, v in number.items() if k != "value"}
            for name, number in numbers.items()
            if name != "dparam_gap" and len(number) > 1}
    print(f"[control] seed {seed} {fault or mode}: host peak "
          f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.1f}"
          f" GiB", file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    import compare
    from harness import ROOT, load_json

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--stage", default="", help="internal: "
                        "<mode>,<fault> of the one trial this child runs; "
                        "its exit code is 1 where it reads correct")
    args = parser.parse_args(argv)
    workload, _ = load_json("workloads", args.workload)
    config, _ = load_json("configs", workload["config"])
    workdir = os.path.join(ROOT, ".bench_work", "control_" + args.workload)
    if args.stage:
        mode, fault = args.stage.split(",")
        (seed,) = args.seeds
        numbers = stage(config, workload, seed % 2147483647, mode, fault,
                        workdir)
        if numbers is None:
            return 0
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "variant": fault or mode, **numbers,
                          "limits": compare.limits_of(workload)}),
              flush=True)
        return int(numbers["correct"])
    env = dict(os.environ)
    if env.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    let_through = 0
    for seed in args.seeds:
        shutil.rmtree(workdir, ignore_errors=True)
        os.makedirs(workdir)
        try:
            for mode, fault in (("f32", ""),) + VARIANTS:
                child = subprocess.run(
                    [sys.executable, os.path.abspath(__file__),
                     "--workload", args.workload, "--seeds", str(seed),
                     "--stage", f"{mode},{fault}"], env=env)
                if child.returncode not in (0, 1):
                    return child.returncode
                let_through += child.returncode
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    return 1 if let_through else 0


if __name__ == "__main__":
    sys.exit(main())
