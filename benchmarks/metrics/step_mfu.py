"""Model step: the whole traced slice's share of the chip's bf16 peak.
flops.py's FLOPs of the optimizer steps the device ran in the slice /
(slice seconds x peak x chips). Idle time is inside: this is what bounds
trials_per_hour. The steps are the train program's executions
(step_ms.py: the two that the slice cuts into count by the share the
trace saw) x ``steps_per_dispatch``."""

import flops
from harness import load_module


def read(run):
    found = load_module("metrics", "step_ms").executions(run)
    if found is None or not run["peaks"]:
        return None
    steps = found[1] * int(run["knobs"]["steps_per_dispatch"])
    work = steps * flops.train_step_flops(run["shapes"])
    peak = run["peaks"]["flops_per_s_bf16"] * run["chips"]
    return 100.0 * work / (run["trace"]["window_s"] * peak)
