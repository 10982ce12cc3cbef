"""Model step: the whole traced slice's share of the chip's bf16 peak,
for the LFM2-family hybrid LM. flops_lfm2.py's USEFUL FLOPs of the
optimizer steps the device ran in the slice (routed experts by the
assignments really held: moe_load_imbalance.py's whole-run mean;
attention at the heads' own 64 lanes, not the 128 they are padded to) /
(slice seconds x peak x chips). Idle time is inside. The steps are the
train program's executions (step_ms.py) x steps_per_dispatch."""

import flops_lfm2
from harness import load_module


def read(run):
    if not run["trace"] or not run["peaks"] \
            or "layer_types" not in run["knobs"]:
        return None
    steps = load_module("metrics", "moe_expert_roofline").steps_in_slice(run)
    held = load_module("metrics", "moe_load_imbalance").held_per_step(run)
    if not steps or held is None:
        return None
    work = steps * flops_lfm2.train_step_flops(
        flops_lfm2.dims(run["knobs"]), held)
    peak = run["peaks"]["flops_per_s_bf16"] * run["chips"]
    return 100.0 * work / (run["trace"]["window_s"] * peak)
