"""Model step: the whole traced slice's share of the chip's bf16 peak,
for the latent-attention + sparse-expert LM. flops_moe.py's USEFUL
FLOPs of the optimizer steps the device ran in the slice (routed
experts by the assignments really held: moe_load_imbalance.py's
whole-run mean; attention at 192 / 128 lanes, not the 256 it is padded
to) / (slice seconds x peak x chips). Idle time is inside. The steps
are the train program's executions (step_ms.py) x steps_per_dispatch
(moe_expert_roofline.steps_in_slice)."""

import flops_moe
from harness import load_module


def read(run):
    steps = load_module("metrics", "moe_expert_roofline").steps_in_slice(run)
    held = load_module("metrics", "moe_load_imbalance").held_per_step(run)
    if not steps or held is None or not run["peaks"]:
        return None
    work = steps * flops_moe.train_step_flops(
        flops_moe.dims(run["knobs"]), held)
    peak = run["peaks"]["flops_per_s_bf16"] * run["chips"]
    return 100.0 * work / (run["trace"]["window_s"] * peak)
