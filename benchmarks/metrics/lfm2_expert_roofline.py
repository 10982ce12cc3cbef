"""Kernels: the routed experts' grouped products' share of their
roofline in the LFM2-family hybrid LM, forward and backward together.
The least time a step's products can take (flops_lfm2.experts_least: 6
x an expert's parameters x the assignments really held,
moe_load_imbalance.py's whole-run mean; the held experts' weights read
once a pass and their gradients written once; compute or memory,
whichever is larger: at 1,024 rows an expert it is compute) / the
device time of the ops that do them, per optimizer step of the traced
slice.

``split(run)`` has moe_expert_roofline.py's signature (the program's
expert layer is the same ``ops/moe.py``) at this model's shapes: an
expert LOOP is a ``while`` op whose tuple holds the padded sort buffer
``[tokens x experts_held + BLOCK]``; a PRODUCT is a device op (no
loop, call or conditional) whose text names both a held-expert weight
stack (``[held, d, f]`` or ``[held, f, d]``) and a block of rows
(``[BLOCK, d]`` or ``[BLOCK, f]``); ROUTING outside the loops is any
other device op that names a (tokens, experts), (tokens, k, experts),
(tokens, k | held) or (tokens x held,) array. That reader takes its
shapes from latent-attention knobs, which this model has not."""

import flops_lfm2
import trace_reduce
from harness import load_module


def split(run):
    """{"loops", "products", "outside"}: device seconds in the traced
    slice, or None where the trace holds no expert loop."""
    shared = load_module("metrics", "moe_expert_roofline")
    block = shared.BLOCK
    s = flops_lfm2.dims(run["knobs"])
    n = s["batch"] * s["t"]
    stacks = {(s["held"], s["d"], s["moe_ffn"]),
              (s["held"], s["moe_ffn"], s["d"])}
    rows = {(block, s["d"]), (block, s["moe_ffn"])}
    padded = (n * s["held"] + block,)
    routing = {(n, s["experts"]), (n, s["k"], s["experts"]), (n, s["k"]),
               (n, s["held"]), (n * s["held"],)}
    out = {"loops": 0.0, "products": 0.0, "outside": 0.0}
    for text, op in run["trace"]["ops"].items():
        found = shared.shapes_in(text)
        opcode = (op["short"].split(" ") + [""])[1]
        if opcode in trace_reduce.CONTAINERS:
            if opcode == "while" and padded in found:
                out["loops"] += op["seconds"]
        elif found & stacks and found & rows:
            out["products"] += op["seconds"]
        elif found & routing and padded not in found:
            out["outside"] += op["seconds"]
    return out if out["loops"] > 0 else None


def read(run):
    if not run["trace"] or not run["peaks"] \
            or "layer_types" not in run["knobs"]:
        return None
    parts = split(run)
    steps = load_module("metrics", "moe_expert_roofline").steps_in_slice(run)
    held = load_module("metrics", "moe_load_imbalance").held_per_step(run)
    if not parts or not steps or held is None or parts["products"] <= 0:
        return None
    least, _ = flops_lfm2.experts_least(flops_lfm2.dims(run["knobs"]), held,
                                        run["peaks"])
    return 100.0 * least / (parts["products"] / steps)
