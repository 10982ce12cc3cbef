"""Model step: device time, per optimizer step of the traced slice, of
the gated short convolution's own work in the LFM2-family hybrid LM,
forward and backward (ops/short_conv.py: the gates b ⊙ u and c ⊙ v, the
shifted multiply-adds of the filter's taps, and their gradients), in
milliseconds.

The signature (``seconds(run)``). The convolution multiplies nothing on
the MXU: XLA makes element-wise fusions of it, and a profiler's op
event carries the HLO instruction, not the ``short_conv`` scope the
program traces it under. What tells its ops is the array only the
``conv`` layers have: the input projection's result (b ‖ c ‖ u), (T, 3
x d_model) wide, read by the forward's gates and written by the
backward's. A convolution op is a device op (no loop, call,
conditional or kernel) whose text names an array that ends in (T, 3 x
d_model) and names no projection weight, an array that ends in
(d_model, 3 x d_model), (3 x d_model, d_model) or (d_model, d_model):
an op that names one is the input or output projection's product (with
whatever part of the convolution the compiler fused into it, which is
then not counted: it rides in the product's time)."""

import flops_lfm2
import trace_reduce
from harness import load_module


def seconds(run):
    """Device seconds of the convolution's ops in the traced slice."""
    shapes_in = load_module("metrics", "moe_expert_roofline").shapes_in
    s = flops_lfm2.dims(run["knobs"])
    d, t = s["d"], s["t"]
    gates = {(t, 3 * d), (s["batch"] * t, 3 * d)}
    weights = {(d, 3 * d), (3 * d, d), (d, d)}
    total = 0.0
    for text, op in run["trace"]["ops"].items():
        opcode = (op["short"].split(" ") + [""])[1]
        if opcode in trace_reduce.CONTAINERS or "custom-call" in opcode:
            continue
        tails = {shape[-2:] for shape in shapes_in(text) if len(shape) >= 2}
        if tails & gates and not tails & weights:
            total += op["seconds"]
    return total


def read(run):
    if not run["trace"] or "conv_taps" not in run["knobs"]:
        return None
    steps = load_module("metrics", "moe_expert_roofline").steps_in_slice(run)
    found = seconds(run)
    if not steps or found <= 0:
        return None
    return 1e3 * found / steps
