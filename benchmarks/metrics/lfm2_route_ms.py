"""Expert layer of the LFM2-family hybrid LM: device time, per optimizer
step of the traced slice, of what the sparse layers do besides the
routed experts' products: router scores, top-k, the chosen mask and
gates, counts, the sort of the assignments, and inside the expert loops
the gathering of rows, SwiGLU's elementwise part, the scatter-add and
the slicing of the weight stacks. lfm2_expert_roofline.py's ``split``
has the signature: (expert loops - products) + routing ops outside the
loops, in milliseconds."""

from harness import load_module


def read(run):
    if not run["trace"] or "layer_types" not in run["knobs"]:
        return None
    parts = load_module("metrics", "lfm2_expert_roofline").split(run)
    steps = load_module("metrics", "moe_expert_roofline").steps_in_slice(run)
    if not parts or not steps:
        return None
    return 1e3 * (parts["loops"] - parts["products"]
                  + parts["outside"]) / steps
