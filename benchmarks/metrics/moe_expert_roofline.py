"""Kernels: the routed experts' grouped products' share of their
roofline, forward and backward together. The least time a step's
products can take (flops_moe.experts_least: 6 x an expert's parameters x
the assignments really held, moe_load_imbalance.py's whole-run mean;
the held experts' weights read once a pass and their gradients written
once; compute or memory, whichever is larger) / the device time of the
ops that do them, per optimizer step of the traced slice.

The signature (``split(run)``). The program (ops/moe.py) sorts the
(token, held expert) assignments and runs ``while`` loops over blocks of
BLOCK = 128 sorted rows of one expert each. In the trace:

* an expert LOOP is a ``while`` op whose tuple holds the padded sort
  buffer ``[tokens x experts_held + BLOCK]``;
* a PRODUCT is a device op (no loop, call or conditional) whose text
  names both a held-expert weight stack (``[held, d, f]`` or
  ``[held, f, d]``, any type) and a block of rows (``[BLOCK, d]`` or
  ``[BLOCK, f]``): the gate / up / down products and their four
  backward products, with the dynamic slice of the stack and the
  accumulation into the gradient stack fused in;
* ROUTING outside the loops is any other device op that names a
  (tokens, experts), (tokens, k, experts), (tokens, k | held) or
  (tokens x held,) array: scores, top-k, the chosen mask, gates, counts,
  the sort.

What a loop does besides its products (gathering rows, SwiGLU's
elementwise part, scatter-add, slicing the weights) is the loop's time
less its products': moe_route_ms.py adds it to the routing outside."""

import re

import flops_moe
import trace_reduce
from harness import load_module

BLOCK = 128  # rows of a block of sorted assignments (ops/moe.py)


def shapes_in(text: str):
    flat = re.sub(r"\{[^{}]*\}", "", text)
    return {tuple(int(d) for d in dims.split(",") if d)
            for _, dims in trace_reduce.SHAPE.findall(flat)}


def split(run):
    """{"loops", "products", "outside"}: device seconds in the traced
    slice, or None where the trace holds no expert loop."""
    s = flops_moe.dims(run["knobs"])
    n = s["batch"] * s["t"]
    stacks = {(s["held"], s["d"], s["moe_ffn"]),
              (s["held"], s["moe_ffn"], s["d"])}
    rows = {(BLOCK, s["d"]), (BLOCK, s["moe_ffn"])}
    padded = (n * s["held"] + BLOCK,)
    routing = {(n, s["experts"]), (n, s["k"], s["experts"]), (n, s["k"]),
               (n, s["held"]), (n * s["held"],)}
    out = {"loops": 0.0, "products": 0.0, "outside": 0.0}
    for text, op in run["trace"]["ops"].items():
        found = shapes_in(text)
        opcode = (op["short"].split(" ") + [""])[1]
        if opcode in trace_reduce.CONTAINERS:
            if opcode == "while" and padded in found:
                out["loops"] += op["seconds"]
        elif found & stacks and found & rows:
            out["products"] += op["seconds"]
        elif found & routing and padded not in found:
            out["outside"] += op["seconds"]
    return out if out["loops"] > 0 else None


def steps_in_slice(run):
    found = load_module("metrics", "step_ms").executions(run)
    if found is None:
        return None
    return found[1] * int(run["knobs"]["steps_per_dispatch"])


def read(run):
    if not run["trace"] or not run["peaks"] \
            or "experts_held" not in run["knobs"]:
        return None
    parts, steps = split(run), steps_in_slice(run)
    held = load_module("metrics", "moe_load_imbalance").held_per_step(run)
    if not parts or not steps or held is None or parts["products"] <= 0:
        return None
    least, _ = flops_moe.experts_least(flops_moe.dims(run["knobs"]), held,
                                       run["peaks"])
    return 100.0 * least / (parts["products"] / steps)
