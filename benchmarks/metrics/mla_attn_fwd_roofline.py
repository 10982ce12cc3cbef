"""Kernels: latent attention's forward share of its roofline. The least
time one layer's causal forward can take at the cell's shapes
(flops_moe.py: q kᵀ over nope + rope = 192 lanes, p v over v_head_dim =
128; q, k, v read and o written at those widths; useful work only) /
the forward kernel's device time per call. The program pads v to 192
and the kernel pads both to 256 lanes, which shows here as a low share.

``kernels(run)`` tells the three flash kernels apart: by the
``kernel_metadata`` name the program gives them (``flash_fwd``,
``flash_dq``, ``flash_dkv``) where the op's text carries it, else by
signature: a ``tpu_custom_call`` whose first three operands are
(batch x heads, T, 256-padded head) with 3 operands and (o, stats) =
forward, 6 operands and 1 / 2 results of that shape = dq / dkv. Under
remat the forward runs twice a layer and step, and the evaluation's
calls have the same shape: every call counts."""

import re

import flops_moe
import trace_reduce

TARGET = 'custom_call_target="tpu_custom_call"'
NAMED = re.compile(r'"kernel"\s*:\s*"(flash_fwd|flash_dq|flash_dkv)"')
BY_SIGNATURE = {(3, 2): "flash_fwd", (6, 1): "flash_dq", (6, 2): "flash_dkv"}


def padded_shape(s):
    lanes = s["nope"] + s["rope"]
    return (s["batch"] * s["h"], s["t"], lanes + (-lanes % 128))


def kernels(run):
    """{kernel name: {"n", "seconds"}} of the flash kernels at the
    cell's attention shape."""
    want = padded_shape(flops_moe.dims(run["knobs"]))
    found = {name: {"n": 0, "seconds": 0.0}
             for name in BY_SIGNATURE.values()}
    for text, op in run["trace"]["ops"].items():
        if TARGET not in text:
            continue
        flat = re.sub(r"\{[^{}]*\}", "", text.partition(" = ")[2])
        results, _, rest = flat.partition(" custom-call(")
        operands = trace_reduce.SHAPE.findall(
            rest.partition("), custom_call_target")[0])
        results = trace_reduce.SHAPE.findall(results)
        dims = [tuple(int(d) for d in shape.split(",") if d)
                for _, shape in operands[:3]]
        if dims != [want] * 3:
            continue
        named = NAMED.search(text)
        name = named.group(1) if named else BY_SIGNATURE.get(
            (len(operands), len(results)))
        if name:
            found[name]["n"] += op["n"]
            found[name]["seconds"] += op["seconds"]
    return found


def read(run):
    if not run["trace"] or not run["peaks"] \
            or "qk_nope_head_dim" not in run["knobs"]:
        return None
    fwd = kernels(run)["flash_fwd"]
    if not fwd["n"] or fwd["seconds"] <= 0:
        return None
    least, _ = flops_moe.attention_fwd_least(
        flops_moe.dims(run["knobs"]), run["peaks"])
    return 100.0 * least / (fwd["seconds"] / fwd["n"])
