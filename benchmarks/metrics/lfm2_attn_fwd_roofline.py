"""Kernels: grouped-query attention's forward share of its roofline.
The least time one layer's causal forward can take at the cell's shapes
(flops_lfm2.py: q kᵀ and p v at the head's own 64 lanes over the causal
half; q read and o written at 32 heads, k and v read at 8: a group's
key-value block once; useful work only) / the forward kernel's device
time per call. The kernels pad the 64-lane heads to 128 lanes, so half
of every MXU pass multiplies zeros: that shows here as a low share.

``kernels(run)`` tells the three flash kernels of the grouped-query
calls apart: a ``tpu_custom_call`` whose leading operands are q at
(batch x heads, T, lanes padded to 128) and k, v at (batch x kv heads,
T, the same lanes), in the order (q, k, v) for the forward and (k, v,
q) for dq and dkv; by the ``kernel_metadata`` name the program gives
them (``flash_fwd``, ``flash_dq``, ``flash_dkv``) where the op's text
carries it, else by the count of operands and results. A call at equal
head counts (another model's) has one shape three times and is not
counted. Under remat the forward runs twice a layer and step, and the
evaluation's calls have the same shape: every call counts."""

import re

import flops_lfm2
import trace_reduce

TARGET = 'custom_call_target="tpu_custom_call"'
NAMED = re.compile(r'"kernel"\s*:\s*"(flash_fwd|flash_dq|flash_dkv)"')
BY_SIGNATURE = {(3, 2): "flash_fwd", (6, 1): "flash_dq", (6, 2): "flash_dkv"}


def padded_shapes(s):
    """((batch x heads, T, lanes), (batch x kv heads, T, lanes))."""
    lanes = s["hd"] + (-s["hd"] % 128)
    return ((s["batch"] * s["h"], s["t"], lanes),
            (s["batch"] * s["hk"], s["t"], lanes))


def kernels(run):
    """{kernel name: {"n", "seconds"}} of the flash kernels at the
    cell's grouped-query shape."""
    q, kv = padded_shapes(flops_lfm2.dims(run["knobs"]))
    leading = {"flash_fwd": [q, kv, kv], "flash_dq": [kv, kv, q],
               "flash_dkv": [kv, kv, q]}
    found = {name: {"n": 0, "seconds": 0.0} for name in leading}
    for text, op in run["trace"]["ops"].items():
        if TARGET not in text:
            continue
        flat = re.sub(r"\{[^{}]*\}", "", text.partition(" = ")[2])
        results, _, rest = flat.partition(" custom-call(")
        operands = trace_reduce.SHAPE.findall(
            rest.partition("), custom_call_target")[0])
        results = trace_reduce.SHAPE.findall(results)
        named = NAMED.search(text)
        name = named.group(1) if named else BY_SIGNATURE.get(
            (len(operands), len(results)))
        dims = [tuple(int(d) for d in shape.split(",") if d)
                for _, shape in operands[:3]]
        if name and q != kv and dims == leading[name]:
            found[name]["n"] += op["n"]
            found[name]["seconds"] += op["seconds"]
    return found


def read(run):
    if not run["trace"] or not run["peaks"] \
            or "n_kv_heads" not in run["knobs"]:
        return None
    fwd = kernels(run)["flash_fwd"]
    if not fwd["n"] or fwd["seconds"] <= 0:
        return None
    least, _ = flops_lfm2.attention_fwd_least(
        flops_lfm2.dims(run["knobs"]), run["peaks"])
    return 100.0 * least / (fwd["seconds"] / fwd["n"])
