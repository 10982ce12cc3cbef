"""Kernels: causal attention forward's share of its roofline. The least
time one layer's forward can take at the cell's shapes (flops.py: two
matmuls, the causal half; q, k, v read and o written, against
peaks.json) / the forward kernel's device time per call in the trace.
At 4 x 16 x 2048 x 128 the bound is compute.

The kernel carries no name of its own in the trace (the op is called
after the jaxpr that holds it: ``closed_call``, ``rematted_computation``,
``checkpoint``), so it is told by its signature: a ``tpu_custom_call``
that takes q, k, v of (batch x heads, T, head_dim) and gives o of that
shape and the row statistics. Under remat it runs twice a layer and
step, and every call counts. The evaluation's calls, at another batch,
are left out."""

import flops
import trace_reduce

TARGET = "tpu_custom_call"


def qkv_shape(run):
    s = run["shapes"]
    return (s["batch"] * s["heads"], s["t"], s["head_dim"])


def kernel_calls(run, n_operands: int, n_results: int):
    """{"n", "seconds"} of the custom calls with that many operands and
    results whose first operands and first result are q-shaped."""
    q = qkv_shape(run)
    return trace_reduce.total(
        call for call in trace_reduce.custom_calls(run["trace"], TARGET)
        if len(call["operands"]) == n_operands
        and len(call["results"]) == n_results
        and all(shape == q for _, shape in call["operands"][:3])
        and call["results"][0][1] == q)


def read(run):
    if not run["trace"] or not run["peaks"]:
        return None
    found = kernel_calls(run, 3, 2)
    if not found["n"] or found["seconds"] <= 0:
        return None
    least, _ = flops.attention_fwd_least(run["shapes"], run["peaks"])
    return 100.0 * least / (found["seconds"] / found["n"])
