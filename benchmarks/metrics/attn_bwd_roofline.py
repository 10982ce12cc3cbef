"""Kernels: causal attention backward's share of its roofline. The least
time one layer's backward can take (flops.py: the four matmuls it needs,
the causal half; the kernels' regenerated softmax is not counted; q, k,
v, o, do read and dq, dk, dv written) / the dq and dkv kernels' device
time together per backward call. At 4 x 16 x 2048 x 128 the bound is
compute.

Both kernels are told by their signature, as the forward is
(attn_fwd_roofline.py): ``tpu_custom_call``s that take q, k, v, do and
two rows of statistics; the one that gives one q-shaped result is dq,
the one that gives two is dkv."""

import flops
from harness import load_module


def read(run):
    if not run["trace"] or not run["peaks"]:
        return None
    kernel_calls = load_module("metrics", "attn_fwd_roofline").kernel_calls
    dq = kernel_calls(run, 6, 1)
    dkv = kernel_calls(run, 6, 2)
    if not dq["n"] or not dkv["n"]:
        return None
    # Per call of each: the slice's edges can fall between the two
    # kernels of one layer, so their counts may differ by one.
    least, _ = flops.attention_bwd_least(run["shapes"], run["peaks"])
    return 100.0 * least / (dq["seconds"] / dq["n"]
                            + dkv["seconds"] / dkv["n"])
