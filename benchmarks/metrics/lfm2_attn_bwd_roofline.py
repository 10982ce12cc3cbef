"""Kernels: grouped-query attention's backward share of its roofline.
The least time one layer's backward can take (flops_lfm2.py: dv, dp, dq
and dk at the head's own 64 lanes, the causal half; the regenerated
softmax is not counted; q, o, do read and dq written at 32 heads, k, v
read and dk, dv written at 8) / the dq and dkv kernels' device time
together per backward call. The kernels are told apart as
lfm2_attn_fwd_roofline.py says."""

import flops_lfm2
from harness import load_module


def read(run):
    if not run["trace"] or not run["peaks"] \
            or "n_kv_heads" not in run["knobs"]:
        return None
    found = load_module("metrics", "lfm2_attn_fwd_roofline").kernels(run)
    dq, dkv = found["flash_dq"], found["flash_dkv"]
    if not dq["n"] or not dkv["n"]:
        return None
    least, _ = flops_lfm2.attention_bwd_least(
        flops_lfm2.dims(run["knobs"]), run["peaks"])
    return 100.0 * least / (dq["seconds"] / dq["n"]
                            + dkv["seconds"] / dkv["n"])
