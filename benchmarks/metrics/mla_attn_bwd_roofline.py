"""Kernels: latent attention's backward share of its roofline. The
least time one layer's backward can take (flops_moe.py: dv and dp over
128 lanes, dq and dk over 192, the causal half; the regenerated softmax
is not counted; q, k, v, o, do read and dq, dk, dv written) / the dq
and dkv kernels' device time together per backward call. The kernels
are told apart as mla_attn_fwd_roofline.py says."""

import flops_moe
from harness import load_module


def read(run):
    if not run["trace"] or not run["peaks"] \
            or "qk_nope_head_dim" not in run["knobs"]:
        return None
    found = load_module("metrics", "mla_attn_fwd_roofline").kernels(run)
    dq, dkv = found["flash_dq"], found["flash_dkv"]
    if not dq["n"] or not dkv["n"]:
        return None
    least, _ = flops_moe.attention_bwd_least(
        flops_moe.dims(run["knobs"]), run["peaks"])
    return 100.0 * least / (dq["seconds"] / dq["n"]
                            + dkv["seconds"] / dkv["n"])
