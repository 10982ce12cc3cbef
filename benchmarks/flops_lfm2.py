"""Work counts of the LFM2-family hybrid LM (gated short convolutions
and grouped-query attention in one layer pattern, one chip's share of
the experts), from a run's knobs alone: what the algorithm needs, never
what a kernel did. Padding (64-lane heads ride through the flash
kernels at 128 lanes), recomputation (remat, the kernels' regenerated
softmax) and the rows a block of sorted assignments leaves empty are
not counted.

``knobs`` everywhere is ``run["knobs"]``: the checked trial's knobs, as
``rafiki_tpu/models/lm_lfm2.py`` names them. The routed experts' work
follows the assignments REALLY routed to held experts
(``held_per_step``, from the program's counter); without it, the
expected k x held / experts a token.

Self-check: ``python benchmarks/flops_lfm2.py`` compares
``train_step_flops`` with ``JaxLfm2MoeLM._flops_per_step`` at every
configuration under ``configs/`` and ``selftest/configs/`` that names
the reference ``lfm2_moe``.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def dims(knobs: dict) -> dict:
    k = knobs
    s = dict(
        d=int(k["d_model"]), h=int(k["n_heads"]), hk=int(k["n_kv_heads"]),
        pattern=tuple(k["layer_types"]), dense=int(k["n_dense_layers"]),
        taps=int(k["conv_taps"]), t=int(k["seq_len"]),
        v=int(k["vocab_size"]), ffn=int(k["ffn_dense"]),
        moe_ffn=int(k["ffn_expert"]), experts=int(k["n_experts"]),
        k=int(k["experts_per_token"]), held=int(k["experts_held"]),
        batch=int(k["batch_size"]))
    s["hd"] = s["d"] // s["h"]
    s["n_attn"] = sum(op == "full_attention" for op in s["pattern"])
    s["n_conv"] = len(s["pattern"]) - s["n_attn"]
    s["n_sparse"] = len(s["pattern"]) - s["dense"]
    return s


def conv_params(s: dict) -> int:
    """A gated short convolution: the input projection to b, c, u, the
    filter's taps (one multiply-add each a token and channel, counted
    as a parameter of a product is) and the output projection."""
    return 3 * s["d"] ** 2 + s["taps"] * s["d"] + s["d"] ** 2


def attention_params(s: dict) -> int:
    """Grouped-query attention's four projections: q and o at h heads,
    k and v at hk."""
    return 2 * s["d"] * s["hd"] * (s["h"] + s["hk"])


def expert_params(s: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * s["d"] * s["moe_ffn"]


def expected_held_per_step(s: dict) -> float:
    """Assignments to held experts a step under uniform routing, over
    every sparse block."""
    return s["n_sparse"] * s["batch"] * s["t"] * s["k"] * s["held"] \
        / s["experts"]


def dense_matmul_flops(s: dict) -> float:
    """6 x matmul parameters a token touches outside the routed
    experts: both operators' projections, the dense feed-forward, the
    routers, the tied head. The embedding gather is not a matmul."""
    per_token = (s["n_conv"] * conv_params(s)
                 + s["n_attn"] * attention_params(s)
                 + s["dense"] * 3 * s["d"] * s["ffn"]
                 + s["n_sparse"] * s["d"] * s["experts"]
                 + s["v"] * s["d"])
    return 6.0 * per_token * s["batch"] * s["t"]


def attention_matmul_flops(s: dict, n_matmuls: int) -> float:
    """One layer's causal attention: ``n_matmuls`` products of (t x t x
    head size) a QUERY head and row, the causal half of each."""
    return (n_matmuls * 2 * s["batch"] * s["h"] * s["t"] ** 2
            * s["hd"]) / 2


def train_step_flops(s: dict, held_per_step: float = None) -> float:
    """Forward + backward of one optimizer step, useful work only."""
    if held_per_step is None:
        held_per_step = expected_held_per_step(s)
    return (dense_matmul_flops(s) + 6.0 * expert_params(s) * held_per_step
            + s["n_attn"] * attention_matmul_flops(s, 6))


def least_seconds(flops: float, n_bytes: float, peaks: dict):
    by_flops = flops / peaks["flops_per_s_bf16"]
    by_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    return ((by_flops, "compute") if by_flops >= by_bytes
            else (by_bytes, "memory"))


def _io_bytes(s: dict, n_q: int, n_kv: int) -> float:
    """``n_q`` tensors of h heads and ``n_kv`` of hk, each (batch, T,
    head size) in bf16, read or written once."""
    return 2.0 * s["batch"] * s["t"] * s["hd"] * (n_q * s["h"]
                                                  + n_kv * s["hk"])


def attention_fwd_least(s: dict, peaks: dict):
    """One layer's forward: q kᵀ and p v at the head's own lanes; q
    read and o written at h heads, k and v read at hk (a group's block
    read once)."""
    return least_seconds(attention_matmul_flops(s, 2), _io_bytes(s, 2, 2),
                         peaks)


def attention_bwd_least(s: dict, peaks: dict):
    """One layer's backward: dv, dp, dq, dk; q, o, do read and dq
    written at h heads, k, v read and dk, dv written at hk."""
    return least_seconds(attention_matmul_flops(s, 4), _io_bytes(s, 4, 4),
                         peaks)


def experts_least(s: dict, held_per_step: float, peaks: dict):
    """The grouped gate / up / down products of every sparse block of
    one step, forward and backward: 6 x an expert's parameters x the
    assignments held; the held experts' weights (bf16) read once a pass
    and their float32 gradients written once, rows in and out (bf16)."""
    weights = s["n_sparse"] * s["held"] * expert_params(s)
    rows = held_per_step * s["d"]
    n_bytes = 2 * (2 * weights) + 4 * weights + 2 * (2 * rows + 2 * rows)
    return least_seconds(6.0 * expert_params(s) * held_per_step, n_bytes,
                         peaks)


def knobs_of(config: dict) -> dict:
    """The knobs the driver's template pins, from a configuration."""
    knobs = {knob: config[key] for knob, key in config["knob_of"].items()}
    knobs.update(config["knobs"])
    return knobs


def _self_check() -> int:
    import sys

    sys.path.insert(0, os.path.dirname(HERE))
    from rafiki_tpu.models import JaxLfm2MoeLM

    bad = 0
    paths = [os.path.join(root, name)
             for root in (os.path.join(HERE, "configs"),
                          os.path.join(HERE, "selftest", "configs"))
             for name in sorted(os.listdir(root))]
    for path in paths:
        name = os.path.basename(path)
        with open(path) as f:
            config = json.load(f)
        if config.get("reference") != "lfm2_moe":
            continue
        knobs = knobs_of(config)
        theirs = JaxLfm2MoeLM(**knobs)._flops_per_step(
            int(knobs["batch_size"]))
        mine = train_step_flops(dims(knobs))
        ok = abs(mine - theirs) <= 1e-9 * theirs
        print(f"{name}: flops_lfm2.py {mine:.6e}  models/lm_lfm2.py "
              f"{theirs:.6e}  {'ok' if ok else 'DRIFT'}")
        bad += not ok
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(_self_check())
