"""Work counts of the latent-attention + sparse-expert LM (one chip's
share of the experts), from a run's knobs alone: what the algorithm
needs, never what a kernel did. Padding (v rides to q's width through
the flash kernels, 192 lanes to 256), recomputation (remat, the
kernels' regenerated softmax) and the rows a block of sorted
assignments leaves empty are not counted.

``knobs`` everywhere is ``run["knobs"]``: the checked trial's knobs, as
``rafiki_tpu/models/lm_moe.py`` names them. The routed experts' work
follows the assignments REALLY routed to held experts
(``held_per_step``, from the program's counter); without it, the
expected k x held / experts a token.

Self-check: ``python benchmarks/flops_moe.py`` compares
``train_step_flops`` with ``JaxLatentMoELM._flops_per_step`` at every
configuration under ``configs/`` and ``selftest/configs/`` that names
this reference.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def dims(knobs: dict) -> dict:
    k = knobs
    return dict(
        d=int(k["d_model"]), h=int(k["n_heads"]), layers=int(k["n_layers"]),
        dense=int(k["n_dense_layers"]), t=int(k["seq_len"]),
        v=int(k["vocab_size"]), q_rank=int(k["q_lora_rank"]),
        kv_rank=int(k["kv_lora_rank"]), nope=int(k["qk_nope_head_dim"]),
        rope=int(k["qk_rope_head_dim"]), vd=int(k["v_head_dim"]),
        ffn=int(k["ffn_dense"]), moe_ffn=int(k["ffn_expert"]),
        experts=int(k["n_experts"]), k=int(k["experts_per_token"]),
        held=int(k["experts_held"]), shared=int(k["n_shared_experts"]),
        mtp=int(k["mtp_depth"]), batch=int(k["batch_size"]))


def attention_params(s: dict) -> int:
    """MLA's five projections: q down, q up, kv down (+ the shared
    rotary key), kv up, output."""
    return (s["d"] * s["q_rank"]
            + s["q_rank"] * s["h"] * (s["nope"] + s["rope"])
            + s["d"] * (s["kv_rank"] + s["rope"])
            + s["kv_rank"] * s["h"] * (s["nope"] + s["vd"])
            + s["h"] * s["vd"] * s["d"])


def expert_params(s: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * s["d"] * s["moe_ffn"]


def sparse_blocks(s: dict) -> int:
    """Blocks with an expert layer: the sparse stack and the
    multi-token module's."""
    return s["layers"] - s["dense"] + s["mtp"]


def tokens_per_step(s: dict):
    """(tokens through the main stack, through the multi-token module,
    which sees one fewer a row)."""
    return s["batch"] * s["t"], s["mtp"] * s["batch"] * (s["t"] - 1)


def expected_held_per_step(s: dict) -> float:
    """Assignments to held experts a step under uniform routing, over
    every sparse block."""
    main, mtp = tokens_per_step(s)
    return ((s["layers"] - s["dense"]) * main + mtp) * s["k"] \
        * s["held"] / s["experts"]


def dense_matmul_flops(s: dict) -> float:
    """6 x matmul parameters a token touches outside the routed
    experts: attention projections, the dense feed-forward, router,
    shared expert, both heads, the module's joining projection. The
    embedding gather is not a matmul."""
    main, mtp = tokens_per_step(s)
    attn = attention_params(s)
    sparse = attn + s["d"] * s["experts"] + expert_params(s) * s["shared"]
    per_main = (s["dense"] * (attn + 3 * s["d"] * s["ffn"])
                + (s["layers"] - s["dense"]) * sparse + s["v"] * s["d"])
    per_mtp = 2 * s["d"] * s["d"] + sparse + s["v"] * s["d"]
    return 6.0 * (per_main * main + per_mtp * mtp)


def attention_matmul_flops(s: dict, t: int, n_qk: int, n_pv: int) -> float:
    """One layer's causal attention at length ``t``: ``n_qk`` products
    of (t x t x (nope + rope)) and ``n_pv`` of (t x t x v_head_dim) a
    head and row, the causal half of each."""
    return (2 * s["batch"] * s["h"] * t * t
            * (n_qk * (s["nope"] + s["rope"]) + n_pv * s["vd"])) / 2


def train_step_flops(s: dict, held_per_step: float = None) -> float:
    """Forward + backward of one optimizer step, useful work only."""
    if held_per_step is None:
        held_per_step = expected_held_per_step(s)
    attn = s["layers"] * attention_matmul_flops(s, s["t"], 3, 3) \
        + s["mtp"] * attention_matmul_flops(s, s["t"] - 1, 3, 3)
    return (dense_matmul_flops(s) + 6.0 * expert_params(s) * held_per_step
            + attn)


def least_seconds(flops: float, n_bytes: float, peaks: dict):
    by_flops = flops / peaks["flops_per_s_bf16"]
    by_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    return ((by_flops, "compute") if by_flops >= by_bytes
            else (by_bytes, "memory"))


def attention_fwd_least(s: dict, peaks: dict):
    """One layer's forward at length t: q kᵀ over nope + rope lanes and
    p v over v_head_dim; q, k read at the one width, v read and o
    written at the other (bf16)."""
    n_bytes = 2 * s["batch"] * s["h"] * s["t"] * (
        2 * (s["nope"] + s["rope"]) + 2 * s["vd"])
    return least_seconds(attention_matmul_flops(s, s["t"], 1, 1),
                         n_bytes, peaks)


def attention_bwd_least(s: dict, peaks: dict):
    """One layer's backward: dv and dp over v_head_dim, dq and dk over
    nope + rope; q, k, v, o, do read and dq, dk, dv written."""
    n_bytes = 2 * s["batch"] * s["h"] * s["t"] * (
        4 * (s["nope"] + s["rope"]) + 4 * s["vd"])
    return least_seconds(attention_matmul_flops(s, s["t"], 2, 2),
                         n_bytes, peaks)


def experts_least(s: dict, held_per_step: float, peaks: dict):
    """The grouped gate / up / down products of every sparse block of
    one step, forward and backward: 6 x an expert's parameters x the
    assignments held; the held experts' weights (bf16) read once a pass
    and their float32 gradients written once, rows in and out (bf16)."""
    blocks = sparse_blocks(s)
    weights = blocks * s["held"] * expert_params(s)
    rows = held_per_step * s["d"]
    n_bytes = 2 * (2 * weights) + 4 * weights + 2 * (2 * rows + 2 * rows)
    return least_seconds(6.0 * expert_params(s) * held_per_step, n_bytes,
                         peaks)


def _self_check() -> int:
    import sys

    sys.path.insert(0, os.path.dirname(HERE))
    from rafiki_tpu.models import JaxLatentMoELM

    bad = 0
    paths = [os.path.join(root, name)
             for root in (os.path.join(HERE, "configs"),
                          os.path.join(HERE, "selftest", "configs"))
             for name in sorted(os.listdir(root))]
    for path in paths:
        name = os.path.basename(path)
        with open(path) as f:
            config = json.load(f)
        if config.get("reference") != "joyai_flash":
            continue
        knobs = {knob: config[key]
                 for knob, key in config["knob_of"].items()}
        knobs.update(config["knobs"])
        theirs = JaxLatentMoELM(**knobs)._flops_per_step(
            int(knobs["batch_size"]))
        mine = train_step_flops(dims(knobs))
        ok = abs(mine - theirs) <= 1e-9 * theirs
        print(f"{name}: flops_moe.py {mine:.6e}  models/lm_moe.py "
              f"{theirs:.6e}  {'ok' if ok else 'DRIFT'}")
        bad += not ok
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(_self_check())
